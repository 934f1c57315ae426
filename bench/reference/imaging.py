"""Plain reference of the 32x32 inpainting problem: the field is the
parameter image with a 12x12 box (rows 10-21, columns 8-19) occluded; an
event reads one pixel chosen by u0, gives its position (row, column and
their sine and cosine at 1, 2 and 4 cycles per image) and the field there
plus logistic noise of scale 0.05 driven by u1."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

H = W = 32
N_PARAMS = H * W
SIGMA = 0.05
FREQS = (1.0, 2.0, 4.0)
OBS_DIM = 3 + 4 * len(FREQS)
NOISE_CHANNELS = 2
EVENTS_PER_SAMPLE = 100


def truth():
    """Two Gaussian blobs on a 0.2 floor, in [0.2, 0.85]."""
    r, c = np.mgrid[0:H, 0:W].astype(np.float64)
    g1 = np.exp(-((r - 11.0) ** 2 + (c - 13.0) ** 2) / (2.0 * 4.0 ** 2))
    g2 = np.exp(-((r - 22.0) ** 2 + (c - 20.0) ** 2) / (2.0 * 5.5 ** 2))
    img = 0.2 + 0.65 * np.clip(0.9 * g1 + 0.8 * g2, 0.0, 1.0)
    return jnp.asarray(img.reshape(-1), jnp.float32)


def _mask():
    m = np.ones((H, W), np.float32)
    m[10:22, 8:20] = 0.0
    return jnp.asarray(m.reshape(-1))


def forward(params, u):
    """params [n, 1024], u [n, E, 2] -> events [n * E, 15]."""
    field = params * _mask()
    idx = jnp.clip((u[..., 0] * N_PARAMS).astype(jnp.int32), 0, N_PARAMS - 1)
    value = jnp.take_along_axis(field, idx, axis=1)
    u1 = jnp.clip(u[..., 1], 1e-6, 1.0 - 1e-6)
    noise = SIGMA * jnp.log(u1 / (1.0 - u1))
    row = (idx // W) / (H - 1.0)
    col = (idx % W) / (W - 1.0)
    feats = [row, col]
    for f in FREQS:
        for p in (row, col):
            feats += [jnp.sin(2.0 * math.pi * f * p),
                      jnp.cos(2.0 * math.pi * f * p)]
    feats.append(value + noise)
    return jnp.stack(feats, axis=-1).reshape(-1, OBS_DIM)


def reference_data(key, n_events: int, params=None):
    params = truth() if params is None else params
    n = -(-n_events // EVENTS_PER_SAMPLE)
    u = jax.random.uniform(key, (n, EVENTS_PER_SAMPLE, NOISE_CHANNELS))
    return forward(jnp.tile(params[None, :], (n, 1)), u)[:n_events]
