"""Plain reference of the paper's 1D proxy app (arXiv 2407.00051, §V):
six parameters in (0, 1) map affinely to (mu, s, k) of two observables,
and each event is y = mu + s * log(u / (1 - u)) + k * (u - 0.5) of a
uniform u clipped to [1e-6, 1 - 1e-6]."""
from __future__ import annotations

import jax
import jax.numpy as jnp

N_PARAMS = 6
OBS_DIM = 2
NOISE_CHANNELS = 2
EVENTS_PER_SAMPLE = 100          # of the reference data set (Tab. III)
TRUTH = (0.35, 0.62, 0.48, 0.71, 0.26, 0.55)
MU, S, K = (-2.0, 2.0), (0.05, 1.0), (-1.0, 1.0)


def _inv_cdf(u, mu, s, k):
    u = jnp.clip(u, 1e-6, 1.0 - 1e-6)
    return mu + s * jnp.log(u / (1.0 - u)) + k * (u - 0.5)


def forward(params, u):
    """params [n, 6], u [n, E, 2] -> events [n * E, 2]."""
    aff = lambda p, r: r[0] + (r[1] - r[0]) * p
    ys = []
    for j in range(2):
        mu = aff(params[:, 3 * j], MU)[:, None]
        s = aff(params[:, 3 * j + 1], S)[:, None]
        k = aff(params[:, 3 * j + 2], K)[:, None]
        ys.append(_inv_cdf(u[:, :, j], mu, s, k))
    return jnp.stack(ys, axis=-1).reshape(-1, OBS_DIM)


def truth():
    return jnp.asarray(TRUTH, jnp.float32)


def reference_data(key, n_events: int, params=None):
    """Events at the truth (or `params`): ceil(n / 100) draws of 100."""
    params = truth() if params is None else params
    n = -(-n_events // EVENTS_PER_SAMPLE)
    u = jax.random.uniform(key, (n, EVENTS_PER_SAMPLE, NOISE_CHANNELS))
    return forward(jnp.tile(params[None, :], (n, 1)), u)[:n_events]
