"""Pallas TPU kernel for the SAGIPS inverse-CDF event sampler.

The paper names the stochastic event sampler as the workflow's compute hot
spot (§I item 2; §IV-B3 reports up to ~1 min/epoch for a pipeline
prototype).  The transform itself is elementwise over (param-sample, event)
pairs — a pure VPU workload:

    y = mu + s * log(u / (1-u)) + k * (u - 0.5)

Tiling: (block_k param rows) x (block_e events) per grid step; the three
per-row parameter vectors ride along as (block_k, 1) blocks broadcast across
the event lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _icdf_kernel(u_ref, mu_ref, s_ref, k_ref, y_ref):
    u = jnp.clip(u_ref[...].astype(jnp.float32), 1e-6, 1.0 - 1e-6)
    mu = mu_ref[...].astype(jnp.float32)          # [bk, 1]
    s = s_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    y = mu + s * jnp.log(u / (1.0 - u)) + k * (u - 0.5)
    y_ref[...] = y.astype(y_ref.dtype)


def interpret_default() -> bool:
    """The one interpret-mode selector for every kernel in this package:
    `interpret=None` means the interpreter only off-TPU; on a TPU runtime
    each kernel compiles to a real Mosaic kernel."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("block_k", "block_e", "interpret"))
def inverse_cdf(u, mu, s, k, block_k: int = 256, block_e: int = 128,
                interpret: bool | None = None):
    """u [K, E] uniforms; mu/s/k [K] per-row parameters. Returns y [K, E].

    interpret=None auto-selects: compiled Mosaic kernel on TPU, interpreter
    elsewhere (CPU hosts cannot lower Mosaic)."""
    if interpret is None:
        interpret = interpret_default()
    K, E = u.shape
    bk, be = min(block_k, K), min(block_e, E)
    padK = (-K) % bk
    padE = (-E) % be
    if padK or padE:
        u = jnp.pad(u, ((0, padK), (0, padE)), constant_values=0.5)
        mu = jnp.pad(mu, (0, padK))
        s = jnp.pad(s, (0, padK))
        k = jnp.pad(k, (0, padK))
    Kp, Ep = u.shape
    grid = (Kp // bk, Ep // be)
    col = lambda ki, ei: (ki, 0)
    y = pl.pallas_call(
        _icdf_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, be), lambda ki, ei: (ki, ei)),
            pl.BlockSpec((bk, 1), col),
            pl.BlockSpec((bk, 1), col),
            pl.BlockSpec((bk, 1), col),
        ],
        out_specs=pl.BlockSpec((bk, be), lambda ki, ei: (ki, ei)),
        out_shape=jax.ShapeDtypeStruct((Kp, Ep), u.dtype),
        interpret=interpret,
    )(u, mu[:, None], s[:, None], k[:, None])
    return y[:K, :E]


def fold_channels(icdf_fn, u, mu, s, k, *args, **kwargs):
    """Shape-polymorphic multi-channel dispatch: u [K, E, C]; mu/s/k [K, C].

    Folds the C observable channels into the param-row axis ([K, E, C] ->
    [K*C, E]) so ONE launch of the single-channel sampler `icdf_fn` covers
    every channel — the grid tiling is identical, just over C-times as many
    rows.  Pass the raw kernel (`inverse_cdf` here) or `kernels.ops.
    inverse_cdf` to ride its custom VJP through the (differentiable) fold
    reshapes.  Extra args forward to `icdf_fn`.  Returns y [K, E, C].
    """
    K, E, C = u.shape
    uf = jnp.moveaxis(u, -1, 1).reshape(K * C, E)
    y = icdf_fn(uf, mu.reshape(K * C), s.reshape(K * C), k.reshape(K * C),
                *args, **kwargs)
    return jnp.moveaxis(y.reshape(K, C, E), 1, -1)


def inverse_cdf_channels(u, mu, s, k, *, block_k: int = 256,
                         block_e: int = 128, interpret: bool | None = None):
    """Raw-kernel multi-channel dispatch (no autodiff wrapper; for gradient
    flow use `kernels.ops.inverse_cdf_channels`).  Options are keyword-only
    — the differentiable sibling takes `interpret` as its 5th positional
    arg, and silently binding that to `block_k` here would be a trap."""
    return fold_channels(inverse_cdf, u, mu, s, k,
                         block_k=block_k, block_e=block_e, interpret=interpret)
