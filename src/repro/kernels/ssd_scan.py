"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

Grid (B, H, n_chunks): chunks are the innermost dimension; the inter-chunk
SSM state [P, N] lives in VMEM scratch and is carried across chunk steps —
the TPU-native shape of the recurrence (the GPU reference implementation
spreads chunks over SMs and does a separate state-passing pass; on TPU the
sequential grid walk with a resident VMEM carry is both simpler and avoids
the extra HBM round-trip for inter-chunk states).

Per chunk of length Q the kernel computes (fp32):
    seg   = cumsum(dt * A)                         (within-chunk log-decay)
    y     = (C B^T ⊙ L) (dt ⊙ x)   + C seg-decayed state   (intra + inter)
    state = chunk_decay * state + B^T (end-decay ⊙ dt ⊙ x)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .inverse_cdf import interpret_default


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_scr, *,
                chunk):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, :, 0, :].astype(jnp.float32)          # [Q, P]
    dt = dt_ref[0, :, 0].astype(jnp.float32)           # [Q]
    a = a_ref[0]                                       # scalar A_h (negative)
    bmat = b_ref[0].astype(jnp.float32)                # [Q, N]
    cmat = c_ref[0].astype(jnp.float32)                # [Q, N]

    dA = dt * a                                        # [Q] <= 0
    seg = jnp.cumsum(dA)                               # [Q]
    xw = x * dt[:, None]                               # dt-weighted input

    # intra-chunk: L[i,j] = exp(seg_i - seg_j) for j<=i (mask BEFORE exp)
    rel = seg[:, None] - seg[None, :]
    causal = jax.lax.broadcasted_iota(jnp.int32, rel.shape, 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, rel.shape, 1)
    L = jnp.exp(jnp.where(causal, rel, -jnp.inf))      # [Q, Q]
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, Q]
    y = jax.lax.dot_general(cb * L, xw, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [Q, P]

    # inter-chunk: contribution of the carried state
    state = state_scr[...]                             # [P, N]
    decay_in = jnp.exp(seg)[:, None]                   # [Q, 1]
    y += jax.lax.dot_general(cmat * decay_in, state,
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q, P]

    # state update
    decay_out = jnp.exp(seg[-1] - seg)[:, None]        # [Q, 1]
    new_part = jax.lax.dot_general(xw * decay_out, bmat,
                                   (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)  # [P, N]
    state_scr[...] = jnp.exp(seg[-1]) * state + new_part

    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bc, Cc, chunk: int = 64,
             interpret: bool | None = None):
    """x [B,S,H,P]; dt [B,S,H] (post-softplus); A [H]; Bc/Cc [B,S,N].

    Returns y [B,S,H,P] (without the D*x skip term).  interpret=None
    auto-selects: compiled Mosaic kernel on TPU, interpreter elsewhere.
    """
    if interpret is None:
        interpret = interpret_default()
    B, S, H, P = x.shape
    N = Bc.shape[-1]
    Q = min(chunk, S)
    S0 = S
    if S % Q:
        pad = Q - S % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bc = jnp.pad(Bc, ((0, 0), (0, pad), (0, 0)))
        Cc = jnp.pad(Cc, ((0, 0), (0, pad), (0, 0)))
        S = S + pad
    nc = S // Q

    kernel = functools.partial(_ssd_kernel, chunk=Q)
    y = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, Q, 1, P), lambda b, h, ci: (b, ci, h, 0)),
            pl.BlockSpec((1, Q, 1), lambda b, h, ci: (b, ci, h)),
            pl.BlockSpec((1,), lambda b, h, ci: (h,)),
            pl.BlockSpec((1, Q, N), lambda b, h, ci: (b, ci, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, ci: (b, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, 1, P), lambda b, h, ci: (b, ci, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, dt, A.astype(jnp.float32), Bc, Cc)
    return y[:, :S0]
