"""Pallas TPU flash attention (causal / sliding-window, GQA-aware).

Blockwise online-softmax: grid (B, H, nQ, nK) with the KV-block loop as the
innermost grid dimension; running max / denominator / accumulator live in
VMEM scratch and persist across that dimension (the canonical TPU flash
schedule — the MXU consumes (block_q x hd) @ (hd x block_k) tiles while the
running statistics stay resident in VMEM, so HBM traffic is O(S) per row
instead of O(S^2)).

GQA is handled in the index maps: KV blocks are fetched for head h // G, so
repeated heads are never materialized in HBM or VMEM.

Sliding-window masking makes the same kernel serve the `long_500k`
sub-quadratic configs.  Blocks fully outside the causal/window band
contribute nothing; on real hardware those grid steps are pruned by the
mask's zero contribution (a future optimization could skip them via
`pltpu.PrefetchScalarGridSpec`).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .inverse_cdf import interpret_default

NEG_INF = -1e30
LANES = 128    # TPU vreg lane width; scratch rows are lane-replicated


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, block_q, block_k, n_kv_blocks, causal, window):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                  # [bq, hd]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bk, hd]
    v = v_ref[0, 0].astype(jnp.float32)                  # [bk, hd]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = jnp.ones(s.shape, jnp.bool_)
    if causal:
        ok &= k_pos <= q_pos
    if window is not None:
        ok &= k_pos > q_pos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[:, :1]                                # [bq, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                               # [bq, bk]
    corr = jnp.exp(m_prev - m_new)                       # [bq, 1]

    l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
    acc_scr[...] = acc

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, causal: bool = True,
                    window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """q [B,H,Sq,hd]; k/v [B,KV,Sk,hd].  Returns [B,H,Sq,hd].

    interpret=None auto-selects: compiled Mosaic kernel on TPU, interpreter
    elsewhere (CPU hosts cannot lower Mosaic)."""
    if interpret is None:
        interpret = interpret_default()
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=bq, block_k=bk,
        n_kv_blocks=nk, causal=causal, window=window)

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),   # running max (lane-replicated)
            pltpu.VMEM((bq, LANES), jnp.float32),   # running denominator
            pltpu.VMEM((bq, hd), jnp.float32),      # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
