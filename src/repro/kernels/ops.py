"""Public jit'd wrappers for the Pallas kernels.

Model code calls these (when `attn_impl == 'pallas'` / `sampler_impl ==
'pallas'`); the layout adapters translate between model-layout tensors and
kernel-layout tensors.  `interpret=None` resolves through
`inverse_cdf.interpret_default`: compiled Mosaic kernels on TPU, the
interpreter elsewhere (CPU cannot lower Mosaic).

Autodiff: each kernel carries a custom_vjp.  Forward runs the Pallas
kernel; backward of `inverse_cdf` uses the closed-form partials, while the
attention / SSD backwards fall back to the jnp reference VJP (a fused
backward kernel is a listed future optimization — the forward is where the
paper-relevant memory savings live).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention as _flash
from .ssd_scan import ssd_scan as _ssd
from .inverse_cdf import inverse_cdf as _icdf
from .inverse_cdf import fold_channels as _fold_channels
from .imaging import blur2d as _blur2d
from .imaging import mask_apply as _mask_apply
from . import ref

# ----------------------------------------------------------------------------
# flash attention (model layout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True, window: Optional[int] = None):
    """Model layout: q [B,S,KV,G,hd], k/v [B,S,KV,hd] -> [B,S,KV,G,hd]."""
    B, S, KV, G, hd = q.shape
    qk = q.reshape(B, S, KV * G, hd).transpose(0, 2, 1, 3)   # [B,H,S,hd]
    kk = k.transpose(0, 2, 1, 3)                             # [B,KV,S,hd]
    vk = v.transpose(0, 2, 1, 3)
    o = _flash(qk, kk, vk, causal=causal, window=window)
    return o.transpose(0, 2, 1, 3).reshape(B, S, KV, G, hd)


def _ref_attention(q, k, v, causal, window):
    B, S, KV, G, hd = q.shape
    qk = q.reshape(B, S, KV * G, hd).transpose(0, 2, 1, 3)
    o = ref.flash_attention_ref(qk, k.transpose(0, 2, 1, 3),
                                v.transpose(0, 2, 1, 3), causal, window)
    return o.transpose(0, 2, 1, 3).reshape(B, S, KV, G, hd)


def _flash_fwd(q, k, v, causal, window):
    return flash_attention(q, k, v, causal, window), (q, k, v)


def _flash_bwd(causal, window, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: _ref_attention(q_, k_, v_, causal, window),
                     q, k, v)
    return vjp(g)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ----------------------------------------------------------------------------
# SSD scan


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def ssd_scan(x, dt, A, Bc, Cc, chunk: int = 64):
    """Model layout (see repro.models.ssm.run_ssm)."""
    return _ssd(x, dt, A, Bc, Cc, chunk=chunk)


def _ssd_fwd(x, dt, A, Bc, Cc, chunk):
    return ssd_scan(x, dt, A, Bc, Cc, chunk), (x, dt, A, Bc, Cc)


def _ssd_bwd(chunk, res, g):
    x, dt, A, Bc, Cc = res
    _, vjp = jax.vjp(ref.ssd_scan_ref, x, dt, A, Bc, Cc)
    return vjp(g)


ssd_scan.defvjp(_ssd_fwd, _ssd_bwd)


# ----------------------------------------------------------------------------
# inverse CDF sampler


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def inverse_cdf(u, mu, s, k, interpret: Optional[bool] = None):
    """Pipeline layout: u [K,E]; mu/s/k [K].  interpret=None auto-selects
    per backend."""
    return _icdf(u, mu, s, k, interpret=interpret)


def _icdf_fwd(u, mu, s, k, interpret):
    return inverse_cdf(u, mu, s, k, interpret), (u, s, k)


def _icdf_bwd(interpret, res, g):
    u, s, k = res
    uc = jnp.clip(u.astype(jnp.float32), 1e-6, 1 - 1e-6)
    gf = g.astype(jnp.float32)
    logit = jnp.log(uc / (1 - uc))
    du = gf * (s[:, None] / (uc * (1 - uc)) + k[:, None])
    dmu = gf.sum(axis=1)
    ds = (gf * logit).sum(axis=1)
    dk = (gf * (uc - 0.5)).sum(axis=1)
    return (du.astype(u.dtype), dmu.astype(u.dtype),
            ds.astype(u.dtype), dk.astype(u.dtype))


inverse_cdf.defvjp(_icdf_fwd, _icdf_bwd)


# ----------------------------------------------------------------------------
# imaging forward operators (linear: closed-form adjoints, see
# kernels/imaging.py — the mask is diagonal, the blur self-adjoint)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def mask_apply(x, m, interpret: Optional[bool] = None):
    """Inpainting occlusion: x [K, P] * m [P].  interpret=None auto-selects
    per backend."""
    return _mask_apply(x, m, interpret=interpret)


def _mask_fwd(x, m, interpret):
    return mask_apply(x, m, interpret), (x, m)


def _mask_bwd(interpret, res, g):
    x, m = res
    gf = g.astype(jnp.float32)
    dx = gf * m.astype(jnp.float32)[None, :]        # diagonal adjoint
    dm = (gf * x.astype(jnp.float32)).sum(axis=0)
    return dx.astype(x.dtype), dm.astype(m.dtype)


mask_apply.defvjp(_mask_fwd, _mask_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def blur2d(x, interpret: Optional[bool] = None):
    """Separable 3-tap zero-boundary blur: x [K, H, W] -> [K, H, W].
    interpret=None auto-selects per backend."""
    return _blur2d(x, interpret=interpret)


def _blur_fwd(x, interpret):
    return blur2d(x, interpret), None


def _blur_bwd(interpret, res, g):
    # the blur matrix is symmetric (zero boundary, symmetric taps), so the
    # adjoint is the forward kernel itself — the backward pass stays on the
    # Pallas path instead of re-deriving a jnp VJP
    return (blur2d(g, interpret),)


blur2d.defvjp(_blur_fwd, _blur_bwd)


def inverse_cdf_channels(u, mu, s, k, interpret: Optional[bool] = None):
    """Multi-channel problem layout: u [K, E, C]; mu/s/k [K, C] -> [K, E, C].

    One fused kernel launch for all observable channels (folded into the
    param-row axis — `kernels.inverse_cdf.fold_channels`); gradients ride
    the closed-form custom VJP of the single-channel `inverse_cdf` through
    the differentiable fold reshapes.
    """
    return _fold_channels(inverse_cdf, u, mu, s, k, interpret)
