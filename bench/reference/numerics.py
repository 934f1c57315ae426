"""The arithmetic of the plain references, in one of two precisions.

    "highest"   float32 at `Precision.HIGHEST`: what the configurations
                state their results against
    "fp8"       the control: every matmul and convolution operand, forward
                and backward, rounded to float8_e4m3fn with one scale per
                tensor (as fp8 training scales them), then multiplied at
                HIGHEST.  It is the precision below the bf16 operands that
                XLA's default gives float32 dots on a TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
FP8_MAX = 448.0                  # largest finite float8_e4m3fn


def _matmul(x, w):
    return jnp.matmul(x, w, precision=HI)


def _conv(x, w):
    return lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HI)


def q8(x):
    """Round `x` to float8_e4m3fn under one per-tensor scale, back to f32."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _fp8(op):
    @jax.custom_vjp
    def f(x, w):
        return op(q8(x), q8(w))

    def fwd(x, w):
        xq, wq = q8(x), q8(w)
        return op(xq, wq), (xq, wq)

    def bwd(res, g):
        _, vjp = jax.vjp(op, *res)
        return vjp(q8(g))

    f.defvjp(fwd, bwd)
    return f


class Ops:
    """`matmul(x, w)` and `conv(x, w)` (3x3 SAME, NHWC x HWIO) in one of
    the precisions above."""

    def __init__(self, precision: str):
        if precision == "highest":
            self.matmul, self.conv = _matmul, _conv
        elif precision == "fp8":
            self.matmul, self.conv = _fp8(_matmul), _fp8(_conv)
        else:
            raise ValueError(f"unknown reference precision {precision!r}")
        self.precision = precision


def leaky_relu(x, slope):
    return jnp.where(x >= 0, x, slope * x)
