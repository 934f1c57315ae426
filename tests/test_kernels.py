"""Per-kernel validation: shape/dtype sweeps + hypothesis properties, all
against the ref.py pure-jnp oracles (interpret=True on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.imaging import blur2d, mask_apply
from repro.kernels.inverse_cdf import inverse_cdf
from repro.kernels.ssd_scan import ssd_scan


def _tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("B,H,KV,S,hd", [
    (2, 4, 2, 256, 64), (1, 4, 4, 128, 32), (2, 2, 1, 256, 64),
    (1, 8, 2, 384, 64), (1, 2, 2, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 64)])
def test_flash_attention_sweep(B, H, KV, S, hd, dtype, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, KV, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, KV, S, hd), dtype)
    o = flash_attention(q, k, v, causal=causal, window=window, interpret=True)
    r = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), **_tol(dtype))


def test_flash_attention_block_shapes():
    """Result must not depend on the BlockSpec tiling."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    outs = [flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
            for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 128, 3, 32, 16, 32), (1, 100, 2, 64, 128, 64),
    (1, 64, 1, 16, 8, 16), (2, 96, 4, 32, 32, 48),
])
def test_ssd_scan_sweep(B, S, H, P, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bc = jax.random.normal(ks[3], (B, S, N))
    Cc = jax.random.normal(ks[4], (B, S, N))
    y = ssd_scan(x, dt, A, Bc, Cc, chunk=chunk, interpret=True)
    r = ref.ssd_scan_ref(x, dt, A, Bc, Cc)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r),
                               rtol=1e-3, atol=1e-3)


def test_ssd_chunk_invariance():
    """SSD output must not depend on the chunk size."""
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    B, S, H, P, N = 1, 128, 2, 16, 8
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bc = jax.random.normal(ks[3], (B, S, N))
    Cc = jax.random.normal(ks[4], (B, S, N))
    outs = [ssd_scan(x, dt, A, Bc, Cc, chunk=c, interpret=True)
            for c in (16, 32, 64, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("K,E", [(100, 64), (1024, 100), (7, 3), (256, 128)])
def test_inverse_cdf_sweep(K, E):
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    u = jax.random.uniform(ks[0], (K, E))
    mu = jax.random.normal(ks[1], (K,))
    s = jax.nn.softplus(jax.random.normal(ks[2], (K,)))
    k = jax.random.normal(ks[3], (K,))
    y = inverse_cdf(u, mu, s, k, interpret=True)
    r = ref.inverse_cdf_ref(u, mu, s, k)
    np.testing.assert_allclose(np.asarray(y), np.asarray(r),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 40), st.integers(1, 40),
       st.floats(-3, 3), st.floats(0.05, 2.0), st.floats(-1, 1))
def test_inverse_cdf_property_monotone(K, E, mu, s, k):
    """F^{-1} must be monotonically increasing in u when s > |k|*u-range
    (the sampler's validity envelope) and match the oracle everywhere."""
    u = jnp.linspace(0.01, 0.99, E)[None, :].repeat(K, axis=0)
    muv = jnp.full((K,), mu)
    sv = jnp.full((K,), s)
    kv = jnp.full((K,), k)
    y = np.asarray(inverse_cdf(u, muv, sv, kv, interpret=True))
    r = np.asarray(ref.inverse_cdf_ref(u, muv, sv, kv))
    np.testing.assert_allclose(y, r, rtol=1e-5, atol=1e-5)
    if s > abs(k) * 0.25:          # logistic term dominates the shear
        assert np.all(np.diff(y, axis=1) > -1e-5)


# ----------------------------------------------------------------------------
# imaging forward operators (ISSUE 9) — Pallas kernel vs jnp oracle


@pytest.mark.parametrize("K,P", [(1, 32), (7, 100), (64, 1024), (300, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mask_apply_sweep(K, P, dtype):
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    x = jax.random.normal(ks[0], (K, P), dtype)
    m = (jax.random.uniform(ks[1], (P,)) > 0.4).astype(dtype)
    y = mask_apply(x, m, interpret=True)
    r = ref.mask_apply_ref(x, m)
    # both sides compute x*m in fp32 with identical ordering: exact
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(r, np.float32))


def test_mask_apply_block_shapes():
    """Result must not depend on the BlockSpec tiling (incl. ragged pads)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    x = jax.random.normal(ks[0], (100, 200))
    m = (jax.random.uniform(ks[1], (200,)) > 0.5).astype(x.dtype)
    outs = [mask_apply(x, m, block_k=bk, block_p=bp, interpret=True)
            for bk, bp in [(256, 128), (32, 64), (100, 200), (64, 256)]]
    for o in outs[1:]:
        np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(o))


@pytest.mark.parametrize("K,H,W", [(1, 8, 8), (5, 32, 32), (20, 16, 24)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_blur2d_sweep(K, H, W, dtype):
    x = jax.random.normal(jax.random.PRNGKey(8), (K, H, W), dtype)
    y = blur2d(x, interpret=True)
    r = ref.blur2d_ref(x)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(r, np.float32),
                               rtol=1e-6, atol=1e-6)


def test_blur2d_is_self_adjoint():
    """<Bx, y> == <x, By>: the property the custom VJP relies on to reuse
    the forward kernel as the backward pass."""
    kx, ky = jax.random.split(jax.random.PRNGKey(9))
    x = jax.random.normal(kx, (3, 16, 16))
    y = jax.random.normal(ky, (3, 16, 16))
    lhs = jnp.vdot(blur2d(x, interpret=True), y)
    rhs = jnp.vdot(x, blur2d(y, interpret=True))
    np.testing.assert_allclose(float(lhs), float(rhs), rtol=1e-5)


def test_imaging_gradients_match_reference():
    """The closed-form custom VJPs (diagonal mask adjoint, self-adjoint
    blur) agree with jax.grad of the jnp oracles."""
    from repro.kernels import ops
    ks = jax.random.split(jax.random.PRNGKey(10), 2)
    x2 = jax.random.normal(ks[0], (6, 64))
    m = (jax.random.uniform(ks[1], (64,)) > 0.3).astype(x2.dtype)
    g1 = jax.grad(lambda x: jnp.sum(ops.mask_apply(x, m, True) ** 2))(x2)
    g2 = jax.grad(lambda x: jnp.sum(ref.mask_apply_ref(x, m) ** 2))(x2)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-5, atol=1e-6)
    x3 = jax.random.normal(ks[0], (4, 12, 12))
    g3 = jax.grad(lambda x: jnp.sum(ops.blur2d(x, True) ** 2))(x3)
    g4 = jax.grad(lambda x: jnp.sum(ref.blur2d_ref(x) ** 2))(x3)
    np.testing.assert_allclose(np.asarray(g3), np.asarray(g4),
                               rtol=1e-5, atol=1e-6)


def test_kernel_gradients_match_reference():
    """custom_vjp backward paths agree with jax.grad of the oracle."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    B, S, KV, G, hd = 1, 64, 2, 2, 32
    q = jax.random.normal(ks[0], (B, S, KV, G, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    from repro.kernels import ops

    def loss_kernel(q_):
        return jnp.sum(ops.flash_attention(q_, k, v) ** 2)

    def loss_ref(q_):
        return jnp.sum(ops._ref_attention(q_, k, v, True, None) ** 2)

    g1 = jax.grad(loss_kernel)(q)
    g2 = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-4)
