"""Model-layer tests: attention/GQA, MoE dispatch, SSD, RoPE/RMSNorm
properties, prefill/decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models import ModelConfig, model as M
from repro.models.layers import (apply_rope, attention_chunked,
                                 attention_naive, rms_norm)
from repro.models.moe import moe_capacity, run_moe, run_moe_reference
from repro.models.ssm import ssd_chunked, ssd_sequential


# ----------------------------------------------------------------------------
# layers


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 4), st.integers(2, 16), st.integers(8, 64))
def test_rms_norm_property(b, s, d):
    x = jax.random.normal(jax.random.PRNGKey(b * 100 + s), (b, s, d))
    y = rms_norm(x, jnp.ones((d,)))
    # unit RMS per vector
    rms = np.sqrt(np.mean(np.asarray(y) ** 2, axis=-1))
    np.testing.assert_allclose(rms, 1.0, atol=2e-2)
    # scale equivariance in the weight
    y2 = rms_norm(x, 2.0 * jnp.ones((d,)))
    np.testing.assert_allclose(np.asarray(y2), 2 * np.asarray(y), rtol=1e-5)


def test_rope_preserves_norm_and_relativity():
    hd, S = 32, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (1, S, 2, hd))
    pos = jnp.arange(S)
    y = apply_rope(x, pos, 10_000.0)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    # dot products depend only on relative distance
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, hd))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, hd))
    def dot_at(pq, pk):
        qr = apply_rope(q, jnp.array([pq]), 1e4)
        kr = apply_rope(k, jnp.array([pk]), 1e4)
        return float(jnp.sum(qr * kr))
    assert abs(dot_at(5, 3) - dot_at(9, 7)) < 1e-4
    assert abs(dot_at(5, 3) - dot_at(6, 3)) > 1e-6


def _mk_cfg(**kw):
    base = dict(name="t", family="dense", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=97,
                dtype="float32", attn_impl="naive")
    base.update(kw)
    return ModelConfig(**base)


@pytest.mark.parametrize("window", [None, 8])
def test_attention_chunked_equals_naive(window):
    cfg = _mk_cfg(attn_chunk=16, sliding_window=window)
    B, S, KV, G, hd = 2, 64, 2, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, KV, G, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    pos = jnp.arange(S)
    o1 = attention_naive(q, k, v, cfg, pos, pos)
    o2 = attention_chunked(q, k, v, cfg, pos, pos)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-4, atol=1e-5)


# ----------------------------------------------------------------------------
# MoE


def test_moe_matches_reference_when_capacity_slack():
    cfg = _mk_cfg(family="moe", num_experts=4, top_k=2, moe_d_ff=32,
                  num_shared_experts=1, capacity_factor=8.0)
    from repro.models.moe import init_moe
    p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    y, aux = run_moe(p, x, cfg)
    y_ref = run_moe_reference(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-3, atol=1e-3)
    assert float(aux) > 0


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 6), st.integers(1, 3), st.floats(1.0, 4.0))
def test_moe_capacity_property(E, k, cf):
    cfg = _mk_cfg(family="moe", num_experts=E, top_k=min(k, E), moe_d_ff=16,
                  capacity_factor=cf)
    T = 64
    C = moe_capacity(T, cfg)
    assert C % 8 == 0 and C >= 8
    assert C * E >= T * min(k, E)        # enough room at cf>=1 on average


def test_moe_aux_loss_balanced_router_is_one():
    """A perfectly uniform router gives aux ~= 1 (Switch normalization)."""
    cfg = _mk_cfg(family="moe", num_experts=4, top_k=2, moe_d_ff=16)
    from repro.models.moe import init_moe
    p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    p = dict(p, router=jnp.zeros((cfg.d_model, 4)))     # uniform probs
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
    _, aux = run_moe(p, x, cfg)
    assert 0.9 < float(aux) < 1.1


# ----------------------------------------------------------------------------
# SSD


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 2), st.sampled_from([32, 48, 64]),
       st.integers(1, 3), st.sampled_from([8, 16]), st.sampled_from([4, 8]),
       st.sampled_from([8, 16]))
def test_ssd_chunked_vs_sequential_property(B, S, H, P, N, Q):
    ks = jax.random.split(jax.random.PRNGKey(S + H), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bc = jax.random.normal(ks[3], (B, S, N))
    Cc = jax.random.normal(ks[4], (B, S, N))
    y1 = ssd_chunked(x, dt, A, Bc, Cc, Q)
    y2 = ssd_sequential(x, dt, A, Bc, Cc)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-3, atol=1e-3)


def test_ssd_causality():
    """Perturbing token t must not change outputs before t."""
    B, S, H, P, N = 1, 32, 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bc = jax.random.normal(ks[3], (B, S, N))
    Cc = jax.random.normal(ks[4], (B, S, N))
    y = ssd_chunked(x, dt, A, Bc, Cc, 8)
    x2 = x.at[:, 20].add(10.0)
    y2 = ssd_chunked(x2, dt, A, Bc, Cc, 8)
    np.testing.assert_allclose(np.asarray(y[:, :20]), np.asarray(y2[:, :20]),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(y[:, 20:] - y2[:, 20:]))) > 1e-3


# ----------------------------------------------------------------------------
# prefill / decode consistency


@pytest.mark.parametrize("fam_kw", [
    dict(family="dense"),
    dict(family="dense", sliding_window=4),
    dict(family="ssm", num_kv_heads=4, d_ff=0, ssm_state=8,
         ssm_head_dim=16, ssm_chunk=4),
    dict(family="hybrid", num_experts=4, top_k=2, moe_d_ff=32,
         ssm_state=8, ssm_head_dim=16, ssm_chunk=4, attn_period=2,
         attn_offset=1, moe_period=2, capacity_factor=8.0),
])
def test_prefill_decode_matches_forward(fam_kw):
    cfg = _mk_cfg(num_layers=2 if fam_kw["family"] != "hybrid" else 4,
                  **fam_kw)
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 8), 0, 97)
    p = M.init(jax.random.PRNGKey(1), cfg)
    full = M.forward(p, {"tokens": toks}, cfg)[0]
    lg, cache = M.prefill(p, {"tokens": toks[:, :6]}, cfg, context_len=8)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, :6]),
                               rtol=2e-3, atol=2e-3)
    for t in (6, 7):
        lg, cache = M.decode_step(p, toks[:, t:t + 1], cache, cfg)
        np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                   np.asarray(full[:, t]),
                                   rtol=2e-3, atol=2e-3)


def test_sliding_window_ring_buffer_wraps():
    """Decode past the window must equal a fresh forward on the same text."""
    cfg = _mk_cfg(sliding_window=4)
    S = 12
    toks = jax.random.randint(jax.random.PRNGKey(0), (1, S), 0, 97)
    p = M.init(jax.random.PRNGKey(1), cfg)
    full = M.forward(p, {"tokens": toks}, cfg)[0]
    lg, cache = M.prefill(p, {"tokens": toks[:, :6]}, cfg, context_len=S)
    for t in range(6, S):
        lg, cache = M.decode_step(p, toks[:, t:t + 1], cache, cfg)
        np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                   np.asarray(full[:, t]),
                                   rtol=2e-3, atol=2e-3)


def test_vlm_loss_masks_vision_positions():
    cfg = _mk_cfg(family="vlm", frontend="vision", num_vision_tokens=4)
    p = M.init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.ones((2, 8), jnp.int32),
             "vision": jax.random.normal(jax.random.PRNGKey(1), (2, 4, 1024))}
    loss, metrics = M.loss_fn(p, batch, cfg)
    assert bool(jnp.isfinite(loss))
    # vision embeddings must influence text logits (cross-modal attention)
    logits1 = M.forward(p, batch, cfg)[0]
    batch2 = dict(batch, vision=batch["vision"] + 1.0)
    logits2 = M.forward(p, batch2, cfg)[0]
    assert float(jnp.max(jnp.abs(logits1 - logits2))) > 1e-4
