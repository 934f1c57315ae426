"""Optimizer + schedule tests."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.optim import (adam, adamw, sgd, apply_updates,
                         clip_by_global_norm, global_norm)
from repro.optim.schedules import cosine_decay, linear_warmup_cosine


def test_adam_matches_closed_form_first_step():
    opt = adam(1e-2, b1=0.9, b2=0.999, eps=1e-8)
    p = {"w": jnp.array([1.0, -2.0])}
    g = {"w": jnp.array([0.5, -0.1])}
    st_ = opt.init(p)
    upd, st_ = opt.update(g, st_)
    # bias-corrected first step = -lr * g / (|g| + eps)
    expect = -1e-2 * np.sign(np.array([0.5, -0.1]))
    np.testing.assert_allclose(np.asarray(upd["w"]), expect, rtol=1e-4)


def test_adam_converges_on_quadratic():
    opt = adam(0.1)
    p = jnp.array([5.0, -3.0])
    st_ = opt.init(p)
    for _ in range(300):
        g = 2 * p
        upd, st_ = opt.update(g, st_)
        p = apply_updates(p, upd)
    assert float(jnp.max(jnp.abs(p))) < 1e-2


def test_adamw_decays_weights():
    optw = adamw(1e-2, weight_decay=0.1)
    p = {"w": jnp.array([10.0])}
    st_ = optw.init(p)
    upd, _ = optw.update({"w": jnp.array([0.0])}, st_, p)
    assert float(upd["w"][0]) < 0          # pure decay pulls toward 0


def test_sgd_momentum():
    opt = sgd(0.1, momentum=0.9)
    p = jnp.array([1.0])
    st_ = opt.init(p)
    upd1, st_ = opt.update(jnp.array([1.0]), st_)
    upd2, st_ = opt.update(jnp.array([1.0]), st_)
    assert float(upd2[0]) < float(upd1[0]) < 0     # accelerating


@settings(max_examples=20, deadline=None)
@given(st.floats(0.1, 10.0), st.integers(1, 5))
def test_clip_property(max_norm, seed):
    tree = {"a": jax.random.normal(jax.random.PRNGKey(seed), (7,)) * 10,
            "b": jax.random.normal(jax.random.PRNGKey(seed + 1), (3, 3)) * 10}
    clipped, pre = clip_by_global_norm(tree, max_norm)
    post = float(global_norm(clipped))
    assert post <= max_norm * (1 + 1e-5)
    if float(pre) <= max_norm:             # no-op below the threshold
        np.testing.assert_allclose(np.asarray(clipped["a"]),
                                   np.asarray(tree["a"]), rtol=1e-6)


def test_schedules():
    sched = linear_warmup_cosine(1.0, warmup=10, total_steps=110)
    assert float(sched(jnp.asarray(0))) == 0.0
    assert abs(float(sched(jnp.asarray(10))) - 1.0) < 1e-6
    assert float(sched(jnp.asarray(110))) < 1e-6
    cos = cosine_decay(2.0, 100, floor=0.5)
    assert abs(float(cos(jnp.asarray(0))) - 2.0) < 1e-6
    assert abs(float(cos(jnp.asarray(100))) - 0.5) < 1e-6
