"""Tier-1 tests for the SyncSchedule abstraction + adaptive staleness
(ISSUE 4 tentpole).

Pins the schedule layer's contract:
  * the factory routes config-time-fixed schedules to `StaticSchedule`
    and `SyncConfig.adaptive` to `AdaptiveSchedule`; the epoch state
    carries ONE schedule-owned `state["sync"]` pytree (no loose
    mailbox/outer_mailbox buffers),
  * `StaticSchedule.exchange` is bitwise the historical `sync_gradients`
    threading (the golden proxy1d trajectory itself is pinned by
    tests/test_problems.py::test_proxy1d_bitwise_identical_to_seed),
  * the adaptive controller keeps k_eff in [1, k_max] under ARBITRARY
    skew sequences (property test), widens under sustained positive skew
    and narrows back under zero skew,
  * zero-skew adaptive is bitwise depth-1 rma_arar_arar (k_max = 1 and
    k_max > 1 both degenerate to k_eff = 1 in the lock-step simulator),
    with and without overlap,
  * adaptive mailbox reads are exactly k_eff epochs old, with honest
    deposit tags riding the ring,
  * the new SyncState layout round-trips through checkpoint/store.py and
    train_vmap resume reproduces the uninterrupted trajectory bitwise,
  * train_vmap always returns a non-empty history (checkpoint_every=0
    records the final epoch),
  * donation/aliasing survives the refactor for the adaptive state too.
"""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import restore_latest, save_checkpoint
from repro.core import workflow
from repro.core.ring import VmapComm, make_deposit_tag
from repro.core.sync import (AdaptiveSchedule, FusionSpec, StaticSchedule,
                             SyncConfig, adaptive_controller_step,
                             adaptive_k_eff, make_schedule, sync_gradients,
                             init_mailbox)
from repro.core.workflow import WorkflowConfig

O, I = 2, 2
R = O * I
MASK = {"w": True, "b": False}


def grads_like(key, shape=(3, 4)):
    ks = jax.random.split(jax.random.PRNGKey(key), 2)
    return {"w": jax.random.normal(ks[0], (R,) + shape),
            "b": jax.random.normal(ks[1], (R, shape[-1]))}


def build_spec():
    example = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), grads_like(0))
    return FusionSpec.build(example, MASK)


def small_wcfg(sync, **kw):
    kw.setdefault("n_param_samples", 8)
    kw.setdefault("events_per_sample", 4)
    return WorkflowConfig(problem="proxy1d", sync=sync, **kw)


def assert_trees_equal(a, b, err=""):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=err)


# ----------------------------------------------------------------------------
# factory + SyncState structure


def test_make_schedule_factory_routes_on_config():
    spec = build_spec()
    assert isinstance(make_schedule(SyncConfig(), MASK, spec),
                      StaticSchedule)
    assert isinstance(
        make_schedule(SyncConfig(mode="rma_arar_arar", staleness=3,
                                 adaptive=True), MASK, spec),
        AdaptiveSchedule)
    assert make_schedule(SyncConfig(), MASK, spec).name == "sync"
    assert make_schedule(SyncConfig(mode="arar_arar", overlap=True),
                         MASK, spec).name == "overlap"
    assert make_schedule(SyncConfig(mode="rma_arar_arar", adaptive=True),
                         MASK, spec).name == "adaptive"


def test_adaptive_config_validation():
    with pytest.raises(ValueError, match="rma_arar_arar"):
        SyncConfig(mode="arar_arar", adaptive=True)
    with pytest.raises(ValueError, match="fuse_tensors"):
        SyncConfig(mode="rma_arar_arar", adaptive=True, fuse_tensors=False)
    SyncConfig(mode="rma_arar_arar", staleness=4, adaptive=True)   # fine
    SyncConfig(mode="rma_arar_arar", staleness=4, adaptive=True,
               overlap=True)                                       # composes


def test_epoch_state_carries_one_sync_pytree():
    """The loose mailbox/outer_mailbox buffers collapsed into
    state["sync"] — static AND adaptive, per-rank AND stacked."""
    for sync in (SyncConfig(mode="rma_arar_arar", staleness=2),
                 SyncConfig(mode="rma_arar_arar", staleness=2,
                            adaptive=True)):
        state = workflow.init_state(jax.random.PRNGKey(0), R,
                                    small_wcfg(sync))
        assert "sync" in state
        assert "mailbox" not in state and "outer_mailbox" not in state
        assert "mailbox" in state["sync"]
        assert "outer_mailbox" in state["sync"]
    # the adaptive state also carries the controller + deposit tags
    assert "ctrl" in state["sync"]
    assert state["sync"]["ctrl"]["k_eff"].shape == (R,)
    assert state["sync"]["mailbox"]["tag"].shape == (R, 2)
    assert bool(jnp.all(state["sync"]["mailbox"]["tag"] == -1))
    assert state["sync"]["mailbox"]["payload"].ndim == 3   # [R, k_max, D]


def test_static_schedule_exchange_matches_sync_gradients():
    """StaticSchedule is a re-packaging, not a re-implementation: its
    exchange must be bitwise the raw sync_gradients threading for every
    pre-existing schedule shape (sync, depth-k, overlap)."""
    spec = build_spec()
    comm = VmapComm(O, I)
    for cfg in (SyncConfig(mode="arar_arar", h=2),
                SyncConfig(mode="rma_arar_arar", h=2, staleness=3),
                SyncConfig(mode="rma_arar_arar", h=2, overlap=True)):
        sched = make_schedule(cfg, MASK, spec)
        st_state = sched.init_state(R)
        mb = init_mailbox(grads_like(0), staleness=cfg.staleness,
                          stacked=True)
        omb = spec.zero_payload(R)
        for e in range(4):
            g = grads_like(50 + e)
            s1, st_state = sched.exchange(comm, g, st_state, jnp.asarray(e))
            s2, mb, omb = sync_gradients(comm, cfg, g, mb, jnp.asarray(e),
                                         MASK, spec=spec, outer_mailbox=omb)
            assert_trees_equal(s1, s2, err=f"{cfg.mode} epoch {e}")
            assert_trees_equal(st_state["mailbox"], mb)
            assert_trees_equal(st_state["outer_mailbox"], omb)


# ----------------------------------------------------------------------------
# adaptive controller invariants


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 6),
       st.lists(st.floats(-50.0, 50.0), min_size=1, max_size=40))
def test_adaptive_k_eff_bounded_under_arbitrary_skew(k_max, skews):
    """Property: whatever the measured skew sequence throws at it, the
    controller's k_eff NEVER leaves [1, k_max]."""
    ctrl = {"skew_ema": jnp.zeros(()), "k_eff": jnp.ones((), jnp.int32)}
    for s in skews:
        ctrl = adaptive_controller_step(ctrl, jnp.asarray(s, jnp.float32),
                                        k_max)
        k = int(ctrl["k_eff"])
        assert 1 <= k <= k_max, (k, k_max, s)


def test_adaptive_controller_widens_then_narrows():
    """Sustained positive skew (producers lagging) widens the window to
    k_max; once the skew vanishes the EMA decays and the window narrows
    back to fresh depth-1 reads."""
    k_max = 4
    ctrl = {"skew_ema": jnp.zeros(()), "k_eff": jnp.ones((), jnp.int32)}
    seen = []
    for _ in range(40):
        ctrl = adaptive_controller_step(ctrl, jnp.asarray(5.0), k_max)
        seen.append(int(ctrl["k_eff"]))
    assert seen[-1] == k_max
    assert seen == sorted(seen)          # monotone widening under constant skew
    for _ in range(60):
        ctrl = adaptive_controller_step(ctrl, jnp.asarray(0.0), k_max)
    assert int(ctrl["k_eff"]) == 1
    assert float(ctrl["skew_ema"]) < 0.5


def test_adaptive_k_eff_is_integer_clip():
    assert int(adaptive_k_eff(jnp.asarray(0.0), 5)) == 1
    assert int(adaptive_k_eff(jnp.asarray(2.4), 5)) == 3
    assert int(adaptive_k_eff(jnp.asarray(100.0), 5)) == 5
    assert int(adaptive_k_eff(jnp.asarray(-100.0), 5)) == 1


# ----------------------------------------------------------------------------
# hysteresis deadband (ISSUE 5 satellite): no flapping between adjacent depths


def _run_controller(skews, k_max, deadband):
    ctrl = {"skew_ema": jnp.zeros(()), "k_eff": jnp.ones((), jnp.int32)}
    ks = []
    for s in skews:
        ctrl = adaptive_controller_step(ctrl, jnp.asarray(s, jnp.float32),
                                        k_max, deadband=deadband)
        ks.append(int(ctrl["k_eff"]))
    return ks


def _transitions(ks):
    return sum(a != b for a, b in zip(ks, ks[1:]))


def test_deadband_suppresses_boundary_oscillation():
    """The motivating failure: skew alternating 0.7/0.3 drives the EMA
    across the 0.5 rounding boundary every step, so the raw controller
    (deadband=0) re-gears k_eff between 1 and 2 indefinitely; the
    deadband controller holds depth 1 throughout — the implied depth
    never strays far enough from the current one to justify a move."""
    skews = [0.7 if i % 2 == 0 else 0.3 for i in range(60)]
    raw = _run_controller(skews, k_max=4, deadband=0.0)
    held = _run_controller(skews, k_max=4, deadband=0.25)
    assert _transitions(raw[20:]) > 10       # flaps at steady state
    assert _transitions(held) == 0 and set(held) == {1}


def test_deadband_still_tracks_large_skew_moves():
    """Hysteresis must not cost responsiveness: sustained large skew still
    widens to k_max and sustained zero skew still narrows back to 1
    (the zero-skew pin that keeps lock-step runs bitwise)."""
    ks = _run_controller([5.0] * 40 + [0.0] * 60, k_max=4, deadband=0.25)
    assert ks[39] == 4 and ks[:40] == sorted(ks[:40])
    assert ks[-1] == 1


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 6),
       st.lists(st.floats(-10.0, 10.0), min_size=2, max_size=60))
def test_deadband_never_increases_transitions(k_max, skews):
    """Property (hypothesis): on ANY skew sequence the deadband
    controller (a) never leaves [1, k_max] and (b) counted from the
    shared initial depth 1, changes depth at most as often as the raw
    rounding controller — every deadband move lands on the raw
    controller's own value (`adaptive_k_eff(ema)`), so between two
    deadband moves the raw trajectory must itself have changed."""
    raw = _run_controller(skews, k_max, deadband=0.0)
    held = _run_controller(skews, k_max, deadband=0.25)
    assert all(1 <= k <= k_max for k in held)
    assert _transitions([1] + held) <= _transitions([1] + raw)


# ----------------------------------------------------------------------------
# adaptive staleness semantics: reads exactly k_eff old, tagged deposits


def test_adaptive_zero_skew_reads_are_exactly_one_epoch_old():
    """Lock-step SPMD shows zero skew, so k_eff stays 1 inside the
    depth-k_max mailbox: epoch e's read must be the ring deposit from
    e-1 — not fresher (that would be synchronous) and not the older
    deposits the max-depth buffer still holds."""
    spec = build_spec()
    comm = VmapComm(1, R)
    cfg = SyncConfig(mode="rma_arar_arar", h=1000, staleness=3,
                     adaptive=True)
    sched = make_schedule(cfg, MASK, spec)
    state = sched.init_state(R)
    gs = [grads_like(100 + e) for e in range(5)]
    for e in range(5):
        out, state = sched.exchange(comm, gs[e], state, jnp.asarray(e))
        if e == 0:       # warmup: empty mailbox, tag -1, zero payload read
            expect = np.asarray(gs[e]["w"])
        else:
            expect = np.asarray(gs[e]["w"]) + \
                np.roll(np.asarray(gs[e - 1]["w"]), 1, axis=0)
        np.testing.assert_allclose(np.asarray(out["w"]), expect, rtol=1e-6,
                                   err_msg=f"epoch {e}")
        # biases never ride the ring (§V-C)
        np.testing.assert_array_equal(np.asarray(out["b"]),
                                      np.asarray(gs[e]["b"]))
        # deposit tags record the producing epoch in slot e % k_max
        assert int(state["mailbox"]["tag"][0, e % 3]) == e
        assert int(state["ctrl"]["k_eff"][0]) == 1


def test_deposit_tag_layouts():
    assert make_deposit_tag(jnp.asarray(7)).shape == ()
    t = make_deposit_tag(jnp.asarray(7), n_ranks=5)
    assert t.shape == (5,) and t.dtype == jnp.int32
    assert bool(jnp.all(t == 7))


# ----------------------------------------------------------------------------
# degeneration: zero-skew adaptive == depth-1 rma, bitwise


@pytest.mark.parametrize("k_max", [1, 3])
def test_adaptive_zero_skew_bitwise_rma_arar_arar(k_max):
    """The acceptance pin: adaptive with zero skew (the lock-step
    simulator's reality) is bitwise the static depth-1 rma_arar_arar
    trajectory — for k_max=1 (clamp) and k_max>1 (controller holds
    k_eff at 1).  Per-epoch jitted driver, 2x2 ranks, hot outer ring."""
    from repro.problems import get_problem
    data = get_problem("proxy1d").make_reference_data(jax.random.PRNGKey(9),
                                                      400)
    gens = {}
    for adaptive in (False, True):
        wcfg = small_wcfg(SyncConfig(
            mode="rma_arar_arar", h=2,
            staleness=k_max if adaptive else 1, adaptive=adaptive))
        state, _ = workflow.train_vmap(jax.random.PRNGKey(0), wcfg, O, I, 3,
                                       data, chunk=1)
        gens[adaptive] = state["gen"]
    assert_trees_equal(gens[False], gens[True],
                       err=f"adaptive k_max={k_max} diverged from rma k=1")


def test_adaptive_overlap_zero_skew_bitwise_static_overlap():
    """Adaptive composes with overlap: zero skew keeps k_eff=1, so the
    ship gate's lead stays 1 and the trajectory is bitwise the static
    overlap schedule."""
    from repro.problems import get_problem
    data = get_problem("proxy1d").make_reference_data(jax.random.PRNGKey(3),
                                                      400)
    gens = {}
    for adaptive in (False, True):
        wcfg = small_wcfg(SyncConfig(
            mode="rma_arar_arar", h=2, overlap=True,
            staleness=3 if adaptive else 1, adaptive=adaptive))
        state, _ = workflow.train_vmap(jax.random.PRNGKey(0), wcfg, O, I, 3,
                                       data, chunk=1)
        gens[adaptive] = state["gen"]
    assert_trees_equal(gens[False], gens[True])


def test_adaptive_overlap_ship_fires_exactly_once_per_cycle_under_k_jumps():
    """Regression (review finding): the stretched ship gate must refresh
    the pod-boundary mailbox exactly once per h-cycle even when k_eff
    jumps mid-cycle.  A naive `(epoch + lead) % h == 0` gate skips the
    whole cycle when lead rises from 1 to 2 exactly at due-1 — the
    `shipped_for` marker makes the gate fire at the first epoch within
    `lead` of the due epoch and suppresses re-ships."""
    spec = build_spec()
    comm = VmapComm(O, I)
    h = 4
    cfg = SyncConfig(mode="rma_arar_arar", h=h, staleness=3, adaptive=True,
                     overlap=True)
    sched = make_schedule(cfg, MASK, spec)
    state = sched.init_state(R)
    # skew_ema injected BEFORE the exchange; the EMA update keeps 0.8 of
    # it (observed skew is 0 in lock-step), so 1.25 -> ema 1.0 -> k_eff 2.
    # Injections recreate the failure pattern: lead 2 at due-2, back to 1
    # at due-1 (epochs 2/3 for due=4, 6/7 for due=8).
    inject = {2: 1.25, 3: 0.0, 6: 1.25, 7: 0.0}
    ships = []
    prev = np.asarray(state["outer_mailbox"])
    for e in range(12):
        if e in inject:
            state["ctrl"]["skew_ema"] = jnp.full((R,), inject[e],
                                                 jnp.float32)
        _, state = sched.exchange(comm, grads_like(300 + e), state,
                                  jnp.asarray(e))
        cur = np.asarray(state["outer_mailbox"])
        ships.append(not np.array_equal(cur, prev))
        prev = cur
    for c in range(3):            # one ship per cycle, whatever k_eff did
        assert sum(ships[c * h:(c + 1) * h]) == 1, (c, ships)


def test_adaptive_trains_finite_on_scan_chunks():
    """The scan-chunked production driver runs the adaptive schedule and
    stays finite (bitwise parity is pinned on the chunk=1 path above; a
    longer scan may fuse differently at the fp-noise level)."""
    from repro.problems import get_problem
    data = get_problem("proxy1d").make_reference_data(jax.random.PRNGKey(5),
                                                      400)
    wcfg = small_wcfg(SyncConfig(mode="rma_arar_arar", h=2, staleness=4,
                                 adaptive=True))
    state, hist = workflow.train_vmap(jax.random.PRNGKey(0), wcfg, O, I, 4,
                                      data)
    for leaf in jax.tree.leaves(state):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))
    assert int(state["epoch"][0]) == 4
    assert 1 <= int(state["sync"]["ctrl"]["k_eff"][0]) <= 4


# ----------------------------------------------------------------------------
# checkpointing: the new SyncState layout round-trips; resume is bitwise


def test_checkpoint_roundtrip_sync_state_layout():
    """Full epoch state (adaptive sync pytree: f32 payload, int32 tags and
    k_eff) survives the npz round-trip bit for bit."""
    wcfg = small_wcfg(SyncConfig(mode="rma_arar_arar", h=2, staleness=3,
                                 adaptive=True, overlap=True))
    state = workflow.init_state(jax.random.PRNGKey(0), R, wcfg)
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 3, state)
        back, step = restore_latest(d, state)
    assert step == 3
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("sync", [
    SyncConfig(mode="rma_arar_arar", h=2, staleness=2),
    SyncConfig(mode="rma_arar_arar", h=2, staleness=3, adaptive=True),
])
def test_train_vmap_resume_reproduces_uninterrupted_bitwise(sync):
    """ISSUE 4 satellite: interrupt at step 2 of 4, resume from the
    checkpoint, and the final state must equal the uninterrupted run bit
    for bit — everything the trajectory depends on (rng, epoch counter,
    optimizer moments, the whole SyncState) lives in the checkpoint."""
    from repro.problems import get_problem
    wcfg = small_wcfg(sync)
    data = get_problem("proxy1d").make_reference_data(jax.random.PRNGKey(7),
                                                      400)
    key = jax.random.PRNGKey(0)
    full, _ = workflow.train_vmap(key, wcfg, O, I, 4, data,
                                  checkpoint_every=2)
    with tempfile.TemporaryDirectory() as d:
        # "interrupted" run: dies after epoch 2 (checkpoint saved)
        workflow.train_vmap(key, wcfg, O, I, 2, data, checkpoint_every=2,
                            checkpoint_dir=d)
        # resumed run continues from step_2 to epoch 4
        resumed, hist = workflow.train_vmap(key, wcfg, O, I, 4, data,
                                            checkpoint_every=2,
                                            checkpoint_dir=d, resume=True)
        from repro.checkpoint import latest_step
        assert latest_step(d) == 4       # resumed run kept checkpointing
    for k in ("gen", "disc", "gen_opt", "disc_opt", "sync", "rng", "epoch"):
        assert_trees_equal(full[k], resumed[k], err=f"state[{k!r}] diverged")
    # post-resume history covers exactly the epochs after the checkpoint
    assert hist["d_loss"].shape[0] == 2  # epochs 2 and 3


def test_train_vmap_resume_from_mid_chunk_checkpoint():
    """Regression (review finding): a final-epoch checkpoint can land off
    the resumed run's chunk grid (n_epochs=5, chunk=2 -> step_5); the
    resumed run must execute ONLY the remaining epochs from the restored
    state — not re-run a partial chunk with shifted labels/extra epochs.
    The continuation crosses a different scan partition than the
    uninterrupted run, so the pin is exact epoch accounting + fp-close
    trajectories (chunk-aligned resume is pinned bitwise above)."""
    from repro.problems import get_problem
    wcfg = small_wcfg(SyncConfig(mode="rma_arar_arar", h=2, staleness=2))
    data = get_problem("proxy1d").make_reference_data(jax.random.PRNGKey(8),
                                                      400)
    key = jax.random.PRNGKey(0)
    full, _ = workflow.train_vmap(key, wcfg, O, I, 7, data,
                                  checkpoint_every=2)
    with tempfile.TemporaryDirectory() as d:
        workflow.train_vmap(key, wcfg, O, I, 5, data, checkpoint_every=2,
                            checkpoint_dir=d)
        from repro.checkpoint import latest_step
        assert latest_step(d) == 5       # final save, off the chunk grid
        resumed, hist = workflow.train_vmap(key, wcfg, O, I, 7, data,
                                            checkpoint_every=2,
                                            checkpoint_dir=d, resume=True)
    assert int(resumed["epoch"][0]) == 7     # exactly 7 epochs, not 8
    assert hist["d_loss"].shape[0] == 1      # one post-resume row: epoch 6
    for a, b in zip(jax.tree.leaves(full["gen"]),
                    jax.tree.leaves(resumed["gen"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------------------------
# history: never empty (satellite)


def test_train_vmap_history_nonempty_without_checkpoint_every():
    """Regression: checkpoint_every=0 used to return {} — the final
    epoch's metrics must always be recorded."""
    from repro.problems import get_problem
    wcfg = small_wcfg(SyncConfig(mode="arar_arar", h=2))
    data = get_problem("proxy1d").make_reference_data(jax.random.PRNGKey(1),
                                                      400)
    _, hist = workflow.train_vmap(jax.random.PRNGKey(0), wcfg, O, I, 3, data)
    assert hist, "history must not be empty with checkpoint_every=0"
    for k in ("d_loss", "g_loss", "pred_params", "residuals"):
        assert k in hist
        assert hist[k].shape[0] == 1     # exactly the final epoch
        assert hist[k].shape[1] == R


# ----------------------------------------------------------------------------
# donation: the adaptive SyncState aliases in place too


def test_adaptive_epoch_keeps_state_donation_aliasing():
    wcfg = small_wcfg(SyncConfig(mode="rma_arar_arar", h=2, staleness=3,
                                 adaptive=True, overlap=True))
    state = workflow.init_state(jax.random.PRNGKey(0), R, wcfg)
    data = wcfg.problem_obj.make_reference_data(jax.random.PRNGKey(1), 200)
    dpr = jnp.stack([data] * R)
    fn = workflow.make_epoch_fn_vmap(O, I, wcfg)
    txt = fn.lower(state, dpr).as_text()
    assert txt.count("tf.aliasing_output") >= len(jax.tree.leaves(state))
