"""Tier-1 tests for ISSUE 7: bf16 wire payloads + asymmetric G/D cadence.

Four pins:

  * fp32 is untouched — with the default / explicit fp32 knob the lowered
    epoch contains NO bf16 anywhere (across sync/fused/depth-k/overlap/
    adaptive schedules), and threading `payload_dtype` through
    `FusionSpec.build` is numerically invisible: the fp32 trajectory is
    BITWISE the one produced by the historical dtype derivation
    (`payload_dtype=None`).  The golden seed capture itself is pinned in
    `test_problems.py::test_proxy1d_bitwise_identical_to_seed`.
  * bf16 is a wire format, not a training dtype: master params and Adam
    state stay fp32 (asserted on the final state), and the trajectory
    matches fp32 within a documented tolerance on ALL registered
    problems.  Tolerance: bf16 rounds each shipped gradient to 8 mantissa
    bits (~0.4% relative); through Adam's normalization four epochs at the
    test scale cost < 5e-4 absolute in generator params and < 5e-3 in
    residuals (measured ~1.6e-5 / ~8e-4 — an order of magnitude of
    headroom, still far below any fp32-vs-fp32 schedule difference).
  * bf16 is backend-invariant: vmap vs shard_map (8 forced host devices,
    subprocess) and vmap vs a zero-jitter lock-step ProcComm run agree at
    the repo's established 1e-6 cross-backend tolerance — all three
    backends round identically at the single flatten/scatter cast points.
  * cadence really disappears at the HLO level: `disc_every=2` lowers the
    epoch to a real `stablehlo.case` (SPMD-uniform cond, not a select)
    whose off-branch contains no discriminator matmuls — the total count
    of disc-width (192-dim) dot_generals does not grow over the
    every-epoch lowering, and composes with donation.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import problems
from repro.core import sync as sync_lib
from repro.core import workflow
from repro.core.sync import FusionSpec, SyncConfig
from repro.core.workflow import WorkflowConfig

ALL_PROBLEMS = problems.available()

# label -> SyncConfig kwargs, every schedule the fused engine supports
SCHEDULES = {
    "sync": dict(mode="conv_arar", h=2),
    "fused_grouped": dict(mode="arar_arar", h=2),
    "depth_k": dict(mode="rma_arar_arar", h=2, staleness=2),
    "overlap": dict(mode="rma_arar_arar", h=2, staleness=2, overlap=True),
    "adaptive": dict(mode="rma_arar_arar", h=2, staleness=3, adaptive=True),
}


def small_wcfg(sync, problem="proxy1d", **kw):
    return WorkflowConfig(problem=problem, sync=sync, n_param_samples=8,
                          events_per_sample=4, **kw)


def _data(problem="proxy1d", n=400, seed=9):
    return problems.get_problem(problem).make_reference_data(
        jax.random.PRNGKey(seed), n)


def _lower_epoch(wcfg, R=4):
    state = workflow.init_state(jax.random.PRNGKey(0), R, wcfg)
    dpr = jnp.stack([_data(wcfg.problem, 100)] * R)
    fn = workflow.make_epoch_fn_vmap(2, R // 2, wcfg)
    return fn.lower(state, dpr).as_text()


# ----------------------------------------------------------------------------
# config validation: the knob names what it can honor


def test_payload_precision_validation():
    SyncConfig(mode="conv_arar", payload_precision="bf16")   # ok
    with pytest.raises(ValueError, match="payload_precision"):
        SyncConfig(payload_precision="fp16")
    with pytest.raises(ValueError, match="fuse_tensors"):
        SyncConfig(mode="conv_arar", fuse_tensors=False,
                   payload_precision="bf16")
    with pytest.raises(ValueError, match="ring"):
        SyncConfig(mode="allreduce", payload_precision="bf16")


def test_cadence_validation():
    WorkflowConfig(disc_every=3, gen_every=2)                # ok
    with pytest.raises(ValueError, match="disc_every"):
        WorkflowConfig(disc_every=0)
    with pytest.raises(ValueError, match="gen_every"):
        WorkflowConfig(gen_every=-1)


# ----------------------------------------------------------------------------
# fp32 unchanged: no bf16 in the lowering, bitwise vs the historical spec


@pytest.mark.parametrize("label", sorted(SCHEDULES))
def test_fp32_lowering_contains_no_bf16(label):
    wcfg = small_wcfg(SyncConfig(**SCHEDULES[label]))
    assert wcfg.sync.payload_precision == "fp32"             # the default
    assert "bf16" not in _lower_epoch(wcfg), \
        f"{label}: fp32 epoch lowering mentions bf16"


@pytest.mark.parametrize("label", sorted(SCHEDULES))
def test_fp32_bitwise_matches_historical_spec_derivation(label, monkeypatch):
    """Threading payload_dtype into FusionSpec must be a no-op at fp32:
    the trajectory is BITWISE the one from the pre-knob derivation
    (payload_dtype=None infers the dtype from the masked leaves)."""
    wcfg = small_wcfg(SyncConfig(**SCHEDULES[label]))
    data = _data()

    def run():
        s, h = workflow.train_vmap(jax.random.PRNGKey(0), wcfg, 2, 2, 3,
                                   data, chunk=1)
        return s, h

    s_knob, h_knob = run()
    orig = FusionSpec.build.__func__

    def legacy_build(cls, example, mask, payload_dtype=None, chunk_bytes=0):
        # the pre-knob derivation had neither wire-dtype nor chunking —
        # drop both (ring_chunking is 0 in every schedule here anyway)
        return orig(cls, example, mask, payload_dtype=None)

    monkeypatch.setattr(FusionSpec, "build", classmethod(legacy_build))
    s_legacy, h_legacy = run()
    for a, b in zip(jax.tree.leaves(s_knob["gen"]),
                    jax.tree.leaves(s_legacy["gen"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(h_knob["residuals"]),
                                  np.asarray(h_legacy["residuals"]))


# ----------------------------------------------------------------------------
# bf16 semantics: wire-only, fp32 master state, bounded drift


def test_bf16_payload_in_lowering_master_state_fp32():
    wcfg = small_wcfg(SyncConfig(mode="rma_arar_arar", h=2, staleness=2,
                                 payload_precision="bf16"))
    assert "bf16" in _lower_epoch(wcfg)
    state, _ = workflow.train_vmap(jax.random.PRNGKey(0), wcfg, 2, 2, 2,
                                   _data())
    for tree in (state["gen"], state["gen_opt"], state["disc"],
                 state["disc_opt"]):
        for leaf in jax.tree.leaves(tree):
            assert leaf.dtype in (jnp.float32, jnp.int32), leaf.dtype
    # the wire really is half-width: every mailbox payload leaf is bf16
    mbx = state["sync"]["mailbox"]
    assert any(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(mbx))


@pytest.mark.parametrize("name", ALL_PROBLEMS)
def test_bf16_matches_fp32_within_tolerance(name):
    data = _data(name)
    outs = {}
    for prec in ("fp32", "bf16"):
        wcfg = small_wcfg(SyncConfig(mode="rma_arar_arar", h=2,
                                     payload_precision=prec), problem=name)
        outs[prec] = workflow.train_vmap(jax.random.PRNGKey(0), wcfg,
                                         2, 2, 4, data, chunk=1)
    pd = max(float(jnp.max(jnp.abs(a - b)))
             for a, b in zip(jax.tree.leaves(outs["fp32"][0]["gen"]),
                             jax.tree.leaves(outs["bf16"][0]["gen"])))
    rd = float(jnp.max(jnp.abs(outs["fp32"][1]["residuals"]
                               - outs["bf16"][1]["residuals"])))
    assert pd < 5e-4, f"{name}: bf16 drifted {pd} in generator params"
    assert rd < 5e-3, f"{name}: bf16 drifted {rd} in residuals"


@pytest.mark.parametrize("label", sorted(SCHEDULES))
def test_bf16_runs_finite_on_every_schedule(label):
    wcfg = small_wcfg(SyncConfig(**SCHEDULES[label],
                                 payload_precision="bf16"))
    state, hist = workflow.train_vmap(jax.random.PRNGKey(0), wcfg, 2, 2, 3,
                                      _data())
    for leaf in jax.tree.leaves(state):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))
    assert bool(jnp.all(jnp.isfinite(hist["residuals"])))


# ----------------------------------------------------------------------------
# cross-backend bf16 equivalence (vmap vs shard vs zero-jitter proc)


_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.core import pipeline, workflow
from repro.core.workflow import WorkflowConfig
from repro.core.sync import SyncConfig

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("pod", "data"))
data = pipeline.make_reference_data(jax.random.PRNGKey(42), 1000)
out = {}
combos = {
    "bf16_conv": ("conv_arar", 1, False, False, 1, 1),
    "bf16_rma_k2": ("rma_arar_arar", 2, False, False, 1, 1),
    "bf16_overlap": ("rma_arar_arar", 2, True, False, 1, 1),
    "bf16_adaptive_k3": ("rma_arar_arar", 3, False, True, 1, 1),
    "bf16_dbtree": ("dbtree", 1, False, False, 1, 1),
    "bf16_cadence": ("rma_arar_arar", 1, False, False, 2, 3),
    "fp32_cadence": None,
}
for label, combo in combos.items():
    if combo is None:
        sc = SyncConfig(mode="arar_arar", h=2)
        de, ge = 2, 3
    else:
        mode, k, overlap, adaptive, de, ge = combo
        sc = SyncConfig(mode=mode, h=2, staleness=k, overlap=overlap,
                        adaptive=adaptive, payload_precision="bf16")
    wcfg = WorkflowConfig(sync=sc, n_param_samples=8, events_per_sample=4,
                          disc_every=de, gen_every=ge)
    R = 8
    state_v = workflow.init_state(jax.random.PRNGKey(0), R, wcfg)
    sub = jax.random.split(jax.random.PRNGKey(9), R)
    dpr = jnp.stack([jnp.take(data, jax.random.permutation(s, 1000)[:500],
                              axis=0) for s in sub])
    ef_s, shardings = workflow.make_epoch_fn_shard(mesh, wcfg)
    ss = jax.device_put(state_v, shardings)
    ds = jax.device_put(dpr, shardings)
    ef_v = workflow.make_epoch_fn_vmap(2, 4, wcfg)
    sv = state_v
    for _ in range(4):
        sv, _ = ef_v(sv, dpr)
    for _ in range(4):
        ss, _ = ef_s(ss, ds)
    diff = max(float(jnp.max(jnp.abs(a - b)))
               for a, b in zip(jax.tree.leaves(sv["gen"]),
                               jax.tree.leaves(jax.device_get(ss["gen"]))))
    out[label] = diff
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_bf16_and_cadence_vmap_shard_equivalence():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run([sys.executable, "-c", _CHILD], cwd=repo,
                         capture_output=True, text=True, timeout=900)
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, f"child failed:\n{res.stderr[-3000:]}"
    diffs = json.loads(line[0][len("RESULT "):])
    assert set(diffs) == {"bf16_conv", "bf16_rma_k2", "bf16_overlap",
                          "bf16_adaptive_k3", "bf16_dbtree",
                          "bf16_cadence", "fp32_cadence"}
    for label, d in diffs.items():
        assert d < 1e-6, f"{label}: backends diverged by {d}"


@pytest.mark.slow
def test_bf16_proc_lockstep_matches_vmap():
    """Zero-jitter lock-step ProcComm with bf16 windows (mmap payloads at
    2 bytes/scalar) matches the vmap engine at the 1e-6 cross-backend
    tolerance — the wire rounding is identical, only matmul batching
    differs."""
    from repro.runtime.launch import run_proc
    wcfg = small_wcfg(SyncConfig(mode="rma_arar_arar", h=2,
                                 payload_precision="bf16"))
    data = _data()
    out = run_proc(wcfg, 1, 2, 3, data, seed=0, lockstep=True, timeout=420)
    sv, _ = workflow.train_vmap(jax.random.PRNGKey(0), wcfg, 1, 2, 3,
                                data, chunk=1)
    worst = max(float(jnp.max(jnp.abs(a - jnp.asarray(b))))
                for a, b in zip(jax.tree.leaves(sv["gen"]),
                                jax.tree.leaves(out["state"]["gen"])))
    assert worst < 1e-6, f"bf16 proc diverged from vmap by {worst}"
    # the deposited mailbox state really crossed the process boundary in
    # bf16 (stacked back into the [R, ...] layout by the launcher)
    assert any(jnp.asarray(x).dtype == jnp.bfloat16
               for x in jax.tree.leaves(out["state"]["sync"]))


# ----------------------------------------------------------------------------
# cadence: HLO-level disappearance + trajectory semantics


def _disc_dot_count(txt):
    """dot_generals touching the discriminator's unique 192-wide hidden
    layers (generator hiddens are 128-wide, gan.DISC_WIDTHS vs GEN_WIDTHS)."""
    return sum(1 for ln in txt.splitlines()
               if "dot_general" in ln and "192" in ln)


def test_disc_every2_off_epochs_have_no_disc_update_matmuls():
    """The off-epoch branch must contain ONLY the generator objective's
    flow-through-discriminator matmuls (those are the generator's
    gradient path and can never be skipped) — none of the discriminator
    UPDATE's own forward/backward.  Counted structurally: the cadenced
    lowering is exactly one every-epoch branch plus one gen-only branch,
    under a real `stablehlo.case` (a batched predicate would have become
    a select computing both, doubling the count)."""
    base = small_wcfg(SyncConfig(mode="rma_arar_arar", h=2))
    every = _lower_epoch(base)
    cadenced = _lower_epoch(small_wcfg(SyncConfig(mode="rma_arar_arar", h=2),
                                       disc_every=2))
    # the gen-only branch in isolation: rank_grads with the disc half off
    R = 4
    state = workflow.init_state(jax.random.PRNGKey(0), R, base)
    dpr = jnp.stack([_data(n=100)] * R)
    ft = jax.jit(jax.vmap(lambda s, d: workflow.rank_grads(
        s, d, base, update_disc=False, update_gen=True)))
    gen_only = ft.lower(state, dpr).as_text()

    n_every, n_cad = _disc_dot_count(every), _disc_dot_count(cadenced)
    n_gen_only = _disc_dot_count(gen_only)
    assert n_every > 0, "pin lost its subject: no 192-wide disc matmuls"
    assert 0 < n_gen_only < n_every, (n_gen_only, n_every)
    # a real branch, not a select
    assert "case" in cadenced and "case" not in every
    assert n_cad == n_every + n_gen_only, \
        f"off-epoch branch is not the gen-only body: {n_cad} != " \
        f"{n_every} + {n_gen_only} disc matmuls"
    # donation survives the conditional
    assert cadenced.count("tf.aliasing_output") >= every.count(
        "tf.aliasing_output") > 0


def test_cadence_trajectory_semantics():
    """disc_every=2: discriminator params freeze on off-epochs, rng stays
    draw-for-draw with the every-epoch run, and the generator still
    updates every epoch; gen_every=2: generator + Adam freeze on its
    off-epochs while the epoch counter advances."""
    data = _data()
    sc = dict(mode="rma_arar_arar", h=2)
    every = small_wcfg(SyncConfig(**sc))
    R = 4
    state0 = workflow.init_state(jax.random.PRNGKey(0), R, every)
    dpr = jnp.stack([data[:200]] * R)

    def run(wcfg, n):
        fn = workflow.make_epoch_fn_vmap(2, 2, wcfg)
        s = jax.tree.map(jnp.copy, state0)
        hist = []
        for _ in range(n):
            s, m = fn(s, dpr)
            hist.append(m)
        return s, hist

    s_d2, h_d2 = run(small_wcfg(SyncConfig(**sc), disc_every=2), 2)
    s_ev, h_ev = run(every, 2)
    # epoch 0 is disc-due on both; epoch 1 skipped -> disc params frozen
    # at the epoch-0 values, i.e. they differ from the every-epoch run
    assert any(float(jnp.max(jnp.abs(a - b))) > 0
               for a, b in zip(jax.tree.leaves(s_d2["disc"]),
                               jax.tree.leaves(s_ev["disc"])))
    # skipped half reports NaN d_loss, live half stays finite
    assert bool(jnp.all(jnp.isnan(h_d2[1]["d_loss"])))
    assert bool(jnp.all(jnp.isfinite(h_d2[1]["g_loss"])))
    # rng advanced identically: the epoch-0 metrics are bitwise shared
    np.testing.assert_array_equal(np.asarray(h_d2[0]["g_loss"]),
                                  np.asarray(h_ev[0]["g_loss"]))

    s_g2, h_g2 = run(small_wcfg(SyncConfig(**sc), gen_every=2), 2)
    # gen epoch 1 skipped: params+opt state frozen at the epoch-0 result,
    # but the epoch counter still advanced both epochs
    assert int(s_g2["epoch"][0]) == 2
    assert bool(jnp.all(jnp.isnan(h_g2[1]["g_loss"])))
    assert bool(jnp.all(jnp.isfinite(h_g2[1]["d_loss"])))


def test_cadence_composes_with_chunked_scan_and_checkpoint(tmp_path):
    """The cadence conds live inside the scanned epoch body: a chunked
    run equals the epoch-by-epoch run, and a mid-run checkpoint resume
    stays on the cadence grid (bitwise)."""
    data = _data()
    wcfg = small_wcfg(SyncConfig(mode="rma_arar_arar", h=2),
                      disc_every=2, gen_every=3)
    s_chunk, _ = workflow.train_vmap(jax.random.PRNGKey(0), wcfg, 2, 2, 6,
                                     data, chunk=6)
    s_step, _ = workflow.train_vmap(jax.random.PRNGKey(0), wcfg, 2, 2, 6,
                                    data, chunk=1)
    for a, b in zip(jax.tree.leaves(s_chunk["gen"]),
                    jax.tree.leaves(s_step["gen"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    d = str(tmp_path / "ck")
    workflow.train_vmap(jax.random.PRNGKey(0), wcfg, 2, 2, 4, data,
                        checkpoint_every=2, checkpoint_dir=d)
    s_res, _ = workflow.train_vmap(jax.random.PRNGKey(0), wcfg, 2, 2, 6,
                                   data, checkpoint_every=2,
                                   checkpoint_dir=d, resume=True)
    for a, b in zip(jax.tree.leaves(s_chunk["gen"]),
                    jax.tree.leaves(s_res["gen"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------------------
# property tests: FusionSpec pack/unpack over arbitrary layouts (ISSUE 8 —
# generalizing the fixed generator-shaped cases above)

from hypothesis import given, settings, strategies as st


def _random_tree(n_leaves, dims, masks, seed):
    """A pytree of `n_leaves` fp32 leaves with drawn shapes + bool mask.

    Shapes come from the drawn `dims` list (rank 0-2); `masks` decides
    which leaves ride the payload.  At least one leaf is forced masked so
    the payload is never empty (the engine always syncs something)."""
    rng = np.random.default_rng(seed)
    leaves, mask = {}, {}
    for i in range(n_leaves):
        rank = dims[3 * i] % 3
        shape = tuple(d + 1 for d in dims[3 * i + 1: 3 * i + 1 + rank])
        leaves[f"l{i}"] = jnp.asarray(
            rng.standard_normal(shape), jnp.float32)
        mask[f"l{i}"] = bool(masks[i]) or i == 0
    return leaves, mask


@settings(max_examples=25)
@given(st.integers(1, 5),
       st.lists(st.integers(0, 6), min_size=15, max_size=15),
       st.lists(st.booleans(), min_size=5, max_size=5),
       st.sampled_from(["fp32", "bf16"]),
       st.integers(0, 10_000))
def test_fusionspec_roundtrip_property(n_leaves, dims, masks, precision,
                                       seed):
    """flatten -> unflatten round-trips ANY leaf layout: masked leaves come
    back at master fp32 (bitwise at fp32 wire; within one bf16 rounding at
    bf16 wire), unmasked leaves pass through untouched, and the payload
    carries exactly the masked element count at the wire dtype."""
    tree, mask = _random_tree(n_leaves, dims, masks, seed)
    spec = sync_lib.FusionSpec.build(
        tree, mask, payload_dtype=sync_lib.payload_dtype_of(precision))

    payload = spec.flatten(tree, stacked=False)
    assert payload.dtype == spec.payload_dtype
    assert payload.shape == (sum(
        v.size for k, v in tree.items() if mask[k]),)

    zeros = jax.tree.map(jnp.zeros_like, tree)
    back = spec.unflatten(payload, zeros, stacked=False)
    for k in tree:
        assert back[k].dtype == jnp.float32          # master dtype restored
        if not mask[k]:
            np.testing.assert_array_equal(np.asarray(back[k]), 0.0)
        elif precision == "fp32":
            np.testing.assert_array_equal(np.asarray(back[k]),
                                          np.asarray(tree[k]))
        else:                                        # one bf16 rounding
            np.testing.assert_array_equal(
                np.asarray(back[k]),
                np.asarray(tree[k].astype(jnp.bfloat16)
                           .astype(jnp.float32)))


@settings(max_examples=10)
@given(st.integers(1, 4),
       st.lists(st.integers(0, 6), min_size=15, max_size=15),
       st.lists(st.booleans(), min_size=5, max_size=5),
       st.sampled_from(["fp32", "bf16"]),
       st.integers(2, 5))
def test_fusionspec_roundtrip_property_stacked(n_leaves, dims, masks,
                                               precision, n_ranks):
    """The stacked [R, ...] layout round-trips identically: per-rank rows
    of the [R, D] payload are independent (rank r's row reconstructs rank
    r's leaves and nothing else)."""
    tree1, mask = _random_tree(n_leaves, dims, masks, seed=7)
    stacked = jax.tree.map(
        lambda x: jnp.stack([x * (r + 1) for r in range(n_ranks)]), tree1)
    spec = sync_lib.FusionSpec.build(
        tree1, mask, payload_dtype=sync_lib.payload_dtype_of(precision))

    payload = spec.flatten(stacked, stacked=True)
    assert payload.shape == (n_ranks, spec.total)
    back = spec.unflatten(payload, jax.tree.map(jnp.zeros_like, stacked),
                          stacked=True)
    for k in tree1:
        for r in range(n_ranks):
            want = np.asarray(stacked[k][r])
            if precision == "bf16":
                want = np.asarray(stacked[k][r].astype(jnp.bfloat16)
                                  .astype(jnp.float32))
            got = np.asarray(back[k][r] if mask[k]
                             else jnp.zeros_like(stacked[k][r]))
            np.testing.assert_array_equal(
                got, want if mask[k] else np.zeros_like(want))
