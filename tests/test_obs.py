"""Tier-1 tests for ISSUE 10: the unified telemetry layer.

Three channels, one invariant each (`scripts/check.sh --obs`):

  metrics   the jit-safe obs channel is schedule-owned and chunk-flushed:
            a DISABLED run lowers to byte-identical HLO (vmap and shard),
            and an ENABLED run leaves the golden proxy1d trajectory
            bitwise untouched — telemetry may never perturb training;
  tracing   every `obs.trace.span` is a profiler TraceAnnotation, and
            with a tracer installed also crash-safe line-at-a-time JSONL
            in Chrome-trace event form: span nesting depths, torn-tail
            tolerance and the Perfetto merge round-trip are pinned; the
            epoch program's matmuls each carry one of the five layer
            scopes in their HLO locations;
  serving   counters/latency histograms behind `SolveService.snapshot()`,
            with the queue recording a rejection INSIDE its lock before
            `Backpressure` propagates (audited under a Gate
            interleaving), so counts never undercount.

Plus the layering lint (repo-lint check 9) and, in the slow lane, the
acceptance run: a free-running 2-process trace that `scripts/obsview.py`
merges into a loadable Chrome trace whose skew counters match the run
summaries.
"""
import importlib.util
import json
import os
import re
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis.faults import InterleavingDriver
from repro.core import gan, workflow
from repro.core.sync import SyncConfig
from repro.core.workflow import WorkflowConfig
from repro.launch.mesh import make_mesh
from repro.obs import trace as obs_trace
from repro.obs.config import OBS_SCHEMA_VERSION, ObsConfig
from repro.obs.counters import Counters, LatencyHistogram
from repro.obs.metrics import MetricsWriter, chunk_row
from repro.obs.trace import (Tracer, load_events, merge_traces,
                             write_chrome_trace)
from repro.problems import get_problem
from repro.runtime import mailbox as mbx_mod
from repro.runtime.jitter import JitterConfig
from repro.runtime.launch import run_proc
from repro.serving import (Backpressure, BoundedRequestQueue, ServingConfig,
                           SolveService)
from repro.serving import queue as serving_queue

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_lint():
    spec = importlib.util.spec_from_file_location(
        "repro_lint", os.path.join(ROOT, "scripts", "repro_lint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


lint = _load_lint()
RING_SRC = open(os.path.join(ROOT, "src", "repro", "core", "ring.py")).read()


def small_wcfg(sync, obs=ObsConfig(), problem="proxy1d"):
    return WorkflowConfig(problem=problem, sync=sync, obs=obs,
                          n_param_samples=8, events_per_sample=4)


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    yield
    t = obs_trace.uninstall()
    if t is not None:
        t.close()


# ----------------------------------------------------------------------------
# config


def test_obs_config_defaults_inert():
    obs = ObsConfig()
    assert not obs.metrics and obs.metrics_out is None
    assert obs.trace_dir is None and obs.profile_dir is None


def test_obs_config_metrics_out_needs_metrics():
    ObsConfig(metrics=True, metrics_out="m.jsonl")       # ok
    with pytest.raises(ValueError, match="metrics"):
        ObsConfig(metrics=False, metrics_out="m.jsonl")


# ----------------------------------------------------------------------------
# disabled-obs HLO identity — the zero-cost claim, pinned at the
# StableHLO byte level on both SPMD drivers

SCHEDULES = {
    "sync": SyncConfig(mode="conv_arar", h=2),
    "overlap": SyncConfig(mode="rma_arar_arar", h=2, staleness=2,
                          overlap=True),
    "adaptive": SyncConfig(mode="rma_arar_arar", h=2, staleness=3,
                           adaptive=True),
}


def _lower_vmap(wcfg, R=4, debug_info=False):
    state = workflow.init_state(jax.random.PRNGKey(0), R, wcfg)
    data = wcfg.problem_obj.make_reference_data(jax.random.PRNGKey(1), 100)
    fn = workflow.make_epoch_fn_vmap(2, R // 2, wcfg)
    return fn.lower(state, jnp.stack([data] * R)).as_text(
        debug_info=debug_info)


def _lower_shard(wcfg, debug_info=False):
    mesh = make_mesh((1, 1), ("pod", "data"))
    state = workflow.init_state(jax.random.PRNGKey(0), 1, wcfg)
    data = wcfg.problem_obj.make_reference_data(jax.random.PRNGKey(1), 100)
    fn, _shardings = workflow.make_epoch_fn_shard(mesh, wcfg)
    return fn.lower(state, jnp.stack([data] * 1)).as_text(
        debug_info=debug_info)


@pytest.mark.parametrize("label", sorted(SCHEDULES))
def test_disabled_obs_hlo_byte_identical_vmap(label, tmp_path):
    """Host-side knobs (trace_dir, profile_dir) must not reach the traced
    program at all: the lowered vmap epoch is byte-for-byte the default
    ObsConfig lowering, for every schedule family."""
    sync = SCHEDULES[label]
    base = _lower_vmap(small_wcfg(sync))
    host = _lower_vmap(small_wcfg(sync, obs=ObsConfig(
        trace_dir=str(tmp_path / "t"), profile_dir=str(tmp_path / "p"))))
    assert base == host


def test_disabled_obs_hlo_byte_identical_shard(tmp_path):
    sync = SCHEDULES["overlap"]
    base = _lower_shard(small_wcfg(sync))
    host = _lower_shard(small_wcfg(sync, obs=ObsConfig(
        trace_dir=str(tmp_path / "t"), profile_dir=str(tmp_path / "p"))))
    assert base == host


def test_enabled_metrics_changes_lowering_only_when_on():
    """Sanity bound on the identity pins above: metrics=True DOES grow
    the traced program (the obs channel is real), on both drivers."""
    sync = SCHEDULES["adaptive"]
    assert _lower_vmap(small_wcfg(sync)) != \
        _lower_vmap(small_wcfg(sync, obs=ObsConfig(metrics=True)))
    assert _lower_shard(small_wcfg(sync)) != \
        _lower_shard(small_wcfg(sync, obs=ObsConfig(metrics=True)))


# ----------------------------------------------------------------------------
# layer scopes: every matmul of the epoch program names its layer

LAYER_SCOPE = re.compile(
    r"sagips_(?:sample|gen|disc|exchange|apply)(?![A-Za-z0-9_])")
LOC_DEF = re.compile(r"^(#loc\d*) = (.*)$", re.M)
MATMUL = re.compile(r"stablehlo\.(?:dot_general|convolution)\b.*"
                    r"loc\((.*)\)\s*$")


def _op_name(defs, loc, depth=0):
    """The `op_name` path of a location (the first string that names a
    jitted function's op), following `#loc` aliases."""
    for m in re.finditer(r'"([^"]*)"|(#loc\d*)', loc):
        if m.group(1) is not None and m.group(1).startswith("jit("):
            return m.group(1)
        if m.group(2) and depth < 50:
            found = _op_name(defs, defs.get(m.group(2), ""), depth + 1)
            if found:
                return found
    return None


@pytest.mark.parametrize("body", ["vmap", "shard"])
@pytest.mark.parametrize("problem", ["proxy1d", "imaging"])
def test_epoch_program_matmuls_carry_a_layer_scope(body, problem):
    """The five `jax.named_scope`s reach the lowered program: every
    dot_general and convolution's location names one of them (the device
    trace attributes operations by this path)."""
    wcfg = small_wcfg(SyncConfig(mode="rma_arar_arar", h=2),
                      problem=problem)
    lower = _lower_vmap if body == "vmap" else _lower_shard
    text = lower(wcfg, debug_info=True)
    defs = dict(LOC_DEF.findall(text))
    names = [_op_name(defs, m.group(1))
             for m in map(MATMUL.search, text.splitlines()) if m]
    assert len(names) >= 8
    unscoped = [n for n in names if not (n and LAYER_SCOPE.search(n))]
    assert not unscoped, unscoped[:5]
    scopes = {LAYER_SCOPE.findall(n)[-1] for n in names}
    assert {"sagips_gen", "sagips_disc"} <= scopes
    # the debug-free text the HLO-identity pins compare carries no scope
    assert "sagips_disc" not in lower(wcfg)


# ----------------------------------------------------------------------------
# metrics-enabled golden: telemetry never perturbs training


def test_golden_proxy1d_bitwise_with_metrics_enabled(tmp_path):
    """The golden proxy1d trajectory (pinned in test_problems.py) must
    stay BITWISE identical with the metrics channel on and flushing —
    the obs state rides along in the carry without touching a single
    training value."""
    golden = np.load(os.path.join(os.path.dirname(__file__),
                                  "golden_proxy1d_epoch.npz"))
    out = str(tmp_path / "metrics.jsonl")
    wcfg = WorkflowConfig(n_param_samples=32, events_per_sample=10,
                          obs=ObsConfig(metrics=True, metrics_out=out))
    data = wcfg.problem_obj.make_reference_data(jax.random.PRNGKey(42), 2000)
    state, hist = workflow.train_vmap(jax.random.PRNGKey(0), wcfg, 2, 2, 2,
                                      data, checkpoint_every=1)
    for i, leaf in enumerate(jax.tree.leaves(state["gen"])):
        np.testing.assert_array_equal(np.asarray(leaf), golden[f"gen_{i}"],
                                      err_msg=f"gen leaf {i} diverged")
    for k in ("residuals", "d_loss", "g_loss", "pred_params"):
        np.testing.assert_array_equal(np.asarray(hist[k]), golden[k],
                                      err_msg=f"history {k!r} diverged")
    # the run also produced a self-describing metrics file: header + one
    # row per chunk (checkpoint_every=1 -> 1-epoch chunks)
    lines = [json.loads(l) for l in open(out)]
    assert lines[0]["kind"] == "header"
    assert lines[0]["schema"] == OBS_SCHEMA_VERSION
    assert lines[0]["n_ranks"] == 4 and lines[0]["payload_bytes"] > 0
    rows = [l for l in lines[1:] if l["kind"] == "row"]
    assert [r["epoch"] for r in rows] == [1, 2]
    assert all(np.isfinite(r["d_loss"]) for r in rows)


# ----------------------------------------------------------------------------
# obs channel semantics — the counters the schedules publish


def _train_obs(sync, n_epochs=4):
    wcfg = small_wcfg(sync, obs=ObsConfig(metrics=True))
    data = wcfg.problem_obj.make_reference_data(jax.random.PRNGKey(7), 400)
    _state, hist = workflow.train_vmap(jax.random.PRNGKey(0), wcfg, 2, 2,
                                       n_epochs, data, checkpoint_every=1)
    return hist["obs"]


def test_overlap_ship_count_accumulates_on_ship_epochs():
    """Static overlap with h=2 ships at the pod boundary on every 2nd
    epoch; the cumulative ship_count and the per-epoch shipped gauge
    must say exactly that."""
    obs = _train_obs(SyncConfig(mode="rma_arar_arar", h=2, staleness=2,
                                overlap=True))
    np.testing.assert_array_equal(np.asarray(obs["shipped"][:, 0]),
                                  [0, 1, 0, 1])
    np.testing.assert_array_equal(np.asarray(obs["ship_count"][:, 0]),
                                  [0, 1, 1, 2])
    np.testing.assert_array_equal(np.asarray(obs["exchange_count"][:, 0]),
                                  [1, 2, 3, 4])


def test_adaptive_lockstep_reports_k_one_zero_skew():
    """The SPMD simulators are perfectly synchronous, so the adaptive
    controller's published k_eff must stay 1 and skew_ema 0 — the same
    pin test_schedule makes on the controller state, read back through
    the obs channel."""
    obs = _train_obs(SyncConfig(mode="rma_arar_arar", h=2, staleness=3,
                                adaptive=True))
    assert np.asarray(obs["k_eff"]).min() == 1
    assert np.asarray(obs["k_eff"]).max() == 1
    assert float(np.abs(np.asarray(obs["skew_ema"])).max()) == 0.0
    assert np.asarray(obs["deposit_age"]).max() <= 3   # clamped by k


def test_chunk_row_reduces_last_epoch():
    metrics = {
        "d_loss": np.array([[1.0, 3.0], [2.0, 4.0]]),     # [chunk, R]
        "residuals": np.array([[9.0, 9.0], [5.0, 7.0]]),
        "obs": {"k_eff": np.array([[1, 1], [2, 3]]),
                "shipped": np.array([[0, 0], [1, 0]]),
                "ship_count": np.array([[0, 0], [1, 0]]),
                "exchange_count": np.array([[1, 1], [2, 2]]),
                "skew_ema": np.array([[0.0, 0.0], [0.5, 0.25]]),
                "deposit_age": np.array([[0.0, 0.0], [2.0, 1.0]])},
    }
    row = chunk_row(2, metrics)
    assert row["epoch"] == 2
    assert row["d_loss"] == pytest.approx(3.0)        # mean of last epoch
    assert row["residual"] == pytest.approx(6.0)
    assert row["k_eff"] == 3 and row["ship_count"] == 1   # rank max
    assert row["skew_ema"] == pytest.approx(0.5)
    assert row["deposit_age"] == pytest.approx(2.0)


# ----------------------------------------------------------------------------
# span tracer units


def test_tracer_span_nesting_and_containment(tmp_path):
    p = str(tmp_path / "t.jsonl")
    tr = Tracer(p, rank=3)
    with tr.span("outer", cat="wait", what="x"):
        with tr.span("inner", cat="wire"):
            pass
    tr.close()
    events, skipped = load_events(p)
    assert skipped == 0
    by_name = {e["name"]: e for e in events}
    inner, outer = by_name["inner"], by_name["outer"]
    assert outer["args"]["depth"] == 0 and inner["args"]["depth"] == 1
    assert outer["args"]["what"] == "x"
    assert all(e["pid"] == 3 and e["ph"] == "X" for e in events)
    # the inner span's interval sits inside the outer's
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3


def test_tracer_crash_safe_skips_torn_tail(tmp_path):
    p = str(tmp_path / "t.jsonl")
    tr = Tracer(p)
    with tr.span("checkpoint"):
        pass
    tr.counter("k_eff", 2)
    tr.close()
    with open(p, "a") as f:                  # a worker killed mid-write
        f.write('{"name": "torn", "ph": "X", "ts": 12')
    events, skipped = load_events(p)
    assert skipped == 1
    assert [e["ph"] for e in events] == ["X", "C"]
    assert events[0]["name"] == "checkpoint"
    assert events[1]["args"] == {"k_eff": 2}


def test_tracer_closed_emit_is_silent(tmp_path):
    tr = Tracer(str(tmp_path / "t.jsonl"))
    tr.close()
    with tr.span("after-close"):             # must not raise
        pass
    events, _ = load_events(tr.path)
    assert events == []


def test_module_span_is_profiler_annotation_when_uninstalled():
    """Without a tracer a span is a bare profiler TraceAnnotation (no
    JSONL sink to feed), and a counter is silently dropped."""
    assert obs_trace.current_tracer() is None
    s1 = obs_trace.span("a")
    s2 = obs_trace.span("b", cat="wait", arg=1)
    assert isinstance(s1, jax.profiler.TraceAnnotation)
    assert isinstance(s2, jax.profiler.TraceAnnotation)
    assert s1 is not s2
    with s1:
        with s2:
            obs_trace.counter("noop", 1.0)
    assert obs_trace.current_tracer() is None


def _profiled(tmp_path, body):
    """Run `body()` inside a CPU profiler session; the host events by name
    as {name: [stats dict, ...]}."""
    import glob
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                      recursive=True)
    events = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(dict(e.stats))
    return events


def test_module_span_reaches_the_profiler_without_a_tracer(tmp_path):
    def body():
        with obs_trace.span("sagips.test.outer", cat="wait", epoch=3):
            with obs_trace.span("sagips.test.inner"):
                pass
    events = _profiled(tmp_path, body)
    assert events["sagips.test.outer"] == [{"epoch": 3}]
    assert len(events["sagips.test.inner"]) == 1
    assert obs_trace.current_tracer() is None


def test_module_span_reaches_profiler_and_jsonl_with_a_tracer(tmp_path):
    p = str(tmp_path / "t.jsonl")
    obs_trace.install(Tracer(p, rank=2))

    def body():
        with obs_trace.span("sagips.test.exchange", cat="wire", epoch=5):
            pass
    events = _profiled(tmp_path, body)
    obs_trace.uninstall().close()
    assert events["sagips.test.exchange"] == [{"epoch": 5}]
    lines, skipped = load_events(p)
    assert skipped == 0 and len(lines) == 1
    ev = lines[0]
    assert ev["name"] == "sagips.test.exchange" and ev["cat"] == "wire"
    assert ev["pid"] == 2 and ev["args"] == {"epoch": 5, "depth": 0}


def test_chrome_trace_merge_roundtrip(tmp_path):
    paths = []
    for rank in (0, 1):
        p = str(tmp_path / f"trace_rank{rank}.jsonl")
        tr = Tracer(p, rank=rank)
        with tr.span("exchange", cat="wire", epoch=0):
            pass
        tr.counter("skew_ema", 0.5 * rank)
        tr.close()
        paths.append(p)
    out = str(tmp_path / "merged.json")
    write_chrome_trace(out, merge_traces(paths))
    doc = json.load(open(out))               # Perfetto-loadable JSON
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    meta = {e["pid"]: e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert meta == {0: "rank 0", 1: "rank 1"}
    body = [e for e in evs if e["ph"] != "M"]
    assert {e["pid"] for e in body} == {0, 1}
    assert min(e["ts"] for e in body) == 0.0   # rebased to first event
    assert all(e["ts"] >= 0 for e in body)


def test_lockstep_mailbox_records_rendezvous_spans(tmp_path):
    """The mailbox fabric's lock-step waits are traced: a paired
    write/read through one installed tracer records the rendezvous-wait
    spans the skew report bills under cat='wait'."""
    tr = Tracer(str(tmp_path / "t.jsonl"))
    obs_trace.install(tr)
    p = str(tmp_path / "edge.bin")
    wr = mbx_mod.Mailbox.for_writer(p, 8, timeout=20.0)
    rd = mbx_mod.Mailbox.for_reader(p, 8, timeout=20.0)
    t = threading.Thread(target=lambda: wr.write(b"x" * 8, tag=1,
                                                 lockstep=True))
    t.start()
    assert rd.read(lockstep=True) == (b"x" * 8, 1)
    t.join(timeout=20)
    obs_trace.uninstall()
    tr.close()
    names = {e["name"] for e in load_events(tr.path)[0]}
    assert "mbx.rendezvous.write" in names and "mbx.rendezvous.read" in names
    assert "mbx.write" in names and "mbx.read" in names


# ----------------------------------------------------------------------------
# serving counters


def test_latency_histogram_snapshot_fields():
    h = LatencyHistogram()
    for v in (0.001, 0.001, 0.002, 0.1):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 4
    assert snap["sum_s"] == pytest.approx(0.104)
    assert 0 < snap["p50_s"] <= snap["p90_s"] <= snap["p99_s"]
    assert snap["p99_s"] >= 0.1              # bucket upper edge >= sample
    assert LatencyHistogram().snapshot()["p50_s"] == 0.0


def test_counters_inc_observe_snapshot():
    c = Counters()
    c.inc("a")
    c.inc("a", 2)
    c.observe("lane", 0.01)
    snap = c.snapshot()
    assert snap["counters"] == {"a": 3} and c.get("a") == 3
    assert snap["latency"]["lane"]["count"] == 1
    assert c.get("missing") == 0


def _tiny_cfg():
    return ServingConfig(
        buckets=(16, 64), max_batch=4, queue_capacity=16, cache_capacity=4,
        retry_after_s=0.01,
        solve=workflow.SolveConfig(n_candidates=8, events_per_candidate=8))


def _prior_stack(prob, ranks=2, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), ranks)
    return jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[gan.init_generator(k, n_params=prob.n_params) for k in keys])


def test_service_snapshot_rates_and_latency_lanes():
    prob = get_problem("proxy1d")
    svc = SolveService(_tiny_cfg())
    svc.register_problem("proxy1d", gen_stack=_prior_stack(prob))

    def wave(n):
        key = jax.random.PRNGKey(n)
        for _ in range(n):
            key, k = jax.random.split(key)
            svc.submit("proxy1d",
                       np.asarray(prob.make_reference_data(k, 12)))
        svc.run_until_empty()

    wave(2)                                  # cold: compile-cache miss
    wave(1)                                  # warm: hit
    snap = svc.snapshot()
    assert snap["served"] == 3 and snap["queue_depth"] == 0
    assert snap["reject_rate"] == 0.0
    assert snap["retry_after_s"] == pytest.approx(0.01)
    assert snap["cache_hit_rate"] == pytest.approx(0.5)   # 1 hit / 1 miss
    assert snap["counters"]["queue.admitted"] == 3
    assert snap["counters"]["queue.drained"] == 3
    lane = snap["latency"]["proxy1d/b16"]
    assert lane["count"] == 3 and lane["p50_s"] > 0
    # latency is queue-inclusive: mean covers submit->resolve
    assert lane["mean_s"] > 0


def test_queue_reject_recorded_before_raise_under_gate():
    """ISSUE 10 satellite fix: the rejection lands in stats AND counters
    inside the queue lock, BEFORE `Backpressure` propagates.  Park the
    rejected submitter at the post-lock 'queue.reject' hook (pre-raise)
    and observe: every counter already shows the rejection."""
    c = Counters()
    q = BoundedRequestQueue(1, retry_after_s=0.01, counters=c)
    q.submit(("p", 16), "fill")
    with InterleavingDriver(set_hook=serving_queue.set_hook) as drv:
        gate = drv.gate("queue.reject", hit=1)
        res = {}

        def victim():
            try:
                q.submit(("p", 16), "one-too-many")
            except Backpressure as e:
                res["retry_after"] = e.retry_after_s

        t = threading.Thread(target=victim)
        t.start()
        gate.wait_reached()                  # parked pre-raise
        assert q.stats["rejected"] == 1      # already recorded
        assert c.get("queue.rejected") == 1
        gate.release()
        t.join(timeout=20)
        assert not t.is_alive()
    assert res["retry_after"] == pytest.approx(0.01)
    assert q.stats["admitted"] == 1 and c.get("queue.admitted") == 1
    # the parked rejection drained nothing and double-counted nothing
    assert q.drain(("p", 16), 8) == ["fill"]
    assert c.get("queue.rejected") == 1


def test_serve_stats_printer_covers_snapshot(capsys):
    """`launch/serve.py --stats` renders every snapshot section without
    KeyErrors — pinned against the snapshot() contract."""
    from repro.launch.serve import _print_snapshot
    prob = get_problem("proxy1d")
    svc = SolveService(_tiny_cfg())
    svc.register_problem("proxy1d", gen_stack=_prior_stack(prob))
    svc.submit("proxy1d", np.asarray(
        prob.make_reference_data(jax.random.PRNGKey(0), 12)))
    svc.run_until_empty()
    _print_snapshot(svc.snapshot())
    out = capsys.readouterr().out
    assert "reject rate" in out and "compile cache" in out
    assert "proxy1d/b16" in out


# ----------------------------------------------------------------------------
# repo-lint check 9: obs layering


def test_lint_obs_layering_flags_violations():
    srcs = {
        "core/ring.py": RING_SRC,
        "core/sync.py": "from ..obs.trace import span\n",
        "core/workflow.py": "from ..obs.counters import Counters\n",
        "runtime/launch.py": "from ..obs.metrics import chunk_row\n",
        "serving/service.py": "from ..obs import metrics\n",
    }
    problems = lint.lint_sources(srcs)
    flagged = [p for p in problems if "obs" in p]
    assert len(flagged) == 4
    assert any("core/sync.py:1: traced core imports host-side" in p
               for p in flagged)
    assert any("core/workflow.py:1" in p and "obs.counters" in p
               for p in flagged)
    assert any("runtime/launch.py:1: host backend imports traced-metrics"
               in p for p in flagged)
    assert any("serving/service.py:1" in p for p in flagged)


def test_lint_obs_layering_allows_correct_split():
    srcs = {
        "core/ring.py": RING_SRC,
        # traced core may import the context-free config + metrics flush
        "core/workflow.py": "from ..obs.config import ObsConfig\n"
                            "from ..obs.metrics import MetricsWriter\n",
        # host backends may import the tracer and counters
        "runtime/mailbox.py": "from ..obs.trace import span as _span\n",
        "serving/queue.py": "from ..obs.counters import Counters\n",
    }
    assert [p for p in lint.lint_sources(srcs) if "obs" in p] == []


def test_lint_repo_is_obs_clean():
    problems = lint.lint_sources(lint.repo_sources())
    assert [p for p in problems if "obs" in p.split(":")[-1]] == []


# ----------------------------------------------------------------------------
# acceptance (slow): free-running 2-process trace through obsview


@pytest.mark.slow
def test_proc_freerun_trace_merges_and_matches_summary(tmp_path):
    """A free-running 2-process run with `trace_dir` writes per-rank
    JSONL that obsview merges into a loadable Chrome trace with exchange
    and wait spans, and whose reported skew matches the run summary."""
    wcfg = small_wcfg(
        SyncConfig(mode="rma_arar_arar", h=1000, staleness=4, adaptive=True),
        obs=ObsConfig(metrics=True, trace_dir="trace"))
    run_dir = str(tmp_path / "run")
    out = run_proc(wcfg, 1, 2, 10, get_problem("proxy1d").make_reference_data(
        jax.random.PRNGKey(5), 400), seed=0, lockstep=False,
        jitter=JitterConfig(rank_lag_ms=30.0), run_dir=run_dir, timeout=420)

    for s in out["summaries"]:
        assert s["obs"]["exchange_count"] == 10
        assert s["obs"]["payload_bytes"] > 0

    tdir = os.path.join(run_dir, "trace")
    for r in (0, 1):
        assert os.path.exists(os.path.join(tdir, f"trace_rank{r}.jsonl"))

    view = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "obsview.py"),
         run_dir], capture_output=True, text=True, timeout=120)
    assert view.returncode == 0, view.stderr
    assert "merged 2 rank trace(s)" in view.stdout
    assert "max skew_ema" in view.stdout
    assert "MISMATCH" not in view.stdout     # counters agree with summaries

    doc = json.load(open(os.path.join(tdir, "merged_trace.json")))
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    assert {e["pid"] for e in evs} == {0, 1}
    names = {e["name"] for e in evs if e["ph"] == "X"}
    assert "epoch" in names and "barrier" in names
    assert any(n.startswith("exchange") for n in names)
    assert any(n == "jitter.sleep" for n in names)
    assert any(e["cat"] == "wait" for e in evs if e["ph"] == "X")
    # counter events carried the adaptive controller + deposit-age gauges
    counters = {e["name"] for e in evs if e["ph"] == "C"}
    assert {"skew_ema", "k_eff", "deposit_age"} <= counters
