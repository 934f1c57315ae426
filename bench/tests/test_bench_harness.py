"""The benchmark harness on the CPU, at tiny sizes.

Each test runs the harness as `bench/run.py` does, with the look for a
TPU skipped, in a copy of `bench/` with small configurations, traffic and
limits added as new files: what a later PR adds is found by name, with
no existing file edited.  Nothing here describes a TPU topology.
"""
from __future__ import annotations

import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

DATA = BENCH / "tests" / "data"

TINY_PROXY = {
    "name": "tiny_proxy1d", "source": "https://arxiv.org/abs/2407.00051",
    "problem": "proxy1d", "reference": "proxy1d",
    "sync": {"mode": "rma_arar_arar", "h": 3},
    "n_param_samples": 8, "events_per_sample": 10, "data_fraction": 0.5,
    "gen_lr": 1e-3, "disc_lr": 1e-3, "sampler_impl": "jnp",
    "disc_compute": "fp32", "reference_events": 400,
    "generator": {"kind": "mlp", "widths": [135, 128, 128, 128, 6]},
    "discriminator": {"widths": [2, 192, 192, 64, 1]},
    "serving": {"buckets": [16, 64], "max_batch": 4, "queue_capacity": 64,
                "cache_capacity": 4, "retry_after_s": 0.01},
    "solve": {"n_candidates": 32, "events_per_candidate": 16,
              "top_frac": 0.25, "seed": 0},
    "reduced": [],
}
TINY_IMAGING = dict(
    TINY_PROXY, name="tiny_imaging", problem="imaging", reference="imaging",
    n_param_samples=4, events_per_sample=8,
    generator={"kind": "conv", "noise_dim": 135, "base": 8,
               "channels": [32, 32, 16], "out_hw": 32},
    discriminator={"widths": [15, 192, 192, 64, 1]})
TRAFFIC = {
    "tiny_train": {"kind": "train_vmap", "n_outer": 2, "n_inner": 2,
                   "epochs_per_call": 2, "checked_calls": 3, "in_flight": 2,
                   "trace_seconds": 1},
    "tiny_shard": {"kind": "train_shard", "mesh": {"pod": 1, "data": 1},
                   "checked_calls": 3, "in_flight": 2, "trace_seconds": 1},
    "tiny_solve": {"kind": "solve_open", "arrivals": "poisson",
                   "n_ranks": 2, "rate_per_s": 60,
                   "events_min": 4, "events_max": 64, "truth_low": 0.1,
                   "truth_high": 0.9, "check_requests": 16, "grace_s": 20,
                   "trace_seconds": 1},
}
TRAIN_LIMITS = {"loss_gap": {"limit": 1e-4}, "grad_gap": {"limit": 1e-3},
                "update_gap": {"limit": 1e-3}, "epoch_gap": {"limit": 0},
                "window_nonfinite": {"limit": 0}}
SOLVE_LIMITS = {"params_gap": {"limit": 1e-3}, "score_gap": {"limit": 1e-3},
                "unanswered": {"limit": 0}}
CELLS = {
    "tiny_proxy1d.tiny_train": ("tiny_proxy1d", "tiny_train", TRAIN_LIMITS),
    "tiny_imaging.tiny_train": ("tiny_imaging", "tiny_train", TRAIN_LIMITS),
    "tiny_proxy1d.tiny_shard": ("tiny_proxy1d", "tiny_shard", TRAIN_LIMITS),
    "tiny_proxy1d.tiny_solve": ("tiny_proxy1d", "tiny_solve", SOLVE_LIMITS),
}


def make_tiny_bench(tmp: Path, mesh=None) -> Path:
    """A copy of the benchmark with the tiny cells added as new files."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cfg, like in ((TINY_PROXY, "tab3_proxy1d"),
                      (TINY_IMAGING, "imaging32")):
        path = root / "bench" / "configs" / f"{cfg['name']}.json"
        path.write_text(json.dumps(cfg))
        shutil.copy(BENCH / "counts" / f"{like}.py",
                    root / "bench" / "counts" / f"{cfg['name']}.py")
        spec["configs"].append({"name": cfg["name"], "source": cfg["source"],
                                "file": f"bench/configs/{cfg['name']}.json",
                                "reduced": [], "why": "tiny"})
    for name, tr in TRAFFIC.items():
        tr = dict(tr, mesh=mesh) if mesh and name == "tiny_shard" else tr
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(tr))
    train_cells = [c for c, v in CELLS.items() if "train" in v[1]
                   or "shard" in v[1]]
    for cell, (cfg, traffic, limits) in CELLS.items():
        spec["workloads"].append({"name": cell, "config": cfg,
                                  "traffic": traffic, "chips": 1,
                                  "why": "tiny"})
        (root / "bench" / "limits" / f"{cell}.json").write_text(
            json.dumps(limits))
    for m in spec["end_to_end"]:
        if m["name"] == "train_events_per_s":
            m["workloads"] += train_cells
        elif m["name"].startswith("solve_"):
            m["workloads"].append("tiny_proxy1d.tiny_solve")
    for m in spec["per_layer"]:
        if m["name"].startswith("train.") or m["name"] == "gan.matmul_ms":
            m["workloads"] += train_cells
        elif m["name"].startswith("solve."):
            m["workloads"].append("tiny_proxy1d.tiny_solve")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run_tiny(root: Path, cell: str, seed=7, seconds=0.5, trace=False,
             control=False, wrap_call=None) -> dict:
    from harness.cell import run_cell
    from harness.spec import Bench
    bench = Bench(root=root, bench_dir=root / "bench")
    return run_cell(cell, seed, seconds, trace, time.perf_counter(),
                    bench=bench, require_tpu=False, control=control,
                    wrap_call=wrap_call)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return make_tiny_bench(tmp_path_factory.mktemp("bench"))


# ---------------------------------------------------------------------------
# the yardstick: counts and peaks


def test_counts_match_the_hand_count():
    """FLOPs per rank-epoch from the published widths: Tab. III
    61.0 (discriminator step) + 20.3 (generator step through it) + 0.4
    (generator) = 81.7 GFLOP; imaging32 3.8 (conv generator) + 1.7
    (discriminator) = 5.5 GFLOP."""
    from harness.spec import Bench
    bench = Bench()
    tab3 = bench.counts("tab3_proxy1d").flops_per_rank_epoch(
        bench.config("tab3_proxy1d"))
    img = bench.counts("imaging32").flops_per_rank_epoch(
        bench.config("imaging32"))
    d = 2 * 49_600
    assert tab3 == pytest.approx(3 * d * 204_800 + 2 * d * 102_400
                                 + 4 * 2 * 50_816 * 1024)
    assert tab3 == pytest.approx(81.7e9, rel=2e-3)
    assert img == pytest.approx(4 * 2 * 7_501_824 * 64
                                + 8 * 2 * 52_096 * 2048)
    assert img == pytest.approx(5.55e9, rel=2e-3)


def test_peaks_refuse_an_unknown_device():
    from harness.device import DeviceError, peaks_for
    v5e = peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(DeviceError, match="no peaks"):
        peaks_for("TPU v99")


def test_a_run_off_the_tpu_is_refused():
    from harness.device import DeviceError, check_devices
    with pytest.raises(DeviceError, match="no TPU"):
        check_devices(1)
    with pytest.raises(DeviceError, match="asks for 4"):
        check_devices(4, require_tpu=False)


def test_run_py_exits_nonzero_without_a_result_off_the_tpu(tmp_path):
    """The command as the benchmark is run: on the CPU it prints nothing
    on standard output and exits with another code than 0."""
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "HOME": str(tmp_path), "TMPDIR": str(tmp_path)}
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "tab3_proxy1d.train_r16", "--seed", str(2 ** 31 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_seeds_past_32_bits_give_distinct_keys():
    import numpy as np
    from harness.seeds import cell_keys
    a = cell_keys(2 ** 31 + 7)["run"]
    b = cell_keys(2 ** 32 + 2 ** 31 + 7)["run"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# the per-layer readers, on recorded chip traces


RECORDED = sorted(DATA.glob("*.json.gz"))


class _Run:
    def __init__(self, rec, cfg):
        from harness.device import peaks_for
        self.trace = rec["trace"]
        self.facts = rec["facts"]
        self.window_s = rec["facts"]["window_s"]
        self.chips = len(rec["trace"]["devices"])
        self.peaks = peaks_for("TPU v5 lite")
        self.config = cfg
        self.traffic = {}


@pytest.mark.parametrize("path", RECORDED, ids=[p.name for p in RECORDED])
def test_per_layer_readers_read_a_recorded_chip_trace(path):
    """Every per-layer metric of the recorded cell is read, and every
    share lies in (0, 100]."""
    from harness import trace
    from harness.spec import Bench
    assert path.stat().st_size < 1_000_000
    rec = trace.load(str(path))
    bench = Bench()
    cell = path.name[:-len(".json.gz")]
    w = bench.workload(cell)
    run = _Run(rec, bench.config(w["config"]))
    metrics = bench.per_layer(cell)
    assert metrics
    for m in metrics:
        v = bench.metric_reader(m["name"])(run)
        assert v is not None, m["name"]
        assert v == v and v >= 0, (m["name"], v)
        if m["unit"] == "%":
            assert 0 < v <= 100, (m["name"], v)
    b = trace.breakdown(rec["trace"])
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_trace_classes_from_hlo_text():
    from harness import trace
    conv = ("%convolution_add_fusion.13 = f32[16,102400,192]{1,2,0:T(8,128)}"
            " fusion(f32[16,2,192]{2,1,0} %a), kind=kOutput, calls=%f.45")
    loop = ("%fusion.12 = (u32[16,2]{0,1:T(2,128)}, u32[16,2]{0,1}) "
            "fusion(u32[16,3,1]{0,2,1} %g), kind=kLoop, calls=%f.537")
    perm = ("%collective-permute-done.1 = f32[1,51206]{1,0} "
            "collective-permute-done(f32[1,51206]{1,0} %s)")
    loop_op = "%while.4 = (s32[]{:T(128)}, f32[16,192]{1,0}) while(%t)"
    assert trace.classify(conv) == "matmul"
    assert trace.classify(loop) == "other"
    assert trace.classify(perm) == "collective"
    assert trace.classify(loop_op) == "container"


def test_exposed_collective_time():
    from harness import trace
    data = {"t0": 0, "t1": 100, "host": [], "devices": [[
        [0, 10, "%a", "other"], [10, 5, "%p", "collective"],
        [12, 20, "%b", "other"], [40, 10, "%q", "collective"]]]}
    # 10-12 and 40-50 have only the collective running
    assert trace.exposed_collective_seconds(data) == pytest.approx(12e-9)
    assert trace.per_device_idle(data) == [pytest.approx(0.58)]


# ---------------------------------------------------------------------------
# the harness end to end at tiny sizes, and its check


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_tiny_cell_runs_and_is_correct(tiny, cell):
    out = run_tiny(tiny, cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert "setup_s" in out["metrics"]
    assert len(out["metrics"]) >= 2


def test_a_dropped_in_metric_is_found_by_name(tiny):
    """A new per-layer metric is one file and one BENCHMARK.json entry."""
    (tiny / "bench" / "metrics" / "train.epochs_in_window.py").write_text(
        "def read(run):\n    return float(run.facts['epochs'])\n")
    spec = json.loads((tiny / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "train.epochs_in_window", "unit": "epochs",
        "better": "higher", "source": "program_counter",
        "layer": "epoch program", "moves": "train_events_per_s",
        "workloads": ["tiny_proxy1d.tiny_train"]})
    (tiny / "BENCHMARK.json").write_text(json.dumps(spec))
    out = run_tiny(tiny, "tiny_proxy1d.tiny_train", trace=True)
    assert out["correct"]
    assert out["metrics"]["train.epochs_in_window"]["value"] > 0
    assert "breakdown" in out and "window_s" in out["device"]


def _add_cell(root: Path, cell: str, config: str, traffic: str, tr: dict,
              limits: dict, e2e=()):
    """A new traffic mix, its limits and its cell, as new files and new
    BENCHMARK.json entries."""
    (root / "bench" / "traffic" / f"{traffic}.json").write_text(
        json.dumps(tr))
    (root / "bench" / "limits" / f"{cell}.json").write_text(
        json.dumps(limits))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": cell, "config": config,
                              "traffic": traffic, "chips": 1, "why": "new"})
    spec["end_to_end"] += list(e2e)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


KIND = """
import time


def run(ctx):
    ctx.setup_done()
    n = 0
    with ctx.window() as w:
        while time.perf_counter() - w.t0 < ctx.window_seconds():
            n += 1
    ctx.e2e["spins_per_s"] = n / w.elapsed
    ctx.attempted = n
    ctx.read_memory()
    return {"spin_gap": 0.0}
"""


def test_a_dropped_in_traffic_kind_is_found_by_name(tiny):
    """A new kind of traffic is one file under kinds/, found by the name
    its mix gives, with no existing file edited."""
    (tiny / "bench" / "kinds" / "spin.py").write_text(KIND)
    _add_cell(tiny, "tiny_proxy1d.tiny_spin", "tiny_proxy1d", "tiny_spin",
              {"kind": "spin", "trace_seconds": 1},
              {"spin_gap": {"limit": 0}},
              [{"name": "spins_per_s", "unit": "1/s", "better": "higher",
                "bound": 0.25, "source": "host_clock",
                "workloads": ["tiny_proxy1d.tiny_spin"]}])
    out = run_tiny(tiny, "tiny_proxy1d.tiny_spin", seconds=0.2)
    assert out["correct"], out["checks"]
    assert out["metrics"]["spins_per_s"]["value"] > 0
    assert set(out["metrics"]) == {"spins_per_s", "setup_s"}


EVEN = """
import numpy as np


def offsets(traffic, seconds, rng):
    n = int(round(traffic["rate_per_s"] * seconds))
    return (np.arange(n) + 0.5) / traffic["rate_per_s"]
"""


def test_a_dropped_in_arrival_process_is_found_by_name(tiny):
    """A new arrival process is one file under arrivals/, named by the
    mix; the solve runner sends by it."""
    (tiny / "bench" / "arrivals" / "even.py").write_text(EVEN)
    tr = dict(TRAFFIC["tiny_solve"], arrivals="even", rate_per_s=40)
    _add_cell(tiny, "tiny_proxy1d.tiny_even", "tiny_proxy1d", "tiny_even",
              tr, SOLVE_LIMITS)
    spec = json.loads((tiny / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"]:
        if m["name"].startswith("solve_"):
            m["workloads"].append("tiny_proxy1d.tiny_even")
    (tiny / "BENCHMARK.json").write_text(json.dumps(spec))
    out = run_tiny(tiny, "tiny_proxy1d.tiny_even", seconds=0.5)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 20 and out["failed"] == 0


@pytest.mark.parametrize("phases,count", [
    (None, 60),
    ([[0.25, 80], [0.25, 0]], 40),
    ([[0.1, 200], [0.4, 50]], 80),
], ids=["one_rate", "on_off", "burst"])
def test_poisson_phases_are_data(phases, count):
    """On/off and bursty arrivals are the Poisson process's data: each
    phase gets round(rate x length) sends inside it, and every seed the
    same gaps in another order."""
    import numpy as np
    from harness.spec import Bench
    process = Bench().arrivals("poisson")
    tr = {"rate_per_s": 60, "phases": phases}
    a = process.offsets(tr, 1.0, np.random.default_rng(2 ** 33 + 1))
    b = process.offsets(tr, 1.0, np.random.default_rng(5))
    assert len(a) == len(b) == count
    assert np.all(np.diff(a) >= 0) and 0 < a[0] and a[-1] < 1.0
    assert not np.array_equal(a, b)
    if phases and phases[1][1] == 0:
        assert np.all((a % 0.5) < 0.25)
    if phases is None:
        gaps = lambda x: np.sort(np.diff(x, prepend=0.0))
        np.testing.assert_allclose(gaps(a), gaps(b), rtol=0, atol=1e-12)


@pytest.mark.parametrize("cell", ["tiny_proxy1d.tiny_train",
                                  "tiny_proxy1d.tiny_solve"])
def test_the_control_is_not_correct(tiny, cell):
    """The reference at fp8 operands in the program's place fails."""
    out = run_tiny(tiny, cell, control=True)
    assert not out["correct"], out["checks"]


FAULTS = [("tiny_proxy1d.tiny_train", "unchanged"),
          ("tiny_proxy1d.tiny_train", "half_batch"),
          ("tiny_proxy1d.tiny_train", "no_exchange"),
          ("tiny_imaging.tiny_train", "no_exchange"),
          ("tiny_proxy1d.tiny_solve", "answer_altered")]


@pytest.mark.parametrize("cell,name", FAULTS,
                         ids=[f"{c}-{f}" for c, f in FAULTS])
def test_a_planted_fault_is_not_correct(tiny, cell, name):
    from harness.faults import fault
    with fault(name) as wrap:
        out = run_tiny(tiny, cell, wrap_call=wrap)
    assert not out["correct"], out["checks"]


SHARD_SCRIPT = """
import json, sys, time
from pathlib import Path
sys.path.insert(0, {tests!r})
import test_bench_harness as T
from harness.faults import fault
root = T.make_tiny_bench(Path({tmp!r}), mesh={{"pod": 2, "data": 2}})
out = {{}}
for name in (None, "no_exchange"):
    if name:
        with fault(name) as wrap:
            r = T.run_tiny(root, "tiny_proxy1d.tiny_shard", wrap_call=wrap)
    else:
        r = T.run_tiny(root, "tiny_proxy1d.tiny_shard")
    out[str(name)] = r["correct"]
print(json.dumps(out))
"""


def test_shard_cell_on_four_devices_and_its_missing_exchange(tmp_path):
    """The four-chip path on four CPU devices (a 2x2 mesh, whose outer
    ring fires at epoch 0): correct as it is, not correct with the
    exchange between chips left out."""
    script = SHARD_SCRIPT.format(tests=str(BENCH / "tests"),
                                 tmp=str(tmp_path))
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "HOME": str(tmp_path), "TMPDIR": str(tmp_path),
           "PYTHONPATH": str(ROOT / "src")}
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) == {
        "None": True, "no_exchange": False}
