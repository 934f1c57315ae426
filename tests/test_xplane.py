"""Reading a profiler trace by layer (`repro.obs.xplane`,
`scripts/profview.py`): each device op's layer scope from the program's
HLO kept in the trace, the solve service's step spans and their args,
and the reductions over both.

The two recordings under `tests/data/` are traced runs on a TPU v5e
(Tab. III training at R = 16, and the open-loop solve service), reduced
and cut to a few seconds of window: on them the five scopes cover the
device's busy time and a solve step's children cover the step.
"""
import glob
import gzip
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.obs import xplane

ROOT = Path(__file__).resolve().parents[1]
DATA = ROOT / "tests" / "data"
RECORDED = sorted(DATA.glob("profile_*.json.gz"))


def test_innermost_scope_of_an_op_name_path():
    assert xplane.innermost_scope(
        "jit(chunked)/while/body/transpose(jvp(vmap(sagips_disc)))/"
        "dot_general") == "sagips_disc"
    assert xplane.innermost_scope(
        "jit(epoch)/vmap(sagips_exchange)/sagips_overlap_ship_outer/"
        "cond") == "sagips_exchange"
    assert xplane.innermost_scope(
        "jit(f)/sagips_gen/sagips_sample/mul") == "sagips_sample"
    assert xplane.innermost_scope(
        "jit(f)/sagips_overlap_ship_outer/add") is None
    assert xplane.innermost_scope("jit(f)/sagips_discx/add") is None


def _pb(field: int, value) -> bytes:
    """One protobuf field: a varint for an int, else length-delimited."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    if isinstance(value, int):
        return varint(field << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(field << 3 | 2) + varint(len(value)) + value


def test_op_scopes_from_the_programs_hlo_in_the_trace(tmp_path):
    """A CPU trace holds the program's optimised HLO in `/host:metadata`,
    as a TPU trace does; a device plane (written here as a TPU's is)
    names each op's program and instruction.  Each op takes the scope of
    its instruction's `op_name`."""
    @jax.jit
    def step(w, x):
        def loss(w):
            with jax.named_scope("sagips_disc"):
                return jnp.sum(jnp.tanh(x @ w))
        g = jax.grad(loss)(w)
        with jax.named_scope("sagips_apply"):
            return w - 0.1 * g

    w, x = jnp.ones((64, 64)), jnp.ones((32, 64))
    step(w, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    step(w, x).block_until_ready()
    jax.profiler.stop_trace()
    path = xplane.find_trace(str(tmp_path))
    raw = Path(path).read_bytes()
    # the program id and instruction names of the program in the trace
    programs = {}
    for f, plane in xplane._fields(raw):
        fields = list(xplane._fields(plane)) if f == 1 else []
        if bytes(dict(fields).get(2, b"")) != b"/host:metadata":
            continue
        for entry in (dict(xplane._fields(v)) for g, v in fields if g == 4):
            for h, stat in xplane._fields(entry[2]):
                hlo = dict(xplane._fields(stat)).get(6) if h == 5 else None
                if hlo is not None:
                    programs[entry[1]] = xplane._program_scopes(hlo)
    (pid, scopes), = [(k, v) for k, v in programs.items() if v]
    disc = next(n for n, s in scopes.items() if s == "sagips_disc")
    apply = next(n for n, s in scopes.items() if s == "sagips_apply")
    plane = _pb(2, "/device:TPU:0") + _pb(5, _pb(1, 7) + _pb(2, _pb(
        1, 7) + _pb(2, "program_id")))
    for i, name in enumerate((disc, apply, "no_such_op"), start=1):
        md = _pb(1, i) + _pb(2, f"%{name} = f32[8] op()") + _pb(4, name) \
            + _pb(5, _pb(1, 7) + _pb(4, pid))
        plane += _pb(4, _pb(1, i) + _pb(2, md))
    Path(path).write_bytes(raw + _pb(1, plane))
    assert xplane.op_scope_table(path) == {"/device:TPU:0": {
        f"%{disc} = f32[8] op()": "sagips_disc",
        f"%{apply} = f32[8] op()": "sagips_apply"}}


SYNTHETIC = {
    "t0": 1000, "t1": 11000,
    # two chips; on chip 0 two disc ops overlap and one apply op runs
    # past t1; one op on each chip holds no scope
    "devices": [
        [[1000, 2000, "%a"], [2500, 1000, "%b"], [5000, 500, "%c"],
         [8000, 400, "%fusion.9 = f32[8] fusion()"], [10500, 1000, "%d"]],
        [[1000, 1000, "%a"], [6000, 300, "%e"], [7000, 100, "%f"],
         [9000, 200, "%copy.1 = f32[8] copy()"]]],
    "scopes": [
        [[1000, 2000, "sagips_disc"], [2500, 1000, "sagips_disc"],
         [5000, 500, "sagips_sample"], [10500, 1000, "sagips_apply"]],
        [[1000, 1000, "sagips_disc"], [6000, 300, "sagips_gen"],
         [7000, 100, "sagips_exchange"]]],
    # one drainer thread (1), one submitter (2); spans in ns
    "spans": [
        [1500, 100, "sagips.solve.submit", 2, {}],
        [2000, 1000, "sagips.solve.step", 1, {}],
        [2000, 100, "sagips.solve.drain", 1, {}],
        [2150, 200, "sagips.solve.assemble", 1,
         {"n": 3, "bucket": 64, "wait_sum_us": 3000.0, "wait_max_us": 1500.0}],
        [2400, 100, "sagips.solve.dispatch", 1, {}],
        [2500, 400, "sagips.solve.fetch", 1, {}],
        [2950, 40, "sagips.solve.resolve", 1, {}],
        [4000, 50, "sagips.solve.step", 1, {}],        # an empty poll
        [4000, 40, "sagips.solve.drain", 1, {}],
        [5000, 2000, "sagips.solve.step", 1, {}],
        [5000, 100, "sagips.solve.drain", 1, {}],
        [5100, 500, "sagips.solve.compile", 1, {"bucket": 256}],
        [5700, 100, "sagips.solve.assemble", 1,
         {"n": 1, "bucket": 256, "wait_sum_us": 1000.0, "wait_max_us": 1000.0}],
        [5800, 100, "sagips.solve.dispatch", 1, {}],
        [5900, 1000, "sagips.solve.fetch", 1, {}],
        [6900, 50, "sagips.solve.resolve", 1, {}],
        # outside the window: not read
        [12000, 500, "sagips.solve.step", 1, {}],
        [12000, 100, "sagips.solve.assemble", 1,
         {"n": 8, "bucket": 64, "wait_sum_us": 1e6, "wait_max_us": 1e6}],
    ],
}


def test_summary_of_synthetic_data():
    s = xplane.summary(SYNTHETIC)
    ns = 1e-9
    assert s["window_s"] == pytest.approx(10000 * ns)
    # chip 0: disc [1000, 3500) = 2500; chip 1: 1000; mean over 2 chips
    assert s["scopes_s"] == pytest.approx({
        "sagips_disc": 1750 * ns, "sagips_sample": 250 * ns,
        "sagips_gen": 150 * ns, "sagips_exchange": 50 * ns,
        # clipped at t1: 500 of 1000 on chip 0
        "sagips_apply": 250 * ns})
    # busy 3900 and 1600; scoped 3500 and 1400
    assert s["busy_s"] == pytest.approx(2750 * ns)
    assert s["unscoped_s"] == pytest.approx(300 * ns)
    assert [n for n, _ in s["unscoped_ops"]] == ["%fusion.9", "%copy.1"]
    assert [v for _, v in s["unscoped_ops"]] == pytest.approx(
        [200 * ns, 100 * ns])
    solve = s["solve"]
    assert solve["batches"] == 2
    # the two served batches: 1000 and 2000 ns
    assert solve["step_ms"] == pytest.approx(1500e-6)
    assert solve["children_ms"] == pytest.approx({
        "sagips.solve.drain": 100e-6, "sagips.solve.compile": 250e-6,
        "sagips.solve.assemble": 150e-6, "sagips.solve.dispatch": 100e-6,
        "sagips.solve.fetch": 700e-6, "sagips.solve.resolve": 45e-6})
    assert solve["children_share"] == pytest.approx(2690 / 3000)
    # (3000 + 1000) us over 4 requests
    assert solve["queue_wait_ms"] == pytest.approx(1.0)
    served = xplane.batches(SYNTHETIC)
    assert [len(kids) for _, kids in served] == [5, 6]
    assert set(served[1][1]) == set(xplane.STEP_CHILDREN)


def test_summary_of_a_trace_without_spans_or_scopes():
    bare = {k: v for k, v in SYNTHETIC.items()
            if k not in ("spans", "scopes")}
    s = xplane.summary(bare)
    assert s["scopes_s"] == {} and s["solve"] is None
    assert s["unscoped_s"] == pytest.approx(s["busy_s"]) == pytest.approx(
        2750e-9)
    for scope in xplane.SCOPES:
        assert xplane.scope_seconds(bare, scope) is None


@pytest.mark.parametrize("path", RECORDED, ids=[p.name for p in RECORDED])
def test_recorded_v5e_trace_is_covered_by_its_layers(path):
    """Training: the five scopes cover at least 90% of the device's busy
    time, and their sum plus the unscoped time is busy time within 2%.
    The solve service: each served step's children cover at least 95% of
    it."""
    assert path.stat().st_size < 1_000_000
    with gzip.open(path, "rt") as f:
        data = json.load(f)["trace"]
    s = xplane.summary(data)
    if "train" in path.name:
        assert set(s["scopes_s"]) == set(xplane.SCOPES)
        scoped = sum(s["scopes_s"].values())
        assert scoped >= 0.9 * s["busy_s"]
        assert scoped + s["unscoped_s"] == pytest.approx(s["busy_s"],
                                                         rel=0.02)
    else:
        served = xplane.batches(data)
        assert served and s["solve"]["batches"] == len(served)
        for step, kids in served:
            assert set(kids) <= set(xplane.STEP_CHILDREN)
            assert sum(k[1] for k in kids.values()) >= 0.95 * step[1]
        assert s["solve"]["queue_wait_ms"] > 0


def test_solve_step_spans_reduce_from_a_cpu_trace(tmp_path):
    """A profiler trace around `SolveService` calls holds, per served
    step, the six children and the batch's args; `scripts/profview.py`
    prints the same reading."""
    from repro.core import gan
    from repro.core.workflow import SolveConfig
    from repro.problems import get_problem
    from repro.serving.service import ServingConfig, SolveService

    prob = get_problem("proxy1d")
    gens = jax.vmap(lambda k: gan.init_generator(
        k, n_params=prob.n_params))(jax.random.split(jax.random.PRNGKey(0),
                                                     2))
    svc = SolveService(ServingConfig(
        buckets=(16, 64), max_batch=4, queue_capacity=16,
        solve=SolveConfig(n_candidates=8, events_per_candidate=8)))
    svc.register_problem("proxy1d", gen_stack=gens)
    y = np.full((10, prob.obs_dim), 0.5, np.float32)

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("window"):
            tickets = [svc.submit("proxy1d", y) for _ in range(3)]
            assert svc.step() == 3          # a cache miss: compiles
            tickets.append(svc.submit("proxy1d", y))
            assert svc.step() == 1          # warm
            assert svc.step() == 0          # an empty poll
    finally:
        jax.profiler.stop_trace()
    for t in tickets:
        t.result(timeout=0)
    data = xplane.reduce_file(xplane.find_trace(str(tmp_path)),
                              window="window")
    assert data["devices"] == [] and data["scopes"] == []   # no TPU
    names = [s[2] for s in data["spans"]]
    assert names.count("sagips.solve.submit") == 4
    assert names.count("sagips.solve.step") == 3
    served = xplane.batches(data)
    assert len(served) == 2
    (_, cold), (_, warm) = served
    assert set(cold) == set(xplane.STEP_CHILDREN)
    assert set(warm) == set(xplane.STEP_CHILDREN) - {"sagips.solve.compile"}
    args = [kids["sagips.solve.assemble"][4] for _, kids in served]
    assert [(a["n"], a["bucket"]) for a in args] == [(3, 16), (1, 16)]
    for a, n in zip(args, (3, 1)):
        assert 0 < a["wait_max_us"] <= a["wait_sum_us"] <= n * a[
            "wait_max_us"] + 1e-6
    for step, kids in served:
        assert all(step[0] <= k[0] and k[0] + k[1] <= step[0] + step[1]
                   for k in kids.values())
    solve = xplane.summary(data)["solve"]
    wait = sum(a["wait_sum_us"] for a in args) / 4 / 1e3
    assert solve["queue_wait_ms"] == pytest.approx(wait)
    assert solve["children_ms"]["sagips.solve.fetch"] > 0
    assert not glob.glob(str(tmp_path / "*.jsonl"))

    out = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "profview.py"),
         str(tmp_path), "--window", "window"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    printed = json.loads(out.stdout)
    assert printed["solve"]["batches"] == 2
    assert printed["solve"]["queue_wait_ms"] == pytest.approx(wait)
