"""Readings that limits are set from, on the chip, in one process.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control] [--faults unchanged,half_batch] [--seconds 2]
    python bench/calibrate.py --workload <solve cell> --sweep 500,1000,2000 \
        --seconds 8

For each seed it runs the cell as `run.py` does (a short window) and
prints the numbers the check compares: the program's, the control's (the
plain reference at fp8 in the program's place) with `--control`, and
with each planted fault of `--faults`.  `--sweep` instead runs the
open-loop solve window at each rate and prints latency, rejections and
backlog, to find the highest rate the service sustains.  The benchmark's
own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def readings(args):
    from harness.cell import run_cell
    from harness.faults import fault
    from harness.spec import Bench
    bench = Bench(root=args.root, bench_dir=args.root / "bench")
    kinds = [("program", None)] + ([("control", None)] if args.control
                                   else []) \
        + [(f, f) for f in args.faults]
    for seed in args.seeds:
        for label, f in kinds:
            t = time.perf_counter()
            with fault(f) if f else _null() as wrap:
                out = run_cell(args.workload, seed, args.seconds, False, t,
                               bench=bench, require_tpu=not args.cpu,
                               control=label == "control", wrap_call=wrap)
            print(json.dumps({"seed": seed, "kind": label,
                              "numbers": {k: v["value"] for k, v in
                                          out["checks"].items()},
                              "metrics": {k: v["value"] for k, v in
                                          out["metrics"].items()}}),
                  flush=True)


class _null:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


def record(args):
    """One traced run; its reduced trace and window facts, cut to
    `--keep-ops` operations per chip, go to `--record` (gzip JSON)."""
    import gzip
    from harness.cell import run_cell
    from harness.spec import Bench
    bench = Bench(root=args.root, bench_dir=args.root / "bench")

    def sink(data, facts):
        for ops in data["devices"]:
            del ops[args.keep_ops:]
        if data["devices"] and data["devices"][0]:
            ends = [o[0] + o[1] for ops in data["devices"] for o in ops]
            data["t1"] = min(data["t1"], max(ends))
        data["host"] = [h for h in data["host"] if h[0] < data["t1"]]
        facts = dict(facts, gen_lag_s=facts.get("gen_lag_s", [])[:2000])
        with gzip.open(args.record, "wt") as f:
            json.dump({"trace": data, "facts": facts}, f)

    out = run_cell(args.workload, args.seeds[0], args.seconds, True,
                   time.perf_counter(), bench=bench,
                   require_tpu=not args.cpu, trace_sink=sink)
    print(json.dumps(out), flush=True)


def sweep(args):
    from harness import solve
    from harness.cell import Context
    from harness.spec import Bench
    import jax
    import numpy as np
    from repro.core import gan
    from repro.problems import get_problem
    from repro.serving.service import SolveService

    bench = Bench(root=args.root, bench_dir=args.root / "bench")
    ctx = Context(bench, args.workload, args.seeds[0], args.seconds, False,
                  time.perf_counter())
    cfg, tr = ctx.config, dict(ctx.traffic)
    prob = get_problem(cfg["problem"])
    k = jax.random.PRNGKey(0)
    gens = jax.jit(lambda k: jax.vmap(lambda kk: gan.init_generator(
        kk, n_params=prob.n_params))(jax.random.split(k, tr["n_ranks"])))(k)
    svc = SolveService(solve.serving_config(cfg))
    svc.register_problem(cfg["problem"], gen_stack=gens)
    svc.warm(cfg["problem"])
    solve.warm_path(svc, cfg["problem"], prob)
    process = bench.arrivals(tr["arrivals"])
    problem_ref = bench.reference(cfg["reference"])
    for rate in args.sweep:
        tr["rate_per_s"] = rate
        ctx.traffic = tr
        sizes, arr = solve.schedule(tr, args.seconds, args.seeds[0], process)
        n = len(sizes)
        reqs = solve.make_requests(ctx, problem_ref, k, sizes)
        loop = solve.OpenLoop(svc, cfg["problem"], reqs, arr,
                              ctx.span).run(10.0)
        lat = loop.latencies()
        lag = loop.lags()
        print(json.dumps({
            "rate": rate, "requests": n,
            "unanswered": int(np.isnan(loop.done).sum()),
            "rejected": loop.rejected, "max_backlog": loop.max_backlog,
            "p50_ms": 1e3 * float(np.percentile(lat, 50)),
            "p99_ms": 1e3 * float(np.percentile(lat, 99)),
            "lag_p99_ms": 1e3 * float(np.nanpercentile(lag, 99)),
            "fill": loop.drained / max(1, loop.busy_steps)
            / svc.cfg.max_batch,
            "elapsed_s": loop.elapsed,
            "last_quarter_p50_ms": 1e3 * float(np.percentile(
                lat[np.argsort(arr)][-n // 4:], 50))}), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=lambda s: [int(x) for x in s.split(",")],
                    default=[1])
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", type=lambda s: [x for x in s.split(",") if x],
                    default=[])
    ap.add_argument("--sweep", type=lambda s: [float(x) for x in s.split(",")],
                    default=None)
    ap.add_argument("--record", type=Path, default=None,
                    help="write one traced run's reduced trace here")
    ap.add_argument("--keep-ops", type=int, default=1_000_000)
    ap.add_argument("--root", type=Path, default=BENCH.parent,
                    help="checkout holding BENCHMARK.json and bench/")
    ap.add_argument("--cpu", action="store_true",
                    help="skip the look for a TPU (tests at tiny sizes)")
    args = ap.parse_args()
    from harness.device import setup_jax
    setup_jax()
    if args.record:
        record(args)
    elif args.sweep:
        sweep(args)
    else:
        readings(args)


if __name__ == "__main__":
    main()
