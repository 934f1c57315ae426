"""Solve-service entry point: serve trained SAGIPS generators over
registered inverse problems (ISSUE 8).

    PYTHONPATH=src python -m repro.launch.serve \
        --problem proxy1d --checkpoint-dir runs/proxy1d \
        --preset reduced --requests 16 --warm

Registers each `--problem NAME[:CKPT_DIR]` (the newest trained generator
checkpoint restores via `serving.load_generator_stack` — a missing
checkpoint is a clear `ServingError`, not a stack trace), then runs a
self-contained demo client: submits `--requests` observation batches
generated from each problem's truth parameters (sizes swept across the
bucket ladder), drains the queue, and reports per-bucket latency
percentiles, residuals against the truth and the cache/queue counters.
Backpressure rejections are honored client-side by draining and
resubmitting, so the demo also exercises the retry-after path.
`benchmarks/serving.py` is the measured version of this loop.
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax

from repro.configs import serving as serving_cfg
from repro.launch.compile_cache import enable_compile_cache
from repro.problems import available, get_problem
from repro.serving import Backpressure, ServingError, SolveService


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs), q)) if xs else float("nan")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--problem", action="append", required=True,
                    metavar="NAME[:CKPT_DIR]",
                    help=f"problem to serve (repeatable); one of "
                         f"{available()}; append :DIR to restore a trained "
                         f"generator checkpoint, else a fresh 2-rank prior "
                         f"stack is served (demo mode)")
    ap.add_argument("--preset", choices=("default", "reduced"),
                    default="reduced")
    ap.add_argument("--requests", type=int, default=16,
                    help="demo requests per problem")
    ap.add_argument("--events", type=int, default=0,
                    help="events per request (0: sweep the bucket ladder)")
    ap.add_argument("--warm", action="store_true",
                    help="pre-compile the whole (problem, bucket) pool "
                         "before serving")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stats", action="store_true",
                    help="print the full SolveService.snapshot() — queue "
                         "depth + reject/retry-after rate, compile-cache "
                         "hit/miss, per-bucket latency histograms "
                         "(ISSUE 10 serving counters)")
    args = ap.parse_args()

    cfg = serving_cfg.DEFAULT if args.preset == "default" \
        else serving_cfg.REDUCED
    svc = SolveService(cfg)

    for spec in args.problem:
        name, _, ckpt = spec.partition(":")
        try:
            if ckpt:
                step = svc.register_problem(name, checkpoint_dir=ckpt)
                print(f"[serve] {name}: generator from {ckpt} (step {step})")
            else:
                from repro.core import gan
                prob = get_problem(name)
                keys = jax.random.split(jax.random.PRNGKey(args.seed), 2)
                stack = jax.tree.map(
                    lambda *xs: jax.numpy.stack(xs),
                    *[gan.init_generator(k, n_params=prob.n_params)
                      for k in keys])
                svc.register_problem(name, gen_stack=stack)
                print(f"[serve] {name}: UNTRAINED 2-rank prior stack "
                      f"(demo mode; pass {name}:CKPT_DIR for a trained one)")
        except ServingError as e:
            raise SystemExit(f"[serve] error: {e}")

    if args.warm:
        t0 = time.perf_counter()
        for name in svc.problems():
            svc.warm(name)
        print(f"[serve] warm pool: {len(svc.cache)} executables in "
              f"{time.perf_counter() - t0:.2f}s")

    rng = np.random.default_rng(args.seed)
    lat = {}                       # (problem, bucket) -> [latency_s]
    for name in svc.problems():
        prob = get_problem(name)
        key = jax.random.PRNGKey(args.seed + 1)
        for i in range(args.requests):
            n = args.events or int(rng.choice(cfg.buckets))
            key, k = jax.random.split(key)
            y = np.asarray(prob.make_reference_data(k, n))
            t0 = time.perf_counter()
            while True:
                try:
                    ticket = svc.submit(name, y)
                    break
                except Backpressure as e:   # honor retry-after by draining
                    svc.run_until_empty()
                    time.sleep(e.retry_after_s)
            svc.run_until_empty()
            out = ticket.result(timeout=60.0)
            dt = time.perf_counter() - t0
            lat.setdefault((name, ticket.bucket), []).append(dt)
            if i == 0:
                res = float(prob.mean_abs_residual(out["params"]))
                print(f"[serve] {name} first solve: bucket {ticket.bucket}, "
                      f"residual {res:.3f}, score {out['score']:.3f}")

    for (name, bucket), xs in sorted(lat.items()):
        print(f"[serve] {name:>12s} bucket {bucket:>5d}: {len(xs):3d} req, "
              f"p50 {_percentile(xs, 50)*1e3:8.1f} ms, "
              f"p99 {_percentile(xs, 99)*1e3:8.1f} ms")
    if args.stats:
        _print_snapshot(svc.snapshot())
    else:
        print(f"[serve] stats: {svc.stats()}")


def _print_snapshot(snap: dict):
    """Human-readable rendering of `SolveService.snapshot()`."""
    q = snap["queue"]
    c = snap["cache"]
    print(f"[stats] served {snap['served']}, queue depth "
          f"{snap['queue_depth']} (admitted {q['admitted']}, rejected "
          f"{q['rejected']}, drained {q['drained']}; reject rate "
          f"{snap['reject_rate']:.1%}, retry-after "
          f"{snap['retry_after_s']*1e3:.0f} ms)")
    print(f"[stats] compile cache: {c['hits']} hits / {c['misses']} misses "
          f"(hit rate {snap['cache_hit_rate']:.1%}), {c['compiles']} "
          f"compiles, {c['evictions']} evictions")
    for lane, h in snap["latency"].items():
        print(f"[stats] latency {lane:>16s}: n={h['count']:4d}  "
              f"p50 {h['p50_s']*1e3:8.1f} ms  p90 {h['p90_s']*1e3:8.1f} ms  "
              f"p99 {h['p99_s']*1e3:8.1f} ms")


if __name__ == "__main__":
    main()
