"""Paper Figs. 11–12 — weak scaling: total training time & analysis rate vs
number of ranks, per communication mode.

CPU-only reproduction strategy (DESIGN.md §6): for each rank count R and
mode, the shard_map epoch step is lowered and compiled on R placeholder host
devices (a subprocess per R — jax pins the device count at first init).
The compiled HLO gives exact per-rank collective traffic; epoch time is then
modeled as

    t_epoch = t_compute + t_comm,
    t_comm  = intra_bytes / BW_FAST + inter_bytes / BW_SLOW + LAT * n_ops

with Polaris-like constants (NVLink-ish 100 GB/s inside a node of 4,
Slingshot-ish 12.5 GB/s across nodes, 10 us/op latency).  t_compute is the
measured single-rank epoch time (the GAN+pipeline work is identical per rank
in weak scaling).  Analysis rate = R * N_disc * N_epochs / total time
(Eq. 9).

The paper's qualitative claims checked here:
  * conventional ARAR total time grows ~linearly in R,
  * grouped (RMA-)ARAR stays nearly flat,
  * grouped analysis-rate gain ~2x conventional ARAR at R=400+.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

from .common import save_result, stamp, timeit_best

BW_FAST = 100e9        # intra-node (inner group) bytes/s
BW_SLOW = 12.5e9       # inter-node bytes/s
LAT = 10e-6            # per collective-op latency
GPUS_PER_NODE = 4      # Polaris nodes
JITTER = 1e-3          # per-rank async compute jitter (s) — the pipeline/
#                        sampler variance the paper names as the reason for
#                        RMA (§IV-B3: "some ranks may run the data
#                        generation task faster / slower than others")

_CHILD = r"""
import os, sys, json
os.environ["JAX_PLATFORMS"] = "cpu"   # placeholder devices, never the chip
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={sys.argv[1]}"
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.core import pipeline, workflow
from repro.core.sync import SyncConfig
from repro.core.workflow import WorkflowConfig
from repro.launch import hlo_cost
from repro.obs.config import ObsConfig
from repro.problems import get_problem

R = int(sys.argv[1]); mode = sys.argv[2]; h = int(sys.argv[3])
fuse = len(sys.argv) > 4 and sys.argv[4] == "fuse"
problem = sys.argv[5] if len(sys.argv) > 5 else "proxy1d"
schedule = sys.argv[6] if len(sys.argv) > 6 else "sync"
precision = sys.argv[7] if len(sys.argv) > 7 else "fp32"
disc_every = int(sys.argv[8]) if len(sys.argv) > 8 else 1
ring_chunking = int(sys.argv[9]) if len(sys.argv) > 9 else 0
n_outer = max(R // %d, 1); n_inner = min(R, %d)
from repro.launch.mesh import make_mesh
mesh = make_mesh((n_outer, n_inner), ("pod", "data"))
wcfg = WorkflowConfig(sync=SyncConfig(mode=mode, h=h, fuse_tensors=fuse,
                                      overlap=schedule == "overlap",
                                      adaptive=schedule == "adaptive",
                                      staleness=4 if schedule == "adaptive"
                                      else 1,
                                      payload_precision=precision,
                                      ring_chunking=ring_chunking),
                      n_param_samples=64, events_per_sample=25,
                      problem=problem, disc_every=disc_every)
fn, shardings = workflow.make_epoch_fn_shard(mesh, wcfg)
state = jax.eval_shape(lambda k: workflow.init_state(k, R, wcfg),
                       jax.random.PRNGKey(0))
obs = get_problem(problem).obs_dim
data = jax.ShapeDtypeStruct((R, 1000, obs), jnp.float32)
state_in = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                       sharding=shardings), state)
data_in = jax.ShapeDtypeStruct(data.shape, data.dtype, sharding=shardings)
lowered = fn.lower(state_in, data_in)
compiled = lowered.compile()
rep = hlo_cost.analyze(compiled.as_text()).as_dict()
# Logical wire dtypes from the pre-optimization StableHLO: XLA's CPU
# float-normalization pass widens bf16 collectives to f32 in the *compiled*
# module (convert -> f32 collective-permute -> convert), an artifact of the
# host backend that accelerator backends don't share — the StableHLO carries
# the dtype the program actually ships on the ring.
import re
_ITEM = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2,
         "i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i8": 1, "ui8": 1, "i1": 1}
wire = {}
for m in re.finditer(r'"?stablehlo\.(?:collective_permute|all_reduce|'
                     r'all_gather|reduce_scatter|all_to_all)"?[^\n]*?'
                     r'->\s*tensor<([^>]+)>', lowered.as_text()):
    *dims, dt = m.group(1).split("x")
    n = 1
    for d in dims:
        n *= int(d)
    if dt in _ITEM:
        wire[dt] = wire.get(dt, 0) + n * _ITEM[dt]
rep["wire_bytes_by_dtype_stablehlo"] = wire
print("RESULT " + json.dumps(rep))
""" % (GPUS_PER_NODE, GPUS_PER_NODE)


def lower_epoch(R: int, mode: str, h: int, fuse: bool = False,
                problem: str = "proxy1d", schedule: str = "sync",
                precision: str = "fp32", disc_every: int = 1,
                ring_chunking: int = 0) -> dict:
    out = subprocess.run([sys.executable, "-c", _CHILD, str(R), mode, str(h),
                          "fuse" if fuse else "nofuse", problem, schedule,
                          precision, str(disc_every), str(ring_chunking)],
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"child failed (R={R}, {mode}):\n{out.stderr[-2000:]}")


def model_epoch_time(rep: dict, mode: str, h: int, t_compute: float,
                     R: int, overlap: bool = False) -> float:
    """Communication-cost model over the measured per-rank HLO traffic.

    Bandwidth: collective-permute = ring neighbour transfer; for grouped
    modes the inner ring stays on-node (fast BW), the outer ring (1/h) and
    the global ring cross nodes (slow BW); allreduce crosses nodes every
    epoch.

    Blocking: a *synchronous* ring is a dependency chain — per-rank jitter
    accumulates along it (rank i waits for i+1, §IV-B3), giving the paper's
    near-linear conventional-ARAR growth (Fig. 11).  Grouped ARAR blocks
    only within the 4-rank node group; RMA-ARAR is one-sided and never
    blocks; allreduce is a barrier (waits for the slowest rank: max of R
    jitters ~ sigma*sqrt(2 ln R)).
    """
    import math
    cp = rep["collective_bytes"].get("collective-permute", 0.0)
    ar = rep["collective_bytes"].get("all-reduce", 0.0) + \
        rep["collective_bytes"].get("all-gather", 0.0) + \
        rep["collective_bytes"].get("reduce-scatter", 0.0)
    n_ops = sum(rep["collective_ops"].values())
    if mode == "conv_arar":
        t_comm = cp / BW_SLOW + JITTER * R          # blocking global chain
    elif mode == "arar_arar":
        inner, outer = 0.5 * cp / BW_FAST, 0.5 * cp / (BW_SLOW * h)
        if overlap:                                 # outer hides behind the
            outer = max(0.0, outer - t_compute)     # next epoch's compute
        t_comm = inner + outer + JITTER * GPUS_PER_NODE  # blocks on-node only
    elif mode == "rma_arar_arar":
        inner, outer = 0.5 * cp / BW_FAST, 0.5 * cp / (BW_SLOW * h)
        if overlap:
            outer = max(0.0, outer - t_compute)
        t_comm = inner + outer                      # one-sided
    elif mode == "allreduce":
        t_comm = ar / BW_SLOW + JITTER * math.sqrt(2 * math.log(max(R, 2)))
    elif mode == "dbtree":
        # log2(R) pairwise stages, each a barrier with its partner; half the
        # stages cross nodes on Polaris-like placement
        t_comm = cp / (2 * BW_FAST) + cp / (2 * BW_SLOW) \
            + JITTER * math.log2(max(R, 2))
    else:
        t_comm = 0.0
    return t_compute + t_comm + LAT * n_ops


def measure_exchange_rows(problem="imaging", ranks=(8, 16), h=25,
                          ring_chunking=524288, reps=8, n_iters=50):
    """Exchange-ONLY wall time: the fused ring transfer in isolation (no
    GAN compute), flat vs chunked payload, on the vmap simulator.

    This is the direct evidence lane for `SyncConfig.ring_chunking`: the
    full-epoch lanes of `measure_fused_wall_time` bury the exchange under
    the generator/discriminator compute (on the megabyte imaging payloads
    the conv generator dominates), so the chunked win there sits inside
    rep noise.  Here each row times `schedule.exchange` alone — same
    driver-built schedule, same VmapComm — and records the payload's wire
    shape from the FusionSpec.  Best-of-`reps` minima, the timeit
    convention.  Rows carry `exchange_s_fused` / `exchange_s_chunked` /
    `chunked_speedup`; a payload below one segment degenerates to the
    identical flat program (toy problems: speedup ~1.0 by construction,
    which is the 'no slower at toy scale' guard)."""
    import time

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src"))
    from repro.core import workflow
    from repro.core.ring import VmapComm
    from repro.core.sync import SyncConfig
    from repro.core.workflow import WorkflowConfig

    rows = []
    for R in ranks:
        n_inner = min(R, GPUS_PER_NODE)
        n_outer = max(R // n_inner, 1)
        comm = VmapComm(n_outer, n_inner)
        per, spec_c = {}, None
        for lane, chunk in (("fused", 0), ("chunked", ring_chunking)):
            wcfg = WorkflowConfig(
                sync=SyncConfig(mode="rma_arar_arar", h=h,
                                ring_chunking=chunk), problem=problem)
            sched = workflow.make_schedule(wcfg)
            if chunk:
                spec_c = sched.spec
            st = sched.init_state(R)
            g = sched._grads_example(R)
            g = jax.tree.map(lambda x: jnp.full(x.shape, 0.5, x.dtype), g)
            fn = jax.jit(lambda g, st, e: sched.exchange(comm, g, st, e))
            o, _ = fn(g, st, 0)
            jax.block_until_ready(o)

            def iters(fn=fn, g=g, st=st):
                o = None
                s = st
                for e in range(n_iters):
                    o, s = fn(g, s, e)
                return o

            per[lane] = timeit_best(iters, n_iters, reps,
                                    block=jax.block_until_ready)
        row = {"ranks": R, "problem": problem, "schedule": "sync",
               "backend": "vmap", "lane": "exchange_only",
               "payload_bytes":
                   spec_c.total * jnp.dtype(spec_c.payload_dtype).itemsize,
               "ring_chunking": ring_chunking,
               "segments": spec_c.n_segments,
               "exchange_s_fused": per["fused"],
               "exchange_s_chunked": per["chunked"],
               "chunked_speedup": per["fused"] / per["chunked"]}
        rows.append(row)
        print(f"  R={R:4d} {problem:12s} exchange-only: flat "
              f"{per['fused']*1e6:8.1f} us  chunked "
              f"{per['chunked']*1e6:8.1f} us "
              f"({row['chunked_speedup']:.2f}x, {row['segments']} seg of "
              f"{ring_chunking} B)", flush=True)
    return rows


def measure_fused_wall_time(ranks=(4, 8, 16), h=25, n_epochs=30,
                            warmup=5, out_path=None, problem="proxy1d",
                            sync_mode="sync", reps=3, max_staleness=4,
                            backend="vmap", proc_ranks=(2,),
                            ring_chunking=524288, trace_dir=None,
                            exchange_problems=("proxy1d", "imaging"),
                            provenance=None):
    """Measured (not modeled) per-epoch wall time, fused vs unfused ring
    payload, on the vmap rank simulator of this host; sync_mode='overlap'
    adds a lane measuring the overlapped pod-boundary schedule (fused
    payload, ship at t / consume at t+1), and sync_mode='adaptive' adds
    both that lane and the adaptive-staleness schedule (tag-driven k_eff
    controller over a depth-`max_staleness` mailbox).

    Each lane runs `reps` back-to-back repetitions of `n_epochs` epochs and
    records the BEST (minimum) per-epoch time — the timeit convention:
    scheduler noise on a shared host only ever ADDS time, so the min is the
    noise-robust estimate of the true cost.

    Every row records which runtime `backend` produced it.  The vmap rows
    (`backend='vmap'`) are the historical regression-gated series;
    `backend='proc'` appends the MEASURED ASYNC lane: real
    free-running worker processes over the `repro.runtime` mailbox fabric
    (adaptive schedule, zero injected jitter), one row per entry of
    `proc_ranks` — kept to the host's core count, since oversubscribed
    free-running workers measure the scheduler, not the runtime.  Proc
    rows record `epoch_s_proc` as the SLOWEST rank's best epoch time (the
    ring's throughput bound) and are descriptive, not regression-gated
    (see docs/benchmarks.md).

    Seeds the repo's BENCH_*.json series: writes BENCH_weak_scaling.json at
    the repo root (plus benchmarks/results/) with per-R epoch times, the
    fused/unfused (and overlap/fused) ratios and the measured problem, so
    future PRs can regress against it — the regression target is the
    ABSOLUTE epoch_s per rank count (see docs/benchmarks.md).
    """
    import time

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src"))
    from repro.core import workflow
    from repro.core.sync import SyncConfig
    from repro.core.workflow import WorkflowConfig
    from repro.problems import get_problem

    lanes = [("unfused", dict(fuse_tensors=False)),
             ("fused", dict(fuse_tensors=True))]
    if ring_chunking:
        # chunked ring lane (ISSUE 9): same fused payload, moved as
        # `ring_chunking`-byte pipelined segments.  On toy payloads below
        # one segment this degenerates to the fused lane (same compiled
        # module); the megabyte imaging payloads are where the split pays.
        lanes.append(("chunked", dict(fuse_tensors=True,
                                      ring_chunking=ring_chunking)))
    if sync_mode in ("overlap", "adaptive"):
        lanes.append(("overlap", dict(fuse_tensors=True, overlap=True)))
    if sync_mode == "adaptive":
        lanes.append(("adaptive", dict(fuse_tensors=True, adaptive=True,
                                       staleness=max_staleness)))

    data = get_problem(problem).make_reference_data(jax.random.PRNGKey(42),
                                                    2000)
    rows = []
    for R in ranks:
        n_inner = min(R, GPUS_PER_NODE)
        n_outer = max(R // n_inner, 1)
        per_lane = {}
        for lane, sync_kw in lanes:
            wcfg = WorkflowConfig(
                sync=SyncConfig(mode="rma_arar_arar", h=h, **sync_kw),
                n_param_samples=32, events_per_sample=25, problem=problem)
            state = workflow.init_state(jax.random.PRNGKey(0), R, wcfg)
            dpr = jnp.stack([data[:1000]] * R)
            fn = workflow.make_chunk_fn_vmap(n_outer, n_inner, wcfg, 1)
            for _ in range(warmup):                     # compile + warm cache
                state, m = fn(state, dpr)
            jax.block_until_ready(m)

            def iters():
                nonlocal state
                m = None
                for _ in range(n_epochs):
                    state, m = fn(state, dpr)
                return m

            per_lane[lane] = timeit_best(iters, n_epochs, reps,
                                         block=jax.block_until_ready)
        # wire-payload shape of the fused exchange, from the driver's own
        # FusionSpec (what the ring actually moves, incl. segmentation)
        spec = workflow.make_schedule(WorkflowConfig(
            sync=SyncConfig(mode="rma_arar_arar", h=h, fuse_tensors=True,
                            ring_chunking=ring_chunking),
            n_param_samples=32, events_per_sample=25, problem=problem)).spec
        row = {"ranks": R, "problem": problem, "schedule": sync_mode,
               "backend": "vmap",
               "payload_bytes":
                   spec.total * jnp.dtype(spec.payload_dtype).itemsize,
               "ring_chunking": ring_chunking,
               "segments": spec.n_segments,
               "epoch_s_unfused": per_lane["unfused"],
               "epoch_s_fused": per_lane["fused"],
               "fused_speedup": per_lane["unfused"] / per_lane["fused"]}
        msg = (f"  R={R:4d} unfused {per_lane['unfused']*1e3:8.2f} ms  "
               f"fused {per_lane['fused']*1e3:8.2f} ms  "
               f"speedup {row['fused_speedup']:.2f}x")
        if "chunked" in per_lane:
            row["epoch_s_chunked"] = per_lane["chunked"]
            row["chunked_vs_fused"] = per_lane["chunked"] / per_lane["fused"]
            msg += (f"  chunked {per_lane['chunked']*1e3:8.2f} ms "
                    f"({row['chunked_vs_fused']:.2f}x fused, "
                    f"{row['segments']} seg)")
        if "overlap" in per_lane:
            row["epoch_s_overlap"] = per_lane["overlap"]
            row["overlap_vs_fused"] = per_lane["overlap"] / per_lane["fused"]
            msg += (f"  overlap {per_lane['overlap']*1e3:8.2f} ms "
                    f"({row['overlap_vs_fused']:.2f}x fused)")
        if "adaptive" in per_lane:
            row["epoch_s_adaptive"] = per_lane["adaptive"]
            row["adaptive_vs_fused"] = per_lane["adaptive"] / per_lane["fused"]
            msg += (f"  adaptive {per_lane['adaptive']*1e3:8.2f} ms "
                    f"({row['adaptive_vs_fused']:.2f}x fused)")
        rows.append(row)
        print(msg, flush=True)

    if backend == "proc":              # vmap lanes above + the async lane
        if sync_mode != "adaptive":
            raise ValueError(
                "the proc async lane measures the adaptive schedule (its "
                "point is measured k_eff under real skew); run with "
                "--sync-mode adaptive so the payload's sync_mode/"
                "max_staleness describe every row coherently")
        from repro.runtime.launch import run_proc
        for R in proc_ranks:
            n_inner = min(R, GPUS_PER_NODE)
            n_outer = max(R // n_inner, 1)
            if n_outer * n_inner != R:
                raise ValueError(
                    f"proc rank count {R} does not factor as pods x "
                    f"{GPUS_PER_NODE}; the row would misreport the "
                    "measured configuration — pick a multiple of "
                    f"{GPUS_PER_NODE} (or a value below it)")
            obs = ObsConfig()
            if trace_dir:
                # absolute path: run_proc's temp run_dir is cleaned after
                # aggregation, the trace must outlive it
                obs = ObsConfig(trace_dir=os.path.abspath(
                    os.path.join(trace_dir, f"R{R}")))
            wcfg = WorkflowConfig(
                sync=SyncConfig(mode="rma_arar_arar", h=h,
                                staleness=max_staleness, adaptive=True),
                n_param_samples=32, events_per_sample=25, problem=problem,
                obs=obs)
            out = run_proc(wcfg, n_outer, n_inner, n_epochs, data[:1000],
                           seed=0, lockstep=False, timeout=900)
            # the ring's throughput is bounded by its slowest rank
            epoch_s = max(s["epoch_s_best"] for s in out["summaries"])
            rows.append({
                "ranks": R, "problem": problem, "schedule": "adaptive",
                "backend": "proc", "epoch_s_proc": epoch_s,
                "distributed": all(s["distributed"]
                                   for s in out["summaries"]),
                "max_k_eff": max(s["max_k_eff"]
                                 for s in out["summaries"]),
            })
            print(f"  R={R:4d} proc (free-running async) "
                  f"{epoch_s * 1e3:8.2f} ms/epoch  "
                  f"distributed={rows[-1]['distributed']}", flush=True)

    # exchange-only evidence rows for the chunked ring (ISSUE 9): the
    # megabyte imaging payload at R >= 8 is where segmentation must win;
    # the toy payload degenerates to the identical flat program
    for xprob in exchange_problems:
        rows.extend(measure_exchange_rows(
            xprob, ranks=tuple(r for r in ranks if r >= 8) or ranks,
            h=h, ring_chunking=ring_chunking))

    payload = {"benchmark": "weak_scaling_fused_exchange",
               "mode": "rma_arar_arar", "h": h, "n_epochs": n_epochs,
               "reps": reps, "problem": problem, "sync_mode": sync_mode,
               "ring_chunking": ring_chunking,
               "max_staleness": max_staleness if sync_mode == "adaptive"
               else None,
               "jax_platform": jax.default_backend(), "rows": rows}
    stamp(payload)                 # obs provenance (docs/benchmarks.md)
    if provenance:
        payload["provenance"] = provenance
    save_result("weak_scaling_fusion", payload)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(out_path or os.path.join(root, "BENCH_weak_scaling.json"),
              "w") as f:
        json.dump(payload, f, indent=1)
    return payload


def run(ranks=(4, 8, 16, 32, 64, 128, 256, 400), h=1000,
        t_compute=0.05, n_epochs=100_000, disc_batch=102_400, quick=False,
        problem="proxy1d"):
    if quick:
        ranks = (4, 8, 16)
    modes = ["conv_arar", "arar_arar", "rma_arar_arar", "allreduce",
             "rma_arar_arar+fused", "rma_arar_arar+overlap",
             "rma_arar_arar+adaptive", "dbtree"]
    results = {}
    for mode_label in modes:
        mode, _, variant = mode_label.partition("+")
        schedule = variant if variant in ("overlap", "adaptive") else "sync"
        rows = []
        for R in ranks:
            R_eff = min(R, 512)
            rep = lower_epoch(R_eff, mode, h,
                              fuse=(variant == "fused"
                                    or schedule != "sync"),
                              problem=problem, schedule=schedule)
            t_ep = model_epoch_time(rep, mode, h, t_compute, R,
                                    overlap=schedule == "overlap")
            total = t_ep * n_epochs
            rate = R * disc_batch * n_epochs / total
            rows.append({"ranks": R, "problem": problem, "epoch_s": t_ep,
                         "schedule": schedule,
                         "total_h": total / 3600, "analysis_rate": rate,
                         "collective_bytes": rep["total_collective_bytes"],
                         "collective_ops": rep["collective_ops"]})
            print(f"  {mode_label:19s} R={R:4d} epoch {t_ep*1e3:8.2f} ms "
                  f"total {total/3600:7.1f} h rate {rate:.3e} ev/s", flush=True)
        results[mode_label] = rows
    payload = {"h": h, "t_compute": t_compute, "problem": problem,
               "modes": results}
    save_result("weak_scaling" + ("_quick" if quick else ""), payload)
    return payload


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--problem", default="proxy1d",
                    help="registered inverse problem to measure "
                         "(recorded in BENCH_weak_scaling.json)")
    ap.add_argument("--fusion-wall-time", action="store_true",
                    help="measure fused-vs-unfused per-epoch wall time "
                         "(writes BENCH_weak_scaling.json)")
    ap.add_argument("--sync-mode", choices=("sync", "overlap", "adaptive"),
                    default="sync",
                    help="with --fusion-wall-time: 'overlap' adds a "
                         "measured lane for the pipelined pod-boundary "
                         "exchange; 'adaptive' adds that lane AND the "
                         "adaptive-staleness schedule (tag-driven k_eff "
                         "controller); every BENCH row records the "
                         "schedule it measured")
    ap.add_argument("--backend", choices=("vmap", "proc"), default="vmap",
                    help="with --fusion-wall-time: 'proc' appends the "
                         "measured async lane — real free-running worker "
                         "processes over the repro.runtime mailbox "
                         "fabric (adaptive schedule, zero injected "
                         "jitter) at --proc-ranks; every BENCH row "
                         "records its backend")
    ap.add_argument("--proc-ranks", type=int, nargs="+", default=[2],
                    help="rank counts for the proc async lane (keep "
                         "within the host's core count)")
    ap.add_argument("--trace-dir", default=None,
                    help="with --backend proc: per-rank host span traces "
                         "for the async lane (ISSUE 10) — written under "
                         "DIR/R<ranks>/, merge with scripts/obsview.py "
                         "to read the rendezvous/exchange wait shares "
                         "behind each epoch_s_proc row")
    a = ap.parse_args()
    if a.fusion_wall_time:
        measure_fused_wall_time(problem=a.problem, sync_mode=a.sync_mode,
                                backend=a.backend,
                                proc_ranks=tuple(a.proc_ranks),
                                trace_dir=a.trace_dir)
    else:
        run(quick=a.quick, problem=a.problem)
