"""End-to-end SAGIPS driver — the paper's application.

Trains the GAN inverse-problem solver across simulated ranks with any
Tab. II communication mode and any registered inverse problem (see
`repro.problems`), periodically checkpoints generator states with
timestamps (the paper's post-training convergence protocol, §VI-C2), and
reports the final ensemble prediction against the problem's own truth.

    PYTHONPATH=src python examples/train_sagips_gan.py \
        --mode rma_arar_arar --ranks 8 --epochs 2000 --h 50 \
        --problem proxy2d --checkpoint-dir /tmp/sagips_ckpt

Sync schedules (`--sync-schedule`): `sync` blocks on every transfer,
`overlap` pipelines the pod boundary, `adaptive` lets a measured-skew
controller widen/narrow the RMA read depth up to `--max-staleness`.
Full-state checkpoints land in `--checkpoint-dir` every `--ckpt-every`
completed epochs; `--resume` continues bitwise from the newest one.

Backends (`--backend`): `vmap` (default) simulates the ranks inside one
SPMD program; `proc` spawns `--num-procs` REAL worker processes via
`jax.distributed.initialize` and exchanges gradients through the
`repro.runtime` one-sided mailbox fabric — add `--free-run` to let the
ranks genuinely desynchronize (implied by any `--jitter-*` flag, which
injects reproducible per-rank compute skew so the adaptive controller
has measured staleness to react to):

    PYTHONPATH=src python examples/train_sagips_gan.py \
        --backend proc --num-procs 2 --mode rma_arar_arar \
        --sync-schedule adaptive --jitter-rank-lag-ms 20 --epochs 200
"""
import argparse
import time

import jax
import numpy as np

from repro.checkpoint import restore_latest, save_checkpoint
from repro.core import gan, workflow
from repro.core.ensemble import ensemble_response
from repro.core.sync import MODES, SyncConfig
from repro.core.workflow import WorkflowConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.obs.config import ObsConfig
from repro.problems import available, get_problem


def report_final(problem, gen_stack, data):
    """Final report shared by both backends: the ensemble prediction (§VI-A)
    plus the serving-path solve — `workflow.make_solver` scoring candidates
    from the trained stack against the reference events, i.e. exactly what
    `repro.serving.SolveService` computes for a client submitting `data`."""
    import jax.numpy as jnp
    from repro.core.workflow import SolveConfig, make_solver

    noise = jax.random.normal(jax.random.PRNGKey(7), (256, gan.NOISE_DIM))
    p_hat, sigma = ensemble_response(gen_stack, noise)
    truth = np.asarray(problem.true_params())
    print("\nfinal ensemble prediction vs truth:")
    show = min(problem.n_params, 16)    # image-valued problems have 1000+
    for i in range(show):
        print(f"  p{i}: {float(p_hat[i]):.4f} ± {float(sigma[i]):.4f} "
              f"(truth {float(truth[i]):.4f})")
    if show < problem.n_params:
        err = np.abs(np.asarray(p_hat) - truth)
        print(f"  ... {problem.n_params - show} more: "
              f"mean|p̂-p*|={err.mean():.4f} max={err.max():.4f}")

    solve = make_solver(problem, SolveConfig())
    n = min(int(data.shape[0]), 1024)
    out = solve(gen_stack, jnp.asarray(data[None, :n]),
                jnp.ones((1, n), bool))
    r_ens = float(problem.mean_abs_residual(p_hat))
    r_sol = float(problem.mean_abs_residual(out["params"][0]))
    print(f"serving-path solve (make_solver, {n} events): "
          f"mean|r̂|={r_sol:.4f} vs ensemble {r_ens:.4f} "
          f"(score {float(out['score'][0]):.3f})")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=MODES, default="rma_arar_arar")
    ap.add_argument("--problem", choices=available(), default="proxy1d",
                    help="registered inverse problem to solve")
    ap.add_argument("--ranks", type=int, default=8)
    ap.add_argument("--inner", type=int, default=4,
                    help="inner group size (GPUs per node, Tab. I)")
    ap.add_argument("--epochs", type=int, default=2000)
    ap.add_argument("--h", type=int, default=50)
    ap.add_argument("--events", type=int, default=50_000)
    ap.add_argument("--param-samples", type=int, default=64)
    ap.add_argument("--checkpoint-dir", default=None,
                    help="directory for FULL-state checkpoints (resume-"
                         "capable, saved every --ckpt-every completed "
                         "epochs at chunk boundaries)")
    ap.add_argument("--ckpt-every", type=int, default=500)
    ap.add_argument("--resume", action="store_true",
                    help="continue from the newest step_N under "
                         "--checkpoint-dir (bitwise-identical to the "
                         "uninterrupted run)")
    ap.add_argument("--staleness", type=int, default=1,
                    help="RMA mailbox depth k (rma_arar_arar only)")
    ap.add_argument("--sync-schedule",
                    choices=("sync", "overlap", "adaptive",
                             "adaptive-overlap"),
                    default="sync",
                    help="epoch schedule: 'sync' blocks on the pod-boundary "
                         "transfer; 'overlap' ships the outer-ring fused "
                         "payload at epoch t and consumes it at t+1; "
                         "'adaptive' widens/narrows the RMA read depth "
                         "k_eff in [1, --max-staleness] from measured "
                         "per-rank skew (rma_arar_arar only); "
                         "'adaptive-overlap' combines both")
    ap.add_argument("--max-staleness", type=int, default=4,
                    help="adaptive schedule: widest effective read depth "
                         "k_max the controller may reach")
    ap.add_argument("--ring-chunking", type=int, default=0,
                    help="fused ring payload segment size in BYTES "
                         "(0 = unchunked); megabyte payloads — the "
                         "imaging family's conv generator — pipeline "
                         "as ceil(payload/SIZE) per-segment transfers")
    ap.add_argument("--no-fuse", action="store_true",
                    help="disable the fused single-buffer ring payload")
    ap.add_argument("--payload-precision", choices=("fp32", "bf16"),
                    default="fp32",
                    help="wire dtype of the exchanged gradient payload: "
                         "bf16 halves ring/mailbox bytes while params and "
                         "Adam state stay fp32 master copies (requires "
                         "the fused payload and a ring mode)")
    ap.add_argument("--disc-every", type=int, default=1,
                    help="update the discriminator every Nth epoch; "
                         "off-epochs skip its forward/backward at the "
                         "HLO level (SPMD-uniform lax.cond)")
    ap.add_argument("--gen-every", type=int, default=1,
                    help="update the generator (and run the gradient "
                         "exchange) every Nth epoch")
    ap.add_argument("--chunk", type=int, default=0,
                    help="epochs per jitted lax.scan chunk "
                         "(0: one chunk per report interval)")
    ap.add_argument("--backend", choices=("vmap", "proc"), default="vmap",
                    help="'vmap': R simulated ranks in one SPMD program; "
                         "'proc': REAL worker processes over the "
                         "repro.runtime mailbox fabric "
                         "(jax.distributed; CPU only, never the chip)")
    ap.add_argument("--num-procs", type=int, default=2,
                    help="proc backend: number of worker processes "
                         "(overrides --ranks)")
    ap.add_argument("--free-run", action="store_true",
                    help="proc backend: skip the lock-step rendezvous so "
                         "ranks genuinely drift apart (one-sided reads "
                         "take the latest deposit; implied by --jitter-*)")
    ap.add_argument("--jitter-rank-lag-ms", type=float, default=0.0,
                    help="proc backend: deterministic per-rank straggler "
                         "skew — rank r sleeps r*LAG ms every epoch")
    ap.add_argument("--jitter-noise-ms", type=float, default=0.0,
                    help="proc backend: seeded uniform [0, NOISE) ms "
                         "per-epoch sleep")
    ap.add_argument("--obs-metrics", action="store_true",
                    help="carry the jit-safe obs channel (k_eff, skew, "
                         "ship counts) through the epoch state; implied "
                         "by --metrics-out")
    ap.add_argument("--metrics-out", default=None, metavar="FILE.jsonl",
                    help="flush chunk-boundary training metrics as JSONL "
                         "(schema-versioned header + one row per chunk)")
    ap.add_argument("--trace-dir", default=None,
                    help="proc backend: per-rank host span traces "
                         "(trace_rank<r>.jsonl; merge with "
                         "scripts/obsview.py)")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler device trace of the "
                         "epoch loop into this directory")
    args = ap.parse_args()

    adaptive = args.sync_schedule.startswith("adaptive")
    overlap = args.sync_schedule.endswith("overlap")
    if adaptive and args.mode != "rma_arar_arar":
        ap.error("--sync-schedule adaptive needs --mode rma_arar_arar "
                 "(the only mode with an RMA mailbox)")
    problem = get_problem(args.problem)
    n_inner = min(args.inner, args.ranks)
    n_outer = args.ranks // n_inner
    obs = ObsConfig(metrics=args.obs_metrics or bool(args.metrics_out),
                    metrics_out=args.metrics_out,
                    trace_dir=args.trace_dir,
                    profile_dir=args.profile_dir)
    wcfg = WorkflowConfig(
        sync=SyncConfig(mode=args.mode, h=args.h,
                        staleness=args.max_staleness if adaptive
                        else args.staleness,
                        fuse_tensors=not args.no_fuse,
                        overlap=overlap, adaptive=adaptive,
                        payload_precision=args.payload_precision,
                        ring_chunking=args.ring_chunking),
        n_param_samples=args.param_samples, events_per_sample=25,
        gen_lr=2e-4, disc_lr=5e-4, problem=args.problem,
        disc_every=args.disc_every, gen_every=args.gen_every, obs=obs)
    # image-valued problems (conv generator path) retune the proxy-scale
    # settings — batch shape + capped generator step; identity otherwise
    from repro.configs import sagips_gan
    wcfg = sagips_gan.for_problem(args.problem, wcfg)

    data = problem.make_reference_data(jax.random.PRNGKey(99), args.events)

    if args.backend == "proc":
        from repro.runtime import JitterConfig
        from repro.runtime.launch import run_proc
        R = args.num_procs
        n_inner = min(args.inner, R)
        if R % n_inner:
            ap.error(f"--num-procs {R} must be divisible by the inner "
                     f"group size {n_inner} (set --inner accordingly); "
                     "anything else would silently launch fewer workers")
        n_outer = R // n_inner
        jitter = None
        if args.jitter_rank_lag_ms > 0 or args.jitter_noise_ms > 0:
            jitter = JitterConfig(rank_lag_ms=args.jitter_rank_lag_ms,
                                  noise_ms=args.jitter_noise_ms)
        lockstep = not (args.free_run or jitter is not None)
        print(f"problem={args.problem} mode={args.mode} "
              f"schedule={args.sync_schedule} backend=proc "
              f"procs={n_outer}x{n_inner} "
              f"{'lock-step' if lockstep else 'FREE-RUNNING'} "
              f"jitter={jitter}")
        t0 = time.time()
        out = run_proc(wcfg, n_outer, n_inner, args.epochs, data, seed=0,
                       lockstep=lockstep, jitter=jitter,
                       run_dir=args.checkpoint_dir,
                       ckpt_every=args.ckpt_every if args.checkpoint_dir
                       else 0,
                       resume=args.resume)
        h = out["history"]
        for s in out["summaries"]:
            best = (f"best {1e3 * s['epoch_s_best']:.1f} ms/epoch"
                    if s["epoch_s_best"] is not None
                    else "no new epochs")     # resume already complete
            msg = (f"  rank {s['rank']}: {s['n_epochs'] - s['start_epoch']} "
                   f"epochs in {s['wall_s']:.1f}s "
                   f"({best}, distributed={s['distributed']}, "
                   f"backend={s['backend']})")
            if wcfg.sync.adaptive:
                msg += (f" max_skew_ema={s['max_skew_ema']:.2f} "
                        f"max_k_eff={s['max_k_eff']}")
            print(msg)
        if len(h.get("d_loss", ())):
            d_l = float(np.asarray(h["d_loss"][-1]).mean())
            g_l = float(np.asarray(h["g_loss"][-1]).mean())
            print(f"final  d_loss={d_l:.3f}  g_loss={g_l:.3f}  "
                  f"({time.time() - t0:.0f}s)")
        else:
            print(f"checkpoint already covers --epochs {args.epochs}; "
                  f"restored final state without training "
                  f"({time.time() - t0:.0f}s)")
        report_final(problem, out["state"]["gen"], data)
        return

    print(f"problem={args.problem} ({problem.n_params} params -> "
          f"{problem.obs_dim} observables) mode={args.mode} "
          f"schedule={args.sync_schedule} "
          f"ranks={n_outer}x{n_inner} disc_batch={wcfg.disc_batch}")

    key = jax.random.PRNGKey(0)
    R = n_outer * n_inner
    state = workflow.init_state(key, R, wcfg)
    n_sub = max(1, int(wcfg.data_fraction * data.shape[0]))
    sub_keys = jax.random.split(jax.random.PRNGKey(1), R)
    import jax.numpy as jnp
    data_per_rank = jnp.stack([
        jnp.take(data, jax.random.permutation(k, data.shape[0])[:n_sub], axis=0)
        for k in sub_keys])
    report_every = max(args.epochs // 10, 1)
    chunk = args.chunk if args.chunk > 0 else report_every
    if args.checkpoint_dir:
        # chunk boundaries must land on the checkpoint cadence: clamp to
        # the LARGEST divisor of --ckpt-every that fits, so no checkpoint
        # epoch is skipped and the scan chunks stay as big as possible
        chunk = max(d for d in range(1, min(chunk, args.ckpt_every) + 1)
                    if args.ckpt_every % d == 0)
    chunk = max(1, min(chunk, args.epochs))
    # scan-chunked driver: one Python round-trip per `chunk` epochs
    run = workflow.make_chunk_runner(n_outer, n_inner, wcfg)

    start = 0
    if args.checkpoint_dir and args.resume:
        restored, step = restore_latest(args.checkpoint_dir, state)
        if restored is not None:
            state, start = restored, step
            print(f"resumed from {args.checkpoint_dir} at epoch {start}")

    noise = jax.random.normal(jax.random.PRNGKey(7), (256, gan.NOISE_DIM))
    # observability sinks (ISSUE 10), mirroring workflow.train_vmap:
    # chunk-boundary metric rows + an optional device-profiler capture
    writer = None
    if wcfg.obs.metrics_out:
        from repro.obs.metrics import MetricsWriter
        sched = workflow.make_schedule(wcfg)
        writer = MetricsWriter(wcfg.obs.metrics_out, header={
            "problem": wcfg.problem, "schedule": sched.name,
            "payload_bytes": sched.payload_bytes, "n_ranks": R,
            "n_epochs": args.epochs})
    if wcfg.obs.profile_dir:
        jax.profiler.start_trace(wcfg.obs.profile_dir)
    t0 = time.time()
    for e, n in workflow.chunk_schedule(args.epochs, chunk):
        done, last = e + n, e + n - 1
        if done <= start:          # covered by the restored checkpoint
            continue
        if e < start:              # checkpoint mid-chunk: run only the
            e, n = start, done - start   # epochs past it
        state, metrics = run(state, data_per_rank, n)
        if writer is not None:
            from repro.obs.metrics import chunk_row
            writer.write_row(chunk_row(done, metrics))
        if last // report_every > (e - 1) // report_every \
                or done == args.epochs:
            p_hat, sigma = ensemble_response(state["gen"], noise)
            r = float(problem.mean_abs_residual(p_hat))
            # under --disc-every/--gen-every, skipped epochs report NaN
            # losses; show the cadence's most recent real update instead
            d_l = float(np.nanmean(np.asarray(metrics["d_loss"])[-1])
                        if not np.all(np.isnan(metrics["d_loss"][-1]))
                        else np.nanmean(np.asarray(metrics["d_loss"])))
            g_l = float(np.nanmean(np.asarray(metrics["g_loss"])[-1])
                        if not np.all(np.isnan(metrics["g_loss"][-1]))
                        else np.nanmean(np.asarray(metrics["g_loss"])))
            print(f"epoch {last:6d}  mean|r̂|={r:.4f}  d_loss={d_l:.3f}  "
                  f"g_loss={g_l:.3f}  ({time.time()-t0:.0f}s)", flush=True)
        # full resume-capable state every --ckpt-every completed epochs
        # (chunk boundaries divide the cadence) and at the end
        if args.checkpoint_dir and (done % args.ckpt_every == 0
                                    or done == args.epochs):
            save_checkpoint(args.checkpoint_dir, done, state,
                            metadata={"wall_s": time.time() - t0,
                                      "problem": args.problem,
                                      "schedule": args.sync_schedule})
    if wcfg.obs.profile_dir:
        jax.profiler.stop_trace()
    if writer is not None:
        writer.close()

    report_final(problem, state["gen"], data)


if __name__ == "__main__":
    main()
