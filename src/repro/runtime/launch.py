"""Multi-process launcher + worker for the free-running SAGIPS runtime.

`run_proc` (parent side) spawns `n_outer * n_inner` worker processes of
this module on the local host, each of which

  1. joins the `jax.distributed` CPU cluster (coordinator = process 0,
     `jax.distributed.initialize`; workers always run with
     `JAX_PLATFORMS=cpu`, so this backend is not a chip path); the
     mailbox fabric is file-based, so a failed join degrades to a
     standalone-but-still-correct run and is recorded in the worker's
     summary,
  2. rebuilds the SAME initial stacked state as `train_vmap` from the run
     seed and slices out its own rank (bitwise-identical initial point),
  3. runs the per-rank epoch body — jitted `rank_grads` / `rank_apply`
     around an EAGER `SyncSchedule.exchange` over `ProcComm` — with
     optional deterministic jitter injection (`runtime/jitter.py`),
  4. checkpoints ITS OWN state every `ckpt_every` epochs under
     `<run_dir>/ckpt/rank_<r>` (`resume=True` restores per process via
     the crash-resilient `checkpoint.restore_latest`, so a worker killed
     mid-save cannot brick the run),
  5. saves its final state + a JSON summary (per-epoch losses, measured
     skew EMA, k_eff, wall times) for the parent to aggregate.

The parent stacks the per-rank final states back into the familiar
`[R, ...]` layout, so downstream analysis (ensemble response, residuals)
is driver-agnostic.  `workflow.train_proc` is the thin driver wrapper.

Lock-step mode (`lockstep=True`, zero jitter) is the bitwise lane: it
reproduces the `VmapComm` trajectory exactly.  Free-running mode is the
paper's actual workflow: ranks drift, deposit tags carry measured skew,
and the adaptive controller finally has something real to chew on.
"""
from __future__ import annotations

import dataclasses
import json
import os
import socket
import subprocess
import sys
import tempfile
import time
from typing import Optional

RUNCONFIG = "runconfig.json"
DATA_FILE = "data.npz"


# ----------------------------------------------------------------------------
# config (de)serialization — workers rebuild WorkflowConfig from JSON


def wcfg_to_dict(wcfg) -> dict:
    return dataclasses.asdict(wcfg)


def wcfg_from_dict(d: dict):
    from ..core.sync import SyncConfig
    from ..core.workflow import WorkflowConfig
    from ..obs.config import ObsConfig
    d = dict(d)
    sync = SyncConfig(**d.pop("sync"))
    obs = ObsConfig(**d.pop("obs", {}))
    return WorkflowConfig(sync=sync, obs=obs, **d)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ----------------------------------------------------------------------------
# parent side


def run_proc(wcfg, n_outer: int, n_inner: int, n_epochs: int, data, *,
             seed: int = 0, run_dir: Optional[str] = None,
             lockstep: bool = True, jitter=None, ckpt_every: int = 0,
             resume: bool = False, use_distributed: bool = True,
             timeout: float = 900.0):
    """Launch the multi-process run and aggregate the results.

    Returns a dict with `state` (per-rank final states stacked back into
    the `[R, ...]` layout), `history` (per-epoch metrics stacked
    `[n_epochs, R]`), `summaries` (the raw per-rank JSON), and `run_dir`.
    `data` is the full reference set (as for `train_vmap`); the per-rank
    split re-derives from `seed` inside each worker.  A caller-supplied
    `run_dir` persists mailboxes/checkpoints/logs (needed for
    `resume=True`); the default is a temp dir cleaned after aggregation.
    """
    import numpy as np

    R = n_outer * n_inner
    cleanup = run_dir is None
    if run_dir is None:
        run_dir = tempfile.mkdtemp(prefix="sagips_proc_")
    os.makedirs(run_dir, exist_ok=True)
    _clear_comm_files(run_dir, R)
    np.savez(os.path.join(run_dir, DATA_FILE), data=np.asarray(data))

    # resume negotiation: every worker must restart from the SAME epoch,
    # so pick the newest step loadable by ALL ranks (a rank killed mid-save
    # has a corrupt newest step — the crash-resilient restore_latest walks
    # past it) and pin it in the runconfig
    if resume and not ckpt_every:
        raise ValueError(
            "resume=True needs ckpt_every > 0: resuming negotiates a "
            "common step from the per-rank ckpt/ directories, and "
            "silently retraining from epoch 0 would overwrite the very "
            "results the caller asked to continue from")
    resume_step = None
    if resume:
        resume_step = _common_resume_step(run_dir, wcfg, R,
                                          max_epoch=n_epochs)
    cfg = {
        "wcfg": wcfg_to_dict(wcfg),
        "n_outer": n_outer, "n_inner": n_inner, "n_epochs": n_epochs,
        "seed": seed, "lockstep": lockstep,
        "jitter": jitter.to_dict() if jitter is not None else None,
        "ckpt_every": ckpt_every, "resume_step": resume_step,
        "use_distributed": use_distributed,
        "coordinator_port": _free_port(),
        "timeout": timeout,
    }
    with open(os.path.join(run_dir, RUNCONFIG), "w") as f:
        json.dump(cfg, f, indent=1)

    src_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = src_root + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    # CPU only, whatever the caller's environment names: a chip belongs to
    # one process, so N workers can never share it
    env["JAX_PLATFORMS"] = "cpu"

    procs, logs = [], []
    for r in range(R):
        log_path = os.path.join(run_dir, f"worker_{r}.log")
        logs.append(log_path)
        with open(log_path, "w") as lf:   # Popen dups the fd; don't leak ours
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "repro.runtime.launch", "--worker",
                 "--rank", str(r), "--run-dir", run_dir],
                stdout=lf, stderr=subprocess.STDOUT, env=env))

    deadline = time.monotonic() + timeout
    try:
        while any(p.poll() is None for p in procs):
            if time.monotonic() > deadline:
                raise RuntimeError(f"proc runtime timed out after "
                                   f"{timeout:.0f}s")
            bad = [r for r, p in enumerate(procs)
                   if p.poll() not in (None, 0)]
            if bad:
                raise RuntimeError(f"worker(s) {bad} exited nonzero")
            time.sleep(0.05)
        bad = [r for r, p in enumerate(procs) if p.returncode != 0]
        if bad:
            raise RuntimeError(f"worker(s) {bad} exited nonzero")
    except Exception:
        for p in procs:
            if p.poll() is None:
                p.kill()
        tails = []
        for r, lp in enumerate(logs):
            try:
                with open(lp) as f:
                    tails.append(f"--- worker {r} ---\n" + f.read()[-3000:])
            except OSError:
                pass
        raise RuntimeError("proc runtime failed:\n" + "\n".join(tails))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    out = _aggregate(run_dir, wcfg, R, n_epochs)
    if cleanup:
        import shutil
        shutil.rmtree(run_dir, ignore_errors=True)
        out["run_dir"] = None
    return out


def _clear_comm_files(run_dir: str, R: int):
    """Mailboxes/boards/barriers are launch-scoped (their sequence counters
    restart at 0 with every launch); stale ones from a previous attempt in
    a persistent run_dir would corrupt the lock-step pairing.  Summaries
    and logs are per-launch artifacts too.  Checkpoints survive — they are
    the resume contract."""
    import glob
    import shutil
    for pat in ("mbx_*.bin", "board_*.bin", "barrier.bin",
                "summary_rank*.json", "worker_*.log"):
        for p in glob.glob(os.path.join(run_dir, pat)):
            try:
                os.remove(p)
            except OSError:
                pass
    # final states are also per-launch artifacts: a stale final/ from an
    # earlier (longer) run in the same run_dir must not shadow this one
    shutil.rmtree(os.path.join(run_dir, "final"), ignore_errors=True)


def _common_resume_step(run_dir: str, wcfg, R: int, max_epoch: int):
    """Newest checkpoint step loadable by EVERY rank (None = fresh start).

    Capped at `max_epoch` (the run's n_epochs): a run re-launched for
    FEWER epochs than it previously completed must resume from a step
    inside the requested range — restoring a later step would return a
    final state whose epoch counter contradicts the requested run, and a
    start past n_epochs would execute zero epochs against a mislabeled
    final save."""
    import warnings

    import jax

    from ..checkpoint.store import list_steps, restore_checkpoint
    from ..core import workflow

    like = workflow.init_rank_state(jax.random.PRNGKey(0), wcfg)
    dirs = [os.path.join(run_dir, "ckpt", f"rank_{r}") for r in range(R)]
    step_sets = [set(s for s in list_steps(d) if s <= max_epoch)
                 for d in dirs]
    if not all(step_sets):
        return None
    # probe candidates newest-down, ONE load per rank in the common case
    # (a step is only rejected when some rank's copy was killed mid-save;
    # structural mismatches raise — same contract as restore_latest)
    from ..checkpoint.store import _corrupt_checkpoint_errors
    for s in sorted(set.intersection(*step_sets), reverse=True):
        ok = True
        for r, d in enumerate(dirs):
            try:
                restore_checkpoint(d, s, like)
            except _corrupt_checkpoint_errors() as e:
                warnings.warn(f"rank {r} checkpoint step_{s} unreadable "
                              f"({type(e).__name__}); excluded from resume")
                ok = False
                break
        if ok:
            return s
    return None


def _aggregate(run_dir: str, wcfg, R: int, n_epochs: int) -> dict:
    import jax
    import numpy as np

    from ..checkpoint.store import restore_checkpoint
    from ..core import workflow

    summaries = []
    for r in range(R):
        with open(os.path.join(run_dir, f"summary_rank{r}.json")) as f:
            summaries.append(json.load(f))
    like = workflow.init_rank_state(jax.random.PRNGKey(0), wcfg)
    states = []
    for r in range(R):
        # the exact step this launch wrote — never a stale survivor
        tree = restore_checkpoint(
            os.path.join(run_dir, "final", f"rank_{r}"), n_epochs, like)
        states.append(tree)
    state = jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]),
                         *states)
    history = {}
    for k in ("d_loss", "g_loss", "skew_ema", "k_eff"):
        rows = [s["history"].get(k) for s in summaries]
        if all(r is not None for r in rows):
            n = min(len(r) for r in rows)
            history[k] = np.stack([np.asarray(r[:n]) for r in rows], axis=1)
    return {"state": state, "history": history, "summaries": summaries,
            "run_dir": run_dir}


# ----------------------------------------------------------------------------
# worker side


def _worker_main(rank: int, run_dir: str) -> int:
    with open(os.path.join(run_dir, RUNCONFIG)) as f:
        cfg = json.load(f)

    import jax

    distributed = False
    if cfg["use_distributed"]:
        try:
            jax.distributed.initialize(
                coordinator_address=f"127.0.0.1:{cfg['coordinator_port']}",
                num_processes=cfg["n_outer"] * cfg["n_inner"],
                process_id=rank)
            distributed = True
        except Exception as e:            # mailboxes don't need the cluster
            print(f"rank {rank}: jax.distributed.initialize failed ({e}); "
                  "continuing standalone", flush=True)

    import jax.numpy as jnp
    import numpy as np

    from ..checkpoint.store import save_checkpoint
    from ..core import workflow
    from .jitter import JitterConfig
    from .mailbox import Barrier
    from .proccomm import ProcComm

    wcfg = wcfg_from_dict(cfg["wcfg"])
    n_outer, n_inner = cfg["n_outer"], cfg["n_inner"]
    R = n_outer * n_inner

    # per-rank host-side span tracer (ISSUE 10): every mailbox wait,
    # window read/write, barrier, jitter sleep and ProcComm exchange below
    # this point records into trace_rank<rank>.jsonl; merge the rank files
    # with scripts/obsview.py.  Relative trace dirs land inside run_dir so
    # the trace survives next to the summaries.
    from ..obs import trace as obs_trace
    tracer = None
    if wcfg.obs.trace_dir:
        tdir = wcfg.obs.trace_dir
        if not os.path.isabs(tdir):
            tdir = os.path.join(run_dir, tdir)
        os.makedirs(tdir, exist_ok=True)
        tracer = obs_trace.Tracer(
            os.path.join(tdir, f"trace_rank{rank}.jsonl"), rank=rank)
        obs_trace.install(tracer)
    n_epochs = cfg["n_epochs"]
    lockstep = cfg["lockstep"]
    jitter = JitterConfig.from_dict(cfg["jitter"])
    timeout = float(cfg.get("timeout", 900.0))

    data = jnp.asarray(np.load(os.path.join(run_dir, DATA_FILE))["data"])

    # -- bitwise-identical starting point: the SAME seed derivation as
    # train_vmap (workflow.init_run is the single shared recipe), built
    # for this rank only — no full R-rank state in every worker ------------
    state, data_local = workflow.init_run(
        jax.random.PRNGKey(cfg["seed"]), R, wcfg, data, rank=rank)

    schedule = workflow.make_schedule(wcfg)
    comm = ProcComm(n_outer, n_inner, rank, run_dir, lockstep=lockstep,
                    timeout=timeout,
                    window_bytes=wcfg.sync.ring_chunking)
    barrier = Barrier(run_dir, rank, R, timeout=timeout)

    # cadence-aware per-rank steps: the proc runtime's epoch loop is eager
    # Python, so the SPMD backends' SPMD-uniform lax.cond becomes a plain
    # `if` on the same epoch-derived predicates (identical on every rank,
    # so the lock-step exchange pairing stays matched — exchanges happen on
    # exactly the generator-due epochs everywhere).  Each (disc, gen) flag
    # combination jits its own specialization, so off-epochs genuinely run
    # the smaller program.
    import functools
    fn_grads = {}
    for ud in (True, False):
        for ug in (True, False):
            fn_grads[(ud, ug)] = jax.jit(functools.partial(
                lambda s, d, ud, ug: workflow.rank_grads(
                    s, d, wcfg, update_disc=ud, update_gen=ug),
                ud=ud, ug=ug))
    fn_apply = jax.jit(
        lambda s, g, ns: workflow.rank_apply(s, g, ns, wcfg))
    fn_bump = jax.jit(lambda s: dict(s, epoch=s["epoch"] + 1))

    start = 0
    ckpt_dir = os.path.join(run_dir, "ckpt", f"rank_{rank}")
    if cfg.get("resume_step") is not None:
        # the launcher negotiated the newest step loadable by EVERY rank;
        # restarting anywhere else would desync the lock-step pairing
        from ..checkpoint.store import restore_checkpoint
        start = cfg["resume_step"]
        state = restore_checkpoint(ckpt_dir, start, state)
        print(f"rank {rank}: resumed from epoch {start}", flush=True)

    barrier.arrive_and_wait("run start")
    adaptive = wcfg.sync.adaptive
    obs_on = wcfg.obs.metrics
    hist = {"d_loss": [], "g_loss": [], "skew_ema": [], "k_eff": [],
            "epoch_s": []}
    if obs_on:
        hist["deposit_age"], hist["shipped"] = [], []
    t_run = time.time()
    for e in range(start, n_epochs):
        with obs_trace.span("epoch", cat="epoch", epoch=e):
            jitter.apply(rank, e)
            t0 = time.perf_counter()
            disc_due = (e % wcfg.disc_every) == 0
            gen_due = (e % wcfg.gen_every) == 0
            with obs_trace.span("compute.grads", cat="compute", epoch=e):
                new_state, g_grads, metrics = fn_grads[(disc_due, gen_due)](
                    state, data_local)
                if tracer is not None:   # make the span cover the compute,
                    jax.block_until_ready(g_grads)   # not just the dispatch
            if gen_due:
                comm.begin_epoch(e)
                row = None
                with obs_trace.span("exchange", cat="wire", epoch=e):
                    if obs_on:
                        synced, new_sync, row = schedule.exchange_with_obs(
                            comm, g_grads, new_state["sync"],
                            new_state["epoch"])
                    else:
                        synced, new_sync = schedule.exchange(
                            comm, g_grads, new_state["sync"],
                            new_state["epoch"])
                with obs_trace.span("compute.apply", cat="compute",
                                    epoch=e):
                    state = fn_apply(new_state, synced, new_sync)
                if obs_on:
                    state = dict(state, obs=schedule.accumulate_obs(
                        new_state["obs"], row))
            else:                   # disc-only epoch: no exchange, no apply
                state = fn_bump(new_state)
            jax.block_until_ready(state)
        hist["epoch_s"].append(time.perf_counter() - t0)
        hist["d_loss"].append(float(metrics["d_loss"]))
        hist["g_loss"].append(float(metrics["g_loss"]))
        if adaptive:
            hist["skew_ema"].append(float(state["sync"]["ctrl"]["skew_ema"]))
            hist["k_eff"].append(int(state["sync"]["ctrl"]["k_eff"]))
            if tracer is not None:
                tracer.counter("skew_ema", hist["skew_ema"][-1])
                tracer.counter("k_eff", hist["k_eff"][-1])
        if obs_on:
            hist["deposit_age"].append(float(state["obs"]["deposit_age"]))
            hist["shipped"].append(int(state["obs"]["shipped"]))
            if tracer is not None:
                tracer.counter("deposit_age", hist["deposit_age"][-1])
        if cfg["ckpt_every"] and (e + 1) % cfg["ckpt_every"] == 0:
            save_checkpoint(ckpt_dir, e + 1, state,
                            metadata={"rank": rank, "epochs": e + 1})

    save_checkpoint(os.path.join(run_dir, "final", f"rank_{rank}"),
                    n_epochs, state, metadata={"rank": rank})
    if not adaptive:
        hist.pop("skew_ema"), hist.pop("k_eff")
    summary = {
        "rank": rank, "n_epochs": n_epochs, "start_epoch": start,
        "distributed": distributed, "backend": jax.default_backend(),
        "lockstep": lockstep,
        "jitter": jitter.to_dict(), "wall_s": time.time() - t_run,
        "epoch_s_best": (min(hist["epoch_s"][1:] or hist["epoch_s"])
                         if hist["epoch_s"] else None),
        "max_skew_ema": max(hist.get("skew_ema") or [0.0]),
        "max_k_eff": max(hist.get("k_eff") or [1]),
        "history": hist,
    }
    if obs_on:
        summary["obs"] = {
            "payload_bytes": schedule.payload_bytes,
            "ship_count": int(state["obs"]["ship_count"]),
            "exchange_count": int(state["obs"]["exchange_count"]),
            "max_deposit_age": max(hist.get("deposit_age") or [0.0]),
        }
    with open(os.path.join(run_dir, f"summary_rank{rank}.json"), "w") as f:
        json.dump(summary, f, indent=1)

    # keep the coordinator (process 0) alive until every rank is done
    barrier.arrive_and_wait("run end")
    if tracer is not None:
        obs_trace.uninstall()
        tracer.close()
    if distributed:
        try:
            jax.distributed.shutdown()
        except Exception:
            pass
    return 0


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="SAGIPS proc-runtime worker entry point (spawned by "
                    "repro.runtime.launch.run_proc; see also "
                    "examples/train_sagips_gan.py --backend proc)")
    ap.add_argument("--worker", action="store_true", required=True)
    ap.add_argument("--rank", type=int, required=True)
    ap.add_argument("--run-dir", required=True)
    args = ap.parse_args(argv)
    from ..launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    return _worker_main(args.rank, args.run_dir)


if __name__ == "__main__":
    sys.exit(main())
