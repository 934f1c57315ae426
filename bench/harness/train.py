"""The general training runner, for the kinds in `kinds/` that drive a
training entry: a kind's `build(traffic, wcfg)` gives the compiled call
(`state, data -> state, metrics`, the state donated), its epochs per
call, the ranks and, for a mesh, the state's sharding.

Set-up builds the state on the device in one jitted call of the
program's own `workflow.init_run` from the seed, then drives the call
through its first `checked_calls` calls as the window drives it: with at
most `in_flight` calls queued on the device, each on the state the last
one donated.  It keeps what the reference is compared with: the losses
of every epoch, Adam's first moments after the first call (copied on the
device before the second call takes the state), and the weights before
the first and after the last.  The window then goes on from that same
state for `--seconds` and ends when the last call has finished; the
state it leaves is checked for its epoch count and its losses.
"""
from __future__ import annotations

import collections
import gc
import time
from typing import Callable, NamedTuple

from . import compare
from .seeds import cell_keys

WORKFLOW_KEYS = ("n_param_samples", "events_per_sample", "data_fraction",
                 "gen_lr", "disc_lr", "sampler_impl", "problem",
                 "disc_every", "gen_every", "disc_compute")


class Entry(NamedTuple):
    call: Callable
    epochs_per_call: int
    n_outer: int
    n_inner: int
    shardings: object          # of the state and data; None on one chip


def workflow_config(cfg: dict):
    from repro.core.sync import SyncConfig
    from repro.core.workflow import WorkflowConfig
    kw = {k: cfg[k] for k in WORKFLOW_KEYS if k in cfg}
    return WorkflowConfig(sync=SyncConfig(**cfg["sync"]), **kw)


def _weights(state):
    return {"gen": state["gen"], "disc": state["disc"]}


def _losses(metrics, n_ranks):
    import numpy as np
    return {k: np.asarray(metrics[k]).reshape(-1, n_ranks)
            for k in ("d_loss", "g_loss")}


def drive(call, state, data, in_flight, span, more, after=None, marks=None):
    """Call `call` on the carried state while `more(calls so far)`, with
    at most `in_flight` calls queued, then wait for the last.
    `after(n, state, metrics)` sees each call's output as it is
    dispatched; `marks` gets the clock at each wait's end.  Returns
    (state, calls made, the last call's metrics)."""
    import jax
    queued = collections.deque()
    n, m = 0, None
    while more(n):
        with span("bench.dispatch"):
            state, m = call(state, data)
        n += 1
        if after is not None:
            after(n, state, m)
        queued.append(m)
        if len(queued) >= in_flight:
            with span("bench.block"):
                jax.block_until_ready(queued.popleft())
            if marks is not None:
                marks.append(time.perf_counter())
    with span("bench.block"):
        jax.block_until_ready((state, list(queued)))
    if marks is not None:
        marks.append(time.perf_counter())
    return state, n, m


def run(ctx, build):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import workflow

    from .cell import log

    cfg, tr = ctx.config, ctx.traffic
    wcfg = workflow_config(cfg)
    prob = wcfg.problem_obj
    entry = build(tr, wcfg)
    n_outer, n_inner, per_call = \
        entry.n_outer, entry.n_inner, entry.epochs_per_call
    R = n_outer * n_inner
    keys = cell_keys(ctx.seed)
    call = ctx.wrap_call(entry.call)

    def init(k_data, k_run):
        data = prob.make_reference_data(k_data, cfg["reference_events"])
        return workflow.init_run(k_run, R, wcfg, data)

    init = jax.jit(init) if entry.shardings is None \
        else jax.jit(init, out_shardings=entry.shardings)
    with ctx.span("bench.init"):
        state, dpr = jax.block_until_ready(init(keys["data"], keys["run"]))

    # the checked calls, queued as in the window
    snap = jax.jit(lambda s: jax.tree.map(
        jnp.copy, {"gen": s["gen_opt"]["mu"], "disc": s["disc_opt"]["mu"]}))
    prog = {"start": jax.device_get(_weights(state))}
    checked, mu_first = [], []

    def keep(n, s, m):
        checked.append(m)
        if n == 1:
            mu_first.append(snap(s))

    state, _, _ = drive(call, state, dpr, tr["in_flight"], ctx.span,
                        lambda n: n < tr["checked_calls"], keep)
    prog["end"] = jax.device_get(_weights(state))
    prog["mu_first"] = jax.device_get(mu_first[0])
    losses = [_losses(jax.device_get(m), R) for m in checked]
    prog["losses"] = {k: np.concatenate([x[k] for x in losses])
                      for k in ("d_loss", "g_loss")}
    del checked, mu_first
    ctx.setup_done()

    window = ctx.window_seconds()
    marks = []
    with ctx.window() as w:
        with ctx.span("bench.window"):
            state, calls, m = drive(
                call, state, dpr, tr["in_flight"], ctx.span,
                lambda n: n == 0 or time.perf_counter() - w.t0 < window,
                marks=marks)
    elapsed = w.elapsed
    epochs = calls * per_call
    gaps = np.diff([w.t0] + marks)
    log(f"[window] {calls} calls; between waits median "
        f"{1e3 * float(np.median(gaps)):.1f} ms, longest "
        f"{1e3 * float(np.max(gaps)):.1f} ms at "
        f"{float(marks[int(np.argmax(gaps))] - w.t0):.3f} s")

    ctx.read_memory()
    # the state the window left: every rank's epoch count, finite losses
    done = (tr["checked_calls"] + calls) * per_call
    epoch_gap = float(np.max(np.abs(
        np.asarray(jax.device_get(state["epoch"]), np.int64) - done)))
    last = _losses(jax.device_get(m), R)
    nonfinite = float(sum(np.sum(~np.isfinite(v)) for v in last.values()))
    del state, dpr, m, call
    gc.collect()

    events = R * cfg["n_param_samples"] * cfg["events_per_sample"]
    counts = ctx.bench.counts(ctx.workload["config"])
    ctx.window_facts.update({
        "epochs": epochs, "rank_epochs": epochs * R,
        "flops_per_rank_epoch": counts.flops_per_rank_epoch(cfg)})
    ctx.e2e["train_events_per_s"] = events * epochs / elapsed
    ctx.attempted, ctx.failed = epochs, 0

    problem_ref = ctx.bench.reference(cfg["reference"])
    reference = ctx.bench.reference("train")
    t_ref = time.perf_counter()
    ref = reference.run(cfg, problem_ref, keys, n_outer, n_inner,
                        tr["checked_calls"], per_call)
    log(f"[reference] {time.perf_counter() - t_ref:.1f} s for "
        f"{tr['checked_calls'] * per_call} epochs at R = {R}")
    if ctx.control:
        # the control: the reference at the precision below, in the
        # program's place
        prog = reference.run(cfg, problem_ref, keys, n_outer, n_inner,
                             tr["checked_calls"], per_call,
                             precision="fp8")
    return dict(compare.train_numbers(prog, ref), epoch_gap=epoch_gap,
                window_nonfinite=nonfinite)
