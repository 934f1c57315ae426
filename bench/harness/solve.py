"""The general open-loop runner of the solve service, for the traffic
kind `solve_open` (`kinds/solve_open.py`).

Independent clients send requests at the times the mix's arrival
process (`arrivals/<arrivals>.py`) gives; each request carries n events,
n log-uniform on [events_min, events_max], made by the plain reference's
forward model at a truth drawn uniformly from [truth_low, truth_high]
per parameter.  Every seed sends the same multiset of sizes and gaps in
its own order, so the work does not change with the seed.  A rejected
request is sent again after the service's retry-after; its latency runs
from when it was first due until its answer was back.

One thread sends, the main thread drains (`SolveService.step`).  The
window closes when every request has its answer, or `grace_s` after the
last was due; a request still unanswered then has failed.
"""
from __future__ import annotations

import collections
import heapq
import math
import threading
import time

import numpy as np

from . import compare
from .seeds import cell_keys


def serving_config(cfg: dict):
    from repro.core.workflow import SolveConfig
    from repro.serving.service import ServingConfig
    s = cfg["serving"]
    return ServingConfig(buckets=tuple(s["buckets"]),
                         max_batch=s["max_batch"],
                         queue_capacity=s["queue_capacity"],
                         cache_capacity=s["cache_capacity"],
                         retry_after_s=s["retry_after_s"],
                         solve=SolveConfig(**cfg["solve"]))


def schedule(tr: dict, seconds: float, seed: int, process):
    """(sizes [n], send offsets [n] in s) for a window of `seconds`, from
    the arrival process `process` (a module of `arrivals/`)."""
    rng = np.random.default_rng(seed)
    arrivals = process.offsets(tr, seconds, rng)
    n = len(arrivals)
    q = (np.arange(n) + 0.5) / n
    lo, hi = math.log(tr["events_min"]), math.log(tr["events_max"])
    sizes = np.rint(np.exp(lo + q * (hi - lo))).astype(np.int64)
    return rng.permutation(sizes), arrivals


class OpenLoop:
    """One open-loop window over a started service."""

    def __init__(self, svc, problem: str, requests, arrivals, span,
                 host=None):
        self.svc, self.problem = svc, problem
        self.requests, self.arrivals = requests, arrivals
        self.span = span
        self.host = host          # a HostWatch over the window, or None
        n = len(requests)
        self.tickets = [None] * n
        self.sent = np.full(n, np.nan)       # first attempt
        self.done = np.full(n, np.nan)
        self.rejected = 0
        self.steps = self.busy_steps = self.drained = 0
        self.max_backlog = 0
        self.longest_step = (0.0, 0.0, 0.0, 0.0)   # wall, thread CPU,
        #                                           process CPU, start
        self._new = collections.deque()
        self._wake = threading.Event()
        self._stop = threading.Event()

    def _send(self, t0):
        from repro.serving.queue import Backpressure
        heap = [(t0 + a, i) for i, a in enumerate(self.arrivals)]
        heapq.heapify(heap)
        retry = self.svc.cfg.retry_after_s
        while heap and not self._stop.is_set():
            due, i = heapq.heappop(heap)
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            now = time.perf_counter()
            if np.isnan(self.sent[i]):
                self.sent[i] = now
            try:
                with self.span("bench.submit"):
                    self.tickets[i] = self.svc.submit(self.problem,
                                                      self.requests[i])
            except Backpressure:
                self.rejected += 1
                heapq.heappush(heap, (now + retry, i))
                continue
            self._new.append(i)
            self._wake.set()

    def run(self, grace_s: float):
        t0 = time.perf_counter()
        sender = threading.Thread(target=self._send, args=(t0,), daemon=True)
        sender.start()
        deadline = t0 + float(np.max(self.arrivals)) + grace_s
        waiting = []
        left = len(self.requests)
        while left and time.perf_counter() < deadline:
            self._wake.clear()
            c_thread, c_proc = time.thread_time(), time.process_time()
            t_step = time.perf_counter()
            with self.span("bench.step"):
                n = self.svc.step()
            now = time.perf_counter()
            if now - t_step > self.longest_step[0]:
                self.longest_step = (now - t_step,
                                     time.thread_time() - c_thread,
                                     time.process_time() - c_proc, t_step)
            self.steps += 1
            while self._new:
                waiting.append(self._new.popleft())
            if n:
                self.busy_steps += 1
                self.drained += n
                still = []
                for i in waiting:
                    if self.tickets[i].done():
                        self.done[i] = now
                        left -= 1
                    else:
                        still.append(i)
                waiting = still
            else:
                self._wake.wait(0.005)
            self.max_backlog = max(self.max_backlog, len(self.svc.queue))
        self._stop.set()
        sender.join(timeout=5.0)
        self.elapsed = time.perf_counter() - t0
        self.t0 = t0
        return self

    def latencies(self):
        """Seconds from due to answer; unanswered requests count to the
        window's end (and as failed)."""
        due = self.t0 + np.asarray(self.arrivals)
        end = np.where(np.isnan(self.done), self.t0 + self.elapsed,
                       self.done)
        return end - due

    def lags(self):
        return self.sent - (self.t0 + np.asarray(self.arrivals))

    def longest_step_text(self) -> str:
        wall, thread, proc, start = self.longest_step
        text = (f"longest step {1e3 * wall:.1f} ms at "
                f"{start - self.t0:.3f} s (drainer CPU {1e3 * thread:.1f} "
                f"ms, process CPU {1e3 * proc:.1f} ms")
        if self.host is not None:
            text += (f", collector "
                     f"{1e3 * self.host.overlap(start, start + wall):.1f} ms")
        lags = self.lags()
        i = int(np.nanargmax(lags))
        return text + (f"); sender's worst lateness {1e3 * lags[i]:.1f} ms "
                       f"at {self.sent[i] - self.t0:.3f} s")


def make_requests(ctx, problem_ref, key, sizes):
    """All requests' events, made by the plain reference's forward model
    (`reference/<problem>.py`) on the device in one call."""
    import jax
    import jax.numpy as jnp
    tr = ctx.traffic
    n, total = len(sizes), int(np.sum(sizes))
    owner = np.repeat(np.arange(n), sizes)

    @jax.jit
    def make(k, owner):
        k_t, k_u = jax.random.split(k)
        truths = jax.random.uniform(k_t, (n, problem_ref.N_PARAMS),
                                    minval=tr["truth_low"],
                                    maxval=tr["truth_high"])
        u = jax.random.uniform(k_u, (total, 1, problem_ref.NOISE_CHANNELS))
        return problem_ref.forward(jnp.take(truths, owner, axis=0), u)

    pool = np.asarray(make(key, owner))
    return np.split(pool, np.cumsum(sizes)[:-1])


def warm_path(svc, problem, prob):
    """Every bucket once through submit -> step, at a full batch."""
    for b in svc.cfg.buckets:
        y = np.zeros((b, prob.obs_dim), np.float32)
        tickets = [svc.submit(problem, y) for _ in range(svc.cfg.max_batch)]
        svc.run_until_empty()
        for t in tickets:
            t.result(timeout=60.0)


def run(ctx):
    import jax
    from repro.core import gan
    from repro.problems import get_problem
    from repro.serving.service import SolveService

    cfg, tr = ctx.config, ctx.traffic
    prob = get_problem(cfg["problem"])
    keys = cell_keys(ctx.seed)
    k_gen, k_req = jax.random.split(keys["traffic"])
    R = tr["n_ranks"]

    @jax.jit
    def init(k):
        return jax.vmap(lambda kk: gan.init_generator(
            kk, n_params=prob.n_params, param_shape=prob.param_shape))(
                jax.random.split(k, R))

    with ctx.span("bench.init"):
        gens = jax.block_until_ready(init(k_gen))
    sizes, arrivals = schedule(tr, ctx.window_seconds(), ctx.seed,
                               ctx.bench.arrivals(tr["arrivals"]))
    n_req = len(sizes)
    problem_ref = ctx.bench.reference(cfg["reference"])
    requests = make_requests(ctx, problem_ref, k_req, sizes)
    svc = SolveService(serving_config(cfg))
    svc.register_problem(cfg["problem"], gen_stack=gens)
    svc.warm(cfg["problem"])
    warm_path(svc, cfg["problem"], prob)
    ctx.setup_done()

    with ctx.window():
        loop = OpenLoop(svc, cfg["problem"], requests, arrivals,
                        ctx.span, ctx.host).run(tr["grace_s"])
    ctx.read_memory()

    lat = loop.latencies()
    answered = ~np.isnan(loop.done)
    ctx.attempted, ctx.failed = n_req, int(np.sum(~answered))
    for q in (50, 95, 99):
        ctx.e2e[f"solve_p{q}_ms"] = 1e3 * float(np.percentile(lat, q))
    lags = loop.lags()
    ctx.window_facts.update({
        "requests": n_req, "rejected": loop.rejected,
        "steps": loop.steps, "busy_steps": loop.busy_steps,
        "drained": loop.drained, "max_batch": svc.cfg.max_batch,
        "gen_lag_s": lags[~np.isnan(lags)].tolist(),
        "latency_p99_ms": ctx.e2e["solve_p99_ms"],
        "max_backlog": loop.max_backlog})
    from .cell import log
    log(f"[solve] {n_req} requests, {ctx.failed} unanswered, "
        f"{loop.rejected} rejections, max backlog {loop.max_backlog}, "
        f"{loop.busy_steps} batches, sender lag p99 "
        f"{1e3 * float(np.nanpercentile(lags, 99)):.2f} ms; latency "
        f"p50/p95/p99 "
        + "/".join(f"{ctx.e2e[f'solve_p{q}_ms']:.3f}" for q in (50, 95, 99))
        + " ms")
    log(f"[solve] {loop.longest_step_text()}")

    # check a seeded sample of the answered requests, the largest among them
    idx = np.flatnonzero(answered)
    pick = np.random.default_rng(ctx.seed + 1).choice(
        idx, size=min(tr["check_requests"], idx.size), replace=False) \
        if idx.size else idx
    if idx.size:
        pick = np.union1d(pick, idx[np.argmax(sizes[idx])])
    prog = [loop.tickets[i].result(timeout=0) for i in pick]
    del svc, gens, loop
    t_ref = time.perf_counter()
    ref_mod = ctx.bench.reference("solve")
    ref_gens = ref_mod.init_generators(cfg, k_gen, R)
    ref_c = ref_mod.candidates(cfg, problem_ref, ref_gens)
    ref = [ref_mod.answer(cfg, *ref_c, requests[i]) for i in pick]
    log(f"[reference] {time.perf_counter() - t_ref:.1f} s for "
        f"{len(pick)} requests")
    if ctx.control:
        ctl_c = ref_mod.candidates(cfg, problem_ref, ref_gens, "fp8")
        prog = [ref_mod.answer(cfg, *ctl_c, requests[i]) for i in pick]
    numbers = compare.solve_numbers(prog, ref)
    numbers["unanswered"] = float(ctx.failed)
    return numbers
