"""The batched solve service — SAGIPS inference as a request surface.

Request lifecycle (docs/serving.md has the full diagram):

    client.submit(problem, y)
        -> bucket_for(n_events)        smallest admitting bucket, or
                                       RequestTooLarge
        -> pad_events                  zero-pad + mask
        -> BoundedRequestQueue.submit  admitted, or Backpressure
                                       (retry-after, never blocks)
    drainer.step()
        -> queue.next_key / drain      oldest-head lane, FIFO batch
        -> CompileCache.get            warm per-(problem, bucket)
                                       executable (LRU; miss = compile)
        -> solve(gen_stack, ys, mask)  `core.workflow.make_solver` output
        -> Ticket.resolve              client unblocks with params/sigma

Each phase is a profiler span (`obs.trace.span`): `sagips.solve.submit`
on the submitter's thread, and per `step()` a `sagips.solve.step` holding
`.drain`, `.compile` (cache misses only), `.assemble`, `.dispatch`,
`.fetch` (waiting for the answer) and `.resolve`.  `.assemble` carries
the batch's `n`, `bucket`, and the queue waits of its requests
(`wait_sum_us`, `wait_max_us`: admission to drain).

The service separates WHAT a solve computes (`make_solver`, built in
`core.workflow` and shared with the trainer's final report) from WHERE it
runs (this module: batching, warm pool, admission control).  All jit goes
through `serving.cache` — lint check 7 keeps it that way.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .bucketing import bucket_for, pad_events, validate_buckets
from .cache import CompileCache, jit_compile
from .queue import Backpressure, BoundedRequestQueue
from ..obs.counters import Counters
from ..obs.trace import span
from ..core import gan
from ..core.workflow import SolveConfig, make_solver
from ..problems import get_problem


class ServingError(RuntimeError):
    """Service-level failure with a client-actionable message (unknown
    problem, missing checkpoint, ...) — never a raw stack trace."""


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Knobs of the serving surface (see docs/serving.md):

    buckets         event-count ladder; a request pads up to the smallest
                    admitting bucket (shape-bucketing, one executable per
                    (problem, bucket))
    max_batch       requests fused per drain; the batch axis is padded to
                    exactly this, so B never shape-specializes
    queue_capacity  global admission bound; a full queue REJECTS
                    (`Backpressure` with `retry_after_s`), never blocks
    cache_capacity  warm executables kept (LRU over (problem, bucket))
    solve           what each executable computes (`core.workflow
                    .SolveConfig`)
    """
    buckets: Tuple[int, ...] = (64, 256, 1024)
    max_batch: int = 8
    queue_capacity: int = 64
    cache_capacity: int = 8
    retry_after_s: float = 0.05
    solve: SolveConfig = SolveConfig()

    def __post_init__(self):
        validate_buckets(self.buckets)
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")


class Ticket:
    """A submitted request's handle: `result(timeout)` blocks until the
    drainer resolves it, then returns {params, sigma, score} (numpy)."""

    def __init__(self, problem: str, bucket: int, n_events: int):
        self.problem = problem
        self.bucket = bucket
        self.n_events = n_events
        # admission stamp and queue-inclusive latency base: `submit` makes
        # the ticket as it hands the request to the queue, and a rejected
        # request's ticket is dropped, so every drained ticket was admitted
        # here
        self.t_submit = time.perf_counter()
        self._done = threading.Event()
        self._result: Optional[dict] = None
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._done.is_set()

    def resolve(self, result: dict):
        self._result = result
        self._done.set()

    def fail(self, exc: BaseException):
        self._error = exc
        self._done.set()

    def result(self, timeout: Optional[float] = None) -> dict:
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"solve request ({self.problem}, bucket {self.bucket}) "
                f"not served within {timeout}s")
        if self._error is not None:
            raise self._error
        return self._result


def load_generator_stack(checkpoint_dir: str, problem) -> jnp.ndarray:
    """Restore the newest trained generator stack `[R, ...]` for `problem`.

    Uses a single-rank `{"gen": ...}` example as the restore template —
    `checkpoint.restore_latest` matches keys (the template may be a subset
    of the saved training state) and keeps the SAVED leaf shapes, so the
    stacked `[R, ...]` generator comes back whole without the server
    knowing R.  No restorable checkpoint is a `ServingError` with a
    client-actionable message, not a stack trace (ISSUE 8 satellite;
    pinned by tests/test_serving.py::test_missing_checkpoint_clear_error).
    """
    from ..checkpoint.store import restore_latest
    like = {"gen": jax.eval_shape(
        lambda k: gan.init_generator(k, n_params=problem.n_params),
        jax.random.PRNGKey(0))}
    try:
        restored, step = restore_latest(checkpoint_dir, like)
    except (KeyError, ValueError, OSError) as e:
        raise ServingError(
            f"checkpoint store at {checkpoint_dir!r} is unusable for "
            f"problem {problem.name!r}: {e}.  Train one with "
            f"examples/train_sagips_gan.py --problem {problem.name} "
            f"--checkpoint-dir {checkpoint_dir}") from None
    if restored is None:
        raise ServingError(
            f"no trained generator checkpoint for problem "
            f"{problem.name!r} under {checkpoint_dir!r}.  Train one with "
            f"examples/train_sagips_gan.py --problem {problem.name} "
            f"--checkpoint-dir {checkpoint_dir}")
    return restored["gen"], step


class SolveService:
    """Batched solve server over registered `InverseProblem`s.

    Thread model: any number of submitter threads call `submit`; ONE
    drainer thread calls `step` in a loop (`run_until_empty` /
    `serve_forever`).  The queue and cache are themselves thread-safe, so
    a misconfigured second drainer degrades throughput, not correctness.
    """

    def __init__(self, cfg: ServingConfig = ServingConfig()):
        self.cfg = cfg
        self.counters = Counters()     # shared obs sink (ISSUE 10): the
        #                                queue records admit/reject into it
        #                                (inside its lock, so interleavings
        #                                can't undercount) and `step`
        #                                records per-bucket latencies
        self.queue = BoundedRequestQueue(cfg.queue_capacity,
                                         cfg.retry_after_s,
                                         counters=self.counters)
        self.cache = CompileCache(cfg.cache_capacity)
        self._problems: Dict[str, tuple] = {}   # name -> (problem, gen_stack)
        self.served = 0

    # -- registration --------------------------------------------------------

    def register_problem(self, name: str, checkpoint_dir: Optional[str] = None,
                         gen_stack=None, step: Optional[int] = None):
        """Make `name` servable.  Provide a trained generator stack either
        directly (`gen_stack`, `[R, ...]` pytree) or via `checkpoint_dir`
        (newest step restored through `load_generator_stack`)."""
        try:
            problem = get_problem(name)
        except KeyError as e:
            raise ServingError(str(e)) from None
        if gen_stack is None:
            if checkpoint_dir is None:
                raise ServingError(
                    f"registering {name!r} needs a trained generator: pass "
                    f"gen_stack or checkpoint_dir")
            gen_stack, step = load_generator_stack(checkpoint_dir, problem)
        self._problems[name] = (problem, gen_stack)
        return step

    def problems(self):
        return tuple(sorted(self._problems))

    # -- client side ---------------------------------------------------------

    def submit(self, problem_name: str, y) -> Ticket:
        """Submit observations `y` [n_events, obs_dim] for `problem_name`.

        Raises `ServingError` (unknown/unregistered problem, wrong obs
        dim), `RequestTooLarge` (n_events above the bucket ladder) or
        `Backpressure` (queue full — retry after `.retry_after_s`).
        Returns a `Ticket`; block on `.result()` for the solve."""
        if problem_name not in self._problems:
            raise ServingError(
                f"problem {problem_name!r} is not registered with this "
                f"service (registered: {list(self.problems())}); call "
                f"register_problem first")
        with span("sagips.solve.submit"):
            problem, _ = self._problems[problem_name]
            y = np.asarray(y, dtype=np.float32)
            if y.ndim != 2 or y.shape[1] != problem.obs_dim:
                raise ServingError(
                    f"{problem_name!r} observations must be [n_events, "
                    f"{problem.obs_dim}], got shape {y.shape}")
            bucket = bucket_for(y.shape[0], self.cfg.buckets)
            padded, mask = pad_events(y, bucket)
            ticket = Ticket(problem_name, bucket, y.shape[0])
            self.queue.submit((problem_name, bucket), (padded, mask, ticket))
        return ticket

    # -- server side ---------------------------------------------------------

    def _executable(self, problem_name: str, bucket: int):
        """The warm per-(problem, bucket) executable, compiling on miss.

        The cached callable is already traced AND compiled (the builder
        runs one dummy batch), so a cache hit costs dispatch only — the
        cold-vs-warm gap is what benchmarks/serving.py measures."""
        problem, gen_stack = self._problems[problem_name]

        def builder():
            with span("sagips.solve.compile", bucket=bucket):
                fn = jit_compile(make_solver(problem, self.cfg.solve))
                ys0 = jnp.zeros((self.cfg.max_batch, bucket,
                                 problem.obs_dim), jnp.float32)
                m0 = jnp.zeros((self.cfg.max_batch, bucket), bool)
                jax.block_until_ready(fn(gen_stack, ys0, m0))
            return fn

        return self.cache.get((problem_name, bucket), builder)

    def warm(self, problem_name: str, buckets: Optional[Tuple[int, ...]] = None):
        """Pre-compile executables for `problem_name` (default: the whole
        ladder), so the first client request hits a warm pool."""
        for b in (buckets or self.cfg.buckets):
            self._executable(problem_name, b)

    def step(self) -> int:
        """Drain and serve ONE batch.  Returns the number of requests
        served (0 = queue empty)."""
        with span("sagips.solve.step"):
            with span("sagips.solve.drain"):
                key = self.queue.next_key()
                items = [] if key is None else self.queue.drain(
                    key, self.cfg.max_batch)
            t_drain = time.perf_counter()
            if not items:
                return 0
            problem_name, bucket = key
            B = self.cfg.max_batch
            tickets = [t for (_, _, t) in items]
            waits = [t_drain - t.t_submit for t in tickets]
            try:
                fn = self._executable(problem_name, bucket)
                with span("sagips.solve.assemble", n=len(tickets),
                          bucket=bucket, wait_sum_us=1e6 * sum(waits),
                          wait_max_us=1e6 * max(waits)):
                    problem, gen_stack = self._problems[problem_name]
                    ys = np.zeros((B, bucket, problem.obs_dim), np.float32)
                    mask = np.zeros((B, bucket), bool)  # padding: all-False
                    for i, (py, pm, _) in enumerate(items):
                        ys[i], mask[i] = py, pm
                    ys, mask = jnp.asarray(ys), jnp.asarray(mask)
                with span("sagips.solve.dispatch"):
                    out = fn(gen_stack, ys, mask)
                with span("sagips.solve.fetch"):
                    out = jax.tree.map(np.asarray, out)
                with span("sagips.solve.resolve"):
                    now = time.perf_counter()
                    for i, t in enumerate(tickets):
                        t.resolve({k: v[i] for k, v in out.items()})
                        # queue-inclusive request latency, bucketed per lane
                        self.counters.observe(f"{problem_name}/b{bucket}",
                                              now - t.t_submit)
                    self.served += len(tickets)
            except Exception as e:   # noqa: BLE001 — tickets must unblock
                for t in tickets:
                    t.fail(e)
                raise
            return len(tickets)

    def run_until_empty(self) -> int:
        """Drain everything queued; returns total requests served."""
        total = 0
        while True:
            n = self.step()
            if n == 0 and len(self.queue) == 0:
                return total
            total += n

    def stats(self) -> dict:
        return {
            "served": self.served,
            "queued": len(self.queue),
            "queue": dict(self.queue.stats),
            "cache": dict(self.cache.stats),
            "warm": self.cache.keys(),
        }

    def snapshot(self) -> dict:
        """`stats()` plus derived serving counters (ISSUE 10): queue
        depth, reject/retry-after rate, compile-cache hit ratio and the
        per-(problem, bucket) queue-inclusive latency histograms.  The
        snapshot is what `launch/serve.py --stats` prints."""
        s = self.stats()
        q, c = s["queue"], s["cache"]
        submits = q["admitted"] + q["rejected"]
        lookups = c["hits"] + c["misses"]
        obs = self.counters.snapshot()
        return dict(s, **{
            "queue_depth": s["queued"],
            "reject_rate": q["rejected"] / submits if submits else 0.0,
            "retry_after_s": self.cfg.retry_after_s,
            "cache_hit_rate": c["hits"] / lookups if lookups else 0.0,
            "counters": obs["counters"],
            "latency": obs["latency"],
        })
