"""Serving-surface tests (ISSUE 8, written test-first).

Covers the four layers of the solve service plus the end-to-end quality
bar, in the fast lane (`scripts/check.sh --serving`):

  bucketing     smallest-admitting-bucket selection, exactly-once
                admission (property test), padding masked out of results
  cache         LRU eviction order, capacity-1 degeneration, hit recency
  queue         reject-not-block backpressure, per-lane FIFO after drain,
                oldest-head lane fairness
  concurrency   concurrent submitters + one drainer never deadlock, drop
                or double-serve (PR 6 `Gate` adversarial interleavings
                through `serving.queue.set_hook`)
  service/e2e   tiny generators trained per registered problem, served
                through `SolveService`, residual below the problem's
                `solve_threshold`; missing-checkpoint and unknown-problem
                failures surface as clear `ServingError`s
"""
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st

from repro.analysis.faults import InterleavingDriver
from repro.core import gan, workflow
from repro.core.sync import SyncConfig
from repro.problems import available, get_problem
from repro.serving import (Backpressure, BoundedRequestQueue, CompileCache,
                           RequestTooLarge, ServingConfig, ServingError,
                           SolveService, bucket_for, make_buckets,
                           pad_events)
from repro.serving import queue as serving_queue
from repro.serving.bucketing import validate_buckets


# ----------------------------------------------------------------------------
# bucketing


def test_bucket_for_smallest_admitting():
    ladder = (16, 64, 256)
    assert bucket_for(1, ladder) == 16
    assert bucket_for(16, ladder) == 16          # boundary: exact fit
    assert bucket_for(17, ladder) == 64          # boundary: first above
    assert bucket_for(64, ladder) == 64
    assert bucket_for(65, ladder) == 256
    assert bucket_for(256, ladder) == 256
    with pytest.raises(RequestTooLarge):
        bucket_for(257, ladder)
    with pytest.raises(ValueError):
        bucket_for(0, ladder)


def test_make_and_validate_buckets():
    assert make_buckets(1000, base=64, growth=4) == (64, 256, 1024)
    assert make_buckets(64, base=64, growth=4) == (64,)
    for bad in ((), (0, 4), (4, 4), (64, 16)):
        with pytest.raises(ValueError):
            validate_buckets(bad)


@settings(max_examples=50)
@given(st.integers(1, 1024))
def test_bucket_assignment_property(n):
    """Any request <= max(buckets) is admitted by EXACTLY ONE bucket — the
    smallest admitting one — and is never split across buckets."""
    ladder = (16, 64, 256, 1024)
    b = bucket_for(n, ladder)
    admitting = [x for x in ladder if n <= x]
    assert b == admitting[0]                     # smallest admitting
    assert b in ladder and n <= b
    # exactly-once: every smaller bucket rejects, so no second home exists
    assert all(n > x for x in ladder if x < b)


def test_pad_events_shapes_and_mask():
    y = np.arange(10, dtype=np.float32).reshape(5, 2)
    padded, mask = pad_events(y, 16)
    assert padded.shape == (16, 2) and mask.shape == (16,)
    assert mask.sum() == 5 and mask[:5].all() and not mask[5:].any()
    np.testing.assert_array_equal(padded[:5], y)
    with pytest.raises(ValueError):
        pad_events(y, 4)                         # does not fit


def test_padding_masked_out_of_results():
    """The same observations padded into two different buckets — and with
    garbage in the padding rows — produce identical solve results."""
    prob = get_problem("proxy1d")
    solve = workflow.make_solver(prob, workflow.SolveConfig(
        n_candidates=8, events_per_candidate=8))
    gen = _prior_stack(prob, ranks=2)
    y = np.asarray(prob.make_reference_data(jax.random.PRNGKey(3), 10))

    outs = []
    for bucket, fill in ((16, 0.0), (64, 123.456)):
        padded, mask = pad_events(y, bucket)
        padded[~mask] = fill                     # garbage must not matter
        outs.append(solve(gen, jnp.asarray(padded[None]),
                          jnp.asarray(mask[None])))
    np.testing.assert_allclose(np.asarray(outs[0]["params"]),
                               np.asarray(outs[1]["params"]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(outs[0]["score"]),
                               np.asarray(outs[1]["score"]), rtol=1e-5)


# ----------------------------------------------------------------------------
# compile cache


def test_cache_lru_eviction_order():
    c = CompileCache(capacity=2)
    build = lambda tag: (lambda: tag)
    assert c.get("a", build("A")) == "A"
    assert c.get("b", build("B")) == "B"
    assert c.keys() == ["a", "b"]                # LRU first
    c.get("c", build("C"))                       # evicts a (LRU)
    assert "a" not in c and "b" in c and "c" in c
    assert c.stats["evictions"] == 1
    # evicted key rebuilds (a fresh compile), refreshing recency
    assert c.get("a", build("A2")) == "A2"
    assert "b" not in c                          # b was LRU at that point
    assert c.stats["compiles"] == 4


def test_cache_hit_refreshes_recency():
    c = CompileCache(capacity=2)
    c.get("a", lambda: 1)
    c.get("b", lambda: 2)
    c.get("a", lambda: 99)                       # HIT: no rebuild...
    assert c.get("a", lambda: 99) == 1
    c.get("c", lambda: 3)                        # ...and a is now MRU
    assert c.keys() == ["a", "c"] and "b" not in c
    assert c.stats["hits"] == 2


def test_cache_capacity_one():
    c = CompileCache(capacity=1)
    assert c.get("a", lambda: 1) == 1
    assert c.get("b", lambda: 2) == 2            # each key evicts the last
    assert len(c) == 1 and "a" not in c
    assert c.get("a", lambda: 10) == 10          # recompiled, not stale
    assert c.stats == {"hits": 0, "misses": 3, "compiles": 3,
                       "evictions": 2}
    with pytest.raises(ValueError):
        CompileCache(capacity=0)


# ----------------------------------------------------------------------------
# queue / backpressure


def test_queue_full_rejects_not_blocks():
    q = BoundedRequestQueue(capacity=2, retry_after_s=0.25)
    q.submit(("p", 16), "r0")
    q.submit(("p", 64), "r1")
    with pytest.raises(Backpressure) as ei:
        q.submit(("p", 16), "r2")                # returns immediately
    assert ei.value.retry_after_s == 0.25
    assert len(q) == 2 and q.stats["rejected"] == 1
    # the rejected submit lost nothing and freed capacity admits again
    assert q.drain(("p", 16), 8) == ["r0"]
    q.submit(("p", 16), "r2")
    assert len(q) == 2


def test_queue_fifo_per_lane_after_drain():
    q = BoundedRequestQueue(capacity=16)
    for i in range(3):
        q.submit(("p", 16), f"a{i}")
        q.submit(("p", 64), f"b{i}")
    # oldest head wins: lane 16 holds the globally oldest request
    assert q.next_key() == ("p", 16)
    assert q.drain(("p", 16), 2) == ["a0", "a1"]  # FIFO, partial drain
    assert q.next_key() == ("p", 64)              # b0 now oldest head
    assert q.drain(("p", 64), 8) == ["b0", "b1", "b2"]
    assert q.drain(("p", 16), 8) == ["a2"]        # remainder kept in order
    assert q.next_key() is None and len(q) == 0


# ----------------------------------------------------------------------------
# concurrency (PR 6 fault-injection harness over serving.queue)


def test_concurrent_submitters_one_drainer_exactly_once():
    """4 submitter threads x 25 requests against capacity 8, one drainer:
    every request is served exactly once — none dropped, none duplicated,
    and everything joins (no deadlock)."""
    q = BoundedRequestQueue(capacity=8, retry_after_s=0.001)
    n_sub, per = 4, 25
    served, lock = [], threading.Lock()
    stop = threading.Event()

    def submitter(tid):
        for i in range(per):
            item = (tid, i)
            while True:
                try:
                    q.submit(("p", 16), item)
                    break
                except Backpressure as e:
                    stop.wait(e.retry_after_s)   # honor retry-after

    def drainer():
        while not stop.is_set() or len(q):
            batch = q.drain(("p", 16), 4)
            if batch:
                with lock:
                    served.extend(batch)

    threads = [threading.Thread(target=submitter, args=(t,))
               for t in range(n_sub)]
    d = threading.Thread(target=drainer)
    d.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive(), "submitter deadlocked"
    stop.set()
    d.join(timeout=30)
    assert not d.is_alive(), "drainer deadlocked"
    assert sorted(served) == sorted((t, i) for t in range(n_sub)
                                    for i in range(per))
    assert q.stats["admitted"] == q.stats["drained"] == n_sub * per


def test_gated_interleaving_no_drop_or_double_serve():
    """Adversarial schedule: park a submitter INSIDE submit (pre-admission
    hook) while the drainer empties the queue past it, then release — the
    parked request must still be admitted and served exactly once."""
    q = BoundedRequestQueue(capacity=8)
    with InterleavingDriver(set_hook=serving_queue.set_hook) as drv:
        # trip on the 2nd submit event: the victim submitter
        gate = drv.gate("queue.submit", hit=2)
        q.submit(("p", 16), "first")

        victim_done = threading.Event()

        def victim():
            q.submit(("p", 16), "second")
            victim_done.set()

        t = threading.Thread(target=victim)
        t.start()
        gate.wait_reached()                      # victim parked pre-admission
        assert q.drain(("p", 16), 8) == ["first"]   # race past it
        assert len(q) == 0
        gate.release()
        t.join(timeout=20)
        assert victim_done.is_set(), "parked submitter never completed"
        # the parked request landed after the race, exactly once
        assert q.drain(("p", 16), 8) == ["second"]
        assert q.stats["admitted"] == 2 and q.stats["drained"] == 2


def test_gated_drainers_never_split_a_drain():
    """Two racing drainers around a gated drain: each admitted item goes to
    exactly one of them (drain pops under the lock; hooks fire outside)."""
    q = BoundedRequestQueue(capacity=16)
    for i in range(6):
        q.submit(("p", 16), i)
    got = {}
    with InterleavingDriver(set_hook=serving_queue.set_hook) as drv:
        gate = drv.gate("queue.drain", hit=1)    # park drainer A post-drain

        def drainer(name):
            got[name] = q.drain(("p", 16), 4)

        a = threading.Thread(target=drainer, args=("a",))
        a.start()
        gate.wait_reached()                      # A drained, parked at hook
        drainer("b")                             # B races the parked A
        gate.release()
        a.join(timeout=20)
        assert not a.is_alive()
    assert sorted(got["a"] + got["b"]) == list(range(6))
    assert len(got["a"]) == 4 and len(got["b"]) == 2


# ----------------------------------------------------------------------------
# service


def _prior_stack(prob, ranks=2, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), ranks)
    return jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[gan.init_generator(k, n_params=prob.n_params) for k in keys])


def _tiny_cfg(max_batch=4):
    return ServingConfig(
        buckets=(16, 64), max_batch=max_batch, queue_capacity=16,
        cache_capacity=4, retry_after_s=0.01,
        solve=workflow.SolveConfig(n_candidates=8, events_per_candidate=8))


def test_missing_checkpoint_clear_error(tmp_path):
    svc = SolveService(_tiny_cfg())
    with pytest.raises(ServingError) as ei:
        svc.register_problem("proxy1d", checkpoint_dir=str(tmp_path))
    msg = str(ei.value)
    assert "proxy1d" in msg and str(tmp_path) in msg
    assert "train" in msg.lower()                # actionable, not a trace


def test_unknown_or_unregistered_problem_clear_error():
    svc = SolveService(_tiny_cfg())
    with pytest.raises(ServingError):
        svc.register_problem("no_such_problem", gen_stack={})
    with pytest.raises(ServingError) as ei:
        svc.submit("proxy1d", np.zeros((4, 2), np.float32))
    assert "register_problem" in str(ei.value)
    svc.register_problem("proxy1d",
                         gen_stack=_prior_stack(get_problem("proxy1d")))
    with pytest.raises(ServingError):            # wrong obs_dim
        svc.submit("proxy1d", np.zeros((4, 3), np.float32))


def test_top_frac_one_is_prior_mean():
    """top_frac=1.0 keeps every candidate, so the estimate is the prior
    (ensemble) mean — independent of the submitted observations."""
    prob = get_problem("proxy1d")
    solve = workflow.make_solver(prob, workflow.SolveConfig(
        n_candidates=8, events_per_candidate=8, top_frac=1.0))
    gen = _prior_stack(prob)
    outs = []
    for seed in (1, 2):
        y = np.asarray(prob.make_reference_data(jax.random.PRNGKey(seed), 12))
        padded, mask = pad_events(y, 16)
        outs.append(np.asarray(solve(gen, jnp.asarray(padded[None]),
                                     jnp.asarray(mask[None]))["params"]))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-6)


def test_service_matches_direct_solver():
    """A request served through the full queue/bucket/cache/batch-padding
    path returns exactly what the bare `make_solver` computes on the same
    padded observations."""
    prob = get_problem("proxy1d")
    cfg = _tiny_cfg()
    svc = SolveService(cfg)
    gen = _prior_stack(prob)
    svc.register_problem("proxy1d", gen_stack=gen)
    y = np.asarray(prob.make_reference_data(jax.random.PRNGKey(5), 12))

    ticket = svc.submit("proxy1d", y)
    assert svc.run_until_empty() == 1
    via_service = ticket.result(timeout=30)

    solve = workflow.make_solver(prob, cfg.solve)
    padded, mask = pad_events(y, ticket.bucket)
    direct = solve(gen, jnp.asarray(padded[None]), jnp.asarray(mask[None]))
    np.testing.assert_allclose(via_service["params"],
                               np.asarray(direct["params"][0]), rtol=1e-6)
    np.testing.assert_allclose(via_service["sigma"],
                               np.asarray(direct["sigma"][0]), rtol=1e-5)


def test_service_batches_share_one_executable():
    """Many requests in one bucket fuse into max_batch-sized drains against
    a single compiled executable; a second bucket compiles its own."""
    prob = get_problem("proxy1d")
    svc = SolveService(_tiny_cfg(max_batch=4))
    svc.register_problem("proxy1d", gen_stack=_prior_stack(prob))
    tickets = [svc.submit("proxy1d",
                          np.asarray(prob.make_reference_data(
                              jax.random.PRNGKey(i), 8 + i)))
               for i in range(6)]                # all land in bucket 16
    t_big = svc.submit("proxy1d", np.asarray(
        prob.make_reference_data(jax.random.PRNGKey(9), 40)))  # bucket 64
    assert svc.run_until_empty() == 7
    for t in tickets + [t_big]:
        assert t.done() and np.isfinite(t.result()["params"]).all()
    stats = svc.stats()
    assert stats["cache"]["compiles"] == 2       # one per touched bucket
    # 6 bucket-16 requests in ceil(6/4)=2 drains + 1 bucket-64 drain
    assert stats["queue"]["drained"] == 7 and svc.served == 7


# ----------------------------------------------------------------------------
# end-to-end: train tiny generators, serve, check the quality bar


@pytest.fixture(scope="module")
def trained_stacks():
    """CPU-scale trained generator stacks per registered problem (R=4,
    300 epochs — seconds each for the proxy problems, ~2 min for the
    image problems; thresholds in `solve_threshold` carry margin over the
    residuals this recipe reaches).  Configs route through
    `sagips_gan.for_problem` so image-valued problems pick up the conv
    recipe (event budget + capped generator step) the presets encode."""
    from repro.configs import sagips_gan
    stacks = {}
    for name in available():
        prob = get_problem(name)
        base = workflow.WorkflowConfig(
            sync=SyncConfig(mode="rma_arar_arar", h=10),
            n_param_samples=16, events_per_sample=8,
            gen_lr=2e-4, disc_lr=5e-4)
        wcfg = sagips_gan.for_problem(name, base)
        data = prob.make_reference_data(jax.random.PRNGKey(99), 2000)
        state, _ = workflow.train_vmap(jax.random.PRNGKey(0), wcfg, 2, 2,
                                       300, data, chunk=100)
        stacks[name] = (state["gen"], data)
    return stacks


@pytest.mark.parametrize("name", available())
def test_e2e_solve_residual_below_threshold(name, trained_stacks):
    """Submit observations generated from the truth; the served estimate
    must land under the problem's `solve_threshold` residual bar."""
    prob = get_problem(name)
    gen, data = trained_stacks[name]
    svc = SolveService(ServingConfig(
        buckets=(64,), max_batch=2, queue_capacity=8, cache_capacity=2,
        solve=workflow.SolveConfig(n_candidates=32, events_per_candidate=16,
                                   top_frac=0.25)))
    svc.register_problem(name, gen_stack=gen)
    ticket = svc.submit(name, np.asarray(data[:64]))
    assert svc.run_until_empty() == 1
    out = ticket.result(timeout=60)
    residual = float(prob.mean_abs_residual(out["params"]))
    assert residual < prob.solve_threshold, (
        f"{name}: served residual {residual:.3f} above the problem's "
        f"solve_threshold {prob.solve_threshold}")
    # and the candidate scoring must have genuinely discriminated: the
    # kept top_frac outscores the problem's bar only if the moment match
    # found the truth region (untrained linear_blur priors sit above 10)
    assert np.isfinite(out["score"]) and np.isfinite(out["sigma"]).all()


def test_e2e_checkpointed_roundtrip(tmp_path, trained_stacks):
    """Save a trained state through the checkpoint store, register the
    problem from the directory (the server path), and serve."""
    from repro.checkpoint.store import save_checkpoint
    prob = get_problem("proxy1d")
    gen, data = trained_stacks["proxy1d"]
    save_checkpoint(str(tmp_path), 300, {"gen": gen},
                    metadata={"problem": "proxy1d"})
    svc = SolveService(_tiny_cfg())
    step = svc.register_problem("proxy1d", checkpoint_dir=str(tmp_path))
    assert step == 300
    ticket = svc.submit("proxy1d", np.asarray(data[:16]))
    svc.run_until_empty()
    out = ticket.result(timeout=60)
    assert float(prob.mean_abs_residual(out["params"])) \
        < prob.solve_threshold
