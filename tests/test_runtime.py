"""Tier-1 tests for the `repro.runtime` multi-process subsystem (ISSUE 5).

Fast units pin the mailbox fabric (seqlock snapshots, lock-step
rendezvous, wire format, warmup values), the deterministic jitter layer
and the `ProcComm` topology edge cases.  The `slow` integration tests
spawn REAL 2-process `jax.distributed` CPU runs through
`runtime.launch.run_proc` and pin the two acceptance behaviours:

  * lock-step, zero jitter: the proc trajectory is BITWISE identical to
    the `VmapComm` exchange engine driving the same jitted per-rank
    compute (inner ring, overlap pod boundary, adaptive bundled tags,
    per-process checkpoint resume), and matches the `train_vmap` golden
    trajectory at the repo's established cross-backend tolerance
    (`tests/test_workflow_dist.py` pins vmap-vs-shard at the same 1e-6:
    batched-vs-unbatched matmul accumulation on CPU costs ~1 ulp/epoch
    in the purely-local discriminator, which no comm backend can remove);
  * free-running with injected jitter: the run completes end-to-end,
    the adaptive controller observes NONZERO deposit-age skew through
    the mailbox tags, and k_eff leaves 1 — the paper's asynchrony,
    measured instead of simulated.
"""
import os
import shutil
import struct
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import workflow
from repro.core.ring import VmapComm
from repro.core.sync import SyncConfig
from repro.core.workflow import WorkflowConfig
from repro.problems import get_problem
from repro.runtime.jitter import JitterConfig
from repro.runtime.launch import run_proc, wcfg_from_dict, wcfg_to_dict
from repro.runtime.mailbox import (_MBX_OFF_WSEQ, _SLOT_HDR, _SLOT_OFF_LOCK,
                                   Board, Mailbox, MailboxTimeout)
from repro.runtime.proccomm import (ProcComm, bytes_to_tree, tree_to_bytes,
                                    warmup_like)

O, I = 1, 2
R = O * I


def small_wcfg(sync):
    return WorkflowConfig(problem="proxy1d", sync=sync,
                          n_param_samples=8, events_per_sample=4)


def assert_trees_equal(a, b, err=""):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=err)


# ----------------------------------------------------------------------------
# mailbox fabric units


def test_mailbox_freerun_latest_snapshot_and_warmup(tmp_path):
    p = str(tmp_path / "edge.bin")
    rd = Mailbox.for_reader(p, 8, timeout=5.0)
    assert rd.read(lockstep=False) is None      # no producer yet: never block
    wr = Mailbox.for_writer(p, 8, timeout=5.0)
    assert rd.read(lockstep=False) is None      # file exists, nothing published
    wr.write(struct.pack("<d", 1.5), tag=3, lockstep=False)
    assert rd.read(lockstep=False) == (struct.pack("<d", 1.5), 3)
    wr.write(struct.pack("<d", 2.5), tag=7, lockstep=False)
    # one-sided: the reader always sees the LATEST deposit, repeatably
    for _ in range(2):
        assert rd.read(lockstep=False) == (struct.pack("<d", 2.5), 7)


def test_mailbox_lockstep_rendezvous_orders_entries(tmp_path):
    p = str(tmp_path / "edge.bin")
    n, got = 6, []

    def producer():
        wr = Mailbox.for_writer(p, 8, timeout=10.0)
        for k in range(n):
            wr.write(struct.pack("<q", k), tag=k, lockstep=True)

    t = threading.Thread(target=producer)
    t.start()
    rd = Mailbox.for_reader(p, 8, timeout=10.0)
    for k in range(n):
        buf, tag = rd.read(lockstep=True)
        got.append((struct.unpack("<q", buf)[0], tag))
    t.join()
    # every entry delivered exactly once, in order — nothing skipped or
    # overwritten even though the producer runs free of the consumer
    assert got == [(k, k) for k in range(n)]


def test_mailbox_lockstep_times_out_on_dead_peer(tmp_path):
    p = str(tmp_path / "edge.bin")
    rd = Mailbox.for_reader(p, 8, timeout=0.2)
    with pytest.raises(MailboxTimeout):
        rd.read(lockstep=True)


def test_board_freerun_latest_and_lockstep_exact(tmp_path):
    p = str(tmp_path / "board.bin")
    wr = Board.for_writer(p, 8, n_ranks=2, timeout=5.0)
    rd = Board.for_reader(p, 8, n_ranks=2, timeout=5.0)
    assert rd.read(1, lockstep=False) is None
    wr.write(struct.pack("<d", 1.0), readers=[1], lockstep=False)
    wr.write(struct.pack("<d", 2.0), readers=[1], lockstep=False)
    assert rd.read(1, lockstep=False) == struct.pack("<d", 2.0)
    # lock-step reader walks the exact sequence the writer published
    assert rd.read(1, lockstep=True) == struct.pack("<d", 1.0)
    assert rd.read(1, lockstep=True) == struct.pack("<d", 2.0)


# ----------------------------------------------------------------------------
# crash recovery (ISSUE 6 satellites): writer restart must RESUME the
# on-file protocol state, never replay it — the adversarial interleavings
# are model-checked in repro.analysis; these pin the real code end-to-end


def test_mailbox_writer_reattach_resumes_freerun_seq(tmp_path):
    p = str(tmp_path / "edge.bin")
    wr = Mailbox.for_writer(p, 8, timeout=5.0)
    for n in (1, 2):
        wr.write(struct.pack("<q", n), tag=n, lockstep=False)
    wr2 = Mailbox.for_writer(p, 8, timeout=5.0)   # checkpoint-resume restart
    wr2.write(struct.pack("<q", 3), tag=3, lockstep=False)
    # seqlock resumed past every value a live reader may hold (2*3), not
    # restarted at 2*1 — a replayed value is the ABA a paused reader's
    # re-check cannot catch
    assert wr2._get(_MBX_OFF_WSEQ) == 6
    rd = Mailbox.for_reader(p, 8, timeout=5.0)
    assert rd.read(lockstep=False) == (struct.pack("<q", 3), 3)


def test_mailbox_writer_reattach_resumes_lockstep_seq(tmp_path):
    p = str(tmp_path / "edge.bin")
    wr = Mailbox.for_writer(p, 8, timeout=5.0)
    rd = Mailbox.for_reader(p, 8, timeout=5.0)
    wr.write(struct.pack("<q", 1), tag=1, lockstep=True)
    assert rd.read(lockstep=True) == (struct.pack("<q", 1), 1)
    wr2 = Mailbox.for_writer(p, 8, timeout=2.0)
    # the restarted writer publishes entry 2, not a second entry 1 — the
    # reader's rendezvous counter is already past 1, so a replay would
    # strand it in MailboxTimeout
    wr2.write(struct.pack("<q", 2), tag=2, lockstep=True)
    assert rd.read(lockstep=True) == (struct.pack("<q", 2), 2)


def test_board_crashed_writer_odd_lock_recovers(tmp_path):
    p = str(tmp_path / "board.bin")
    wr = Board.for_writer(p, 8, n_ranks=1, timeout=5.0)
    wr.write(struct.pack("<q", 1), readers=[0], lockstep=False)   # slot 1
    # simulate dying mid-publish of entry 2: slot 0's seqlock left ODD
    # over a half-written payload
    struct.pack_into("<Q", wr._mm, _SLOT_OFF_LOCK, 1)
    struct.pack_into("<q", wr._mm, _SLOT_HDR.size, 99)
    b2 = Board.for_writer(p, 8, n_ranks=1, timeout=0.5)
    # attach rounded the crashed slot's lock word up to even (a blind
    # `lock + 1` would publish odd forever and wedge every reader)
    assert struct.unpack_from("<Q", b2._mm, _SLOT_OFF_LOCK)[0] == 2
    rd = Board.for_reader(p, 8, n_ranks=1, timeout=0.5)
    # the recovered-but-unpublished slot is dead (logical_seq 0), so the
    # half-written 99 can never be served — entry 1 survives
    assert rd.read(0, lockstep=False) == struct.pack("<q", 1)
    # and the counter resumed from the published logical_seq: the next
    # publish is entry 2, landing in the recovered slot with an advancing
    # (even) seqlock
    b2.write(struct.pack("<q", 2), readers=[0], lockstep=False)
    assert rd.read(0, lockstep=False) == struct.pack("<q", 2)


def test_mailbox_freerun_checksum_stress(tmp_path):
    """One writer thread hammering a free-run Mailbox: every successful
    read must decode a COMPLETE published entry (all 8 checksum words
    agree and match the tag) and the latest-wins order is monotone."""
    p = str(tmp_path / "edge.bin")
    N = 1500
    wr = Mailbox.for_writer(p, 64, timeout=10.0)
    rd = Mailbox.for_reader(p, 64, timeout=10.0)

    def pay(n):
        return struct.pack("<Q", n) * 8

    t = threading.Thread(target=lambda: [
        wr.write(pay(n), tag=n, lockstep=False) for n in range(1, N + 1)])
    t.start()
    seen = 0
    while seen < N:
        got = rd.read(lockstep=False)
        if got is None:
            continue
        buf, tag = got
        words = struct.unpack("<8Q", buf)
        assert len(set(words)) == 1 and words[0] == tag, (words, tag)
        assert words[0] >= seen
        seen = words[0]
    t.join()


def test_board_freerun_checksum_stress(tmp_path):
    """One writer + two concurrent reader threads on a free-run Board:
    no torn snapshot ever escapes the seqlock re-check."""
    p = str(tmp_path / "board.bin")
    N = 800
    wr = Board.for_writer(p, 64, n_ranks=2, timeout=10.0)
    errors = []

    def pay(n):
        return struct.pack("<Q", n) * 8

    def reader(k):
        rd = Board.for_reader(p, 64, n_ranks=2, timeout=10.0)
        last = 0
        try:
            while last < N:
                buf = rd.read(k, lockstep=False)
                if buf is None:
                    continue
                words = struct.unpack("<8Q", buf)
                assert len(set(words)) == 1, words
                assert words[0] >= last
                last = words[0]
        except Exception as e:          # surface in the main thread
            errors.append(e)

    ts = [threading.Thread(target=reader, args=(k,)) for k in range(2)]
    for t in ts:
        t.start()
    for n in range(1, N + 1):
        wr.write(pay(n), readers=[0, 1], lockstep=False)
    for t in ts:
        t.join(timeout=60)
    assert not errors, errors
    assert not any(t.is_alive() for t in ts)


def test_tree_wire_format_roundtrip_and_warmup_values():
    tree = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "tag": jnp.asarray(5, jnp.int32)}
    back = bytes_to_tree(tree_to_bytes(tree), tree)
    assert_trees_equal(tree, back)
    warm = warmup_like(tree)
    # floats warm up to zero, integer leaves to -1 (the tag convention:
    # the adaptive controller treats -1 as "never deposited")
    assert float(jnp.abs(warm["w"]).max()) == 0.0
    assert int(warm["tag"]) == -1


def test_jitter_is_deterministic_and_rank_monotone():
    cfg = JitterConfig(seed=3, rank_lag_ms=10.0, noise_ms=5.0)
    a = [cfg.sleep_s(1, e) for e in range(20)]
    assert a == [cfg.sleep_s(1, e) for e in range(20)]   # replayable
    assert len(set(a)) > 1                               # noise varies
    for e in range(5):   # lag (10ms/rank) dominates the noise (<5ms)
        assert cfg.sleep_s(2, e) > cfg.sleep_s(1, e) > cfg.sleep_s(0, e)
    assert JitterConfig(rank_lag_ms=10.0).sleep_s(0, 0) == 0.0
    assert not JitterConfig().enabled
    assert cfg.enabled
    assert JitterConfig.from_dict(cfg.to_dict()) == cfg


def test_proccomm_degenerate_topologies_and_dbtree(tmp_path):
    comm = ProcComm(1, 1, rank=0, run_dir=str(tmp_path))
    tree = {"w": jnp.arange(3.0)}
    # single-rank / size-1 groups: every ring hop is the identity, exactly
    # like a size-1 VmapComm roll — no mailbox I/O at all
    assert_trees_equal(comm.recv_ring_inner(tree), tree)
    assert_trees_equal(comm.recv_ring_outer(tree), tree)
    assert_trees_equal(comm.recv_ring_all(tree), tree)
    assert_trees_equal(comm.ship_outer(tree), tree)
    assert_trees_equal(comm.pmean_all(tree), tree)
    assert int(comm.inner_index()) == 0
    with pytest.raises(NotImplementedError, match="proc backend"):
        comm.recv_hypercube(tree, 0)


def test_proccomm_ring_neighbour_layout():
    comm = ProcComm(2, 4, rank=5, run_dir="/nonexistent")   # o=1, j=1
    assert comm._peers("inner") == (6, 4)     # deposit to j+1, recv from j-1
    assert comm._peers("outer") == (1, 1)     # pod o+1 / o-1, same j (O=2)
    assert comm._peers("all") == (6, 4)
    assert int(comm.inner_index()) == 1


def test_init_run_per_rank_path_equals_sliced_stacked():
    """`workflow.init_run(rank=r)` is the worker's cheap seed derivation;
    it must be BITWISE the r-th slice of the stacked derivation every
    other driver uses (same generator copy, same data split) — this is
    the ground the proc parity pins stand on."""
    wcfg = small_wcfg(SyncConfig(mode="rma_arar_arar", h=2, staleness=2))
    data = get_problem("proxy1d").make_reference_data(jax.random.PRNGKey(3),
                                                      300)
    key = jax.random.PRNGKey(11)
    stacked, dpr = workflow.init_run(key, 4, wcfg, data)
    for r in range(4):
        st_r, d_r = workflow.init_run(key, 4, wcfg, data, rank=r)
        assert_trees_equal(jax.tree.map(lambda x: x[r], stacked), st_r,
                           err=f"rank {r} state")
        assert_trees_equal(dpr[r], d_r, err=f"rank {r} data")


def test_wcfg_json_roundtrip():
    wcfg = small_wcfg(SyncConfig(mode="rma_arar_arar", h=7, staleness=3,
                                 adaptive=True, overlap=True))
    assert wcfg_from_dict(wcfg_to_dict(wcfg)) == wcfg


def test_run_proc_rejects_resume_without_ckpt_every(tmp_path):
    """Regression (review finding): resume=True with ckpt_every=0 used to
    silently retrain from epoch 0, overwriting the results the caller
    asked to continue from — it must refuse before spawning anything."""
    wcfg = small_wcfg(SyncConfig(mode="rma_arar_arar", h=2))
    with pytest.raises(ValueError, match="resume=True needs ckpt_every"):
        run_proc(wcfg, 1, 2, 3, jnp.zeros((8, 6)), resume=True,
                 run_dir=str(tmp_path))


# ----------------------------------------------------------------------------
# integration: real 2-process jax.distributed runs


DATA = None


def _data():
    global DATA
    if DATA is None:
        DATA = get_problem("proxy1d").make_reference_data(
            jax.random.PRNGKey(7), 400)
    return DATA


def _reference_lockstep(wcfg, n_outer, n_inner, n_epochs, seed=0):
    """The bitwise twin of a zero-jitter lock-step proc run: the SAME
    jitted per-rank compute the workers execute, exchanged through the
    stacked `VmapComm` engine each epoch.  Seeding goes through the
    shared `workflow.init_run` in the STACKED layout, so the parity
    tests also pin that the workers' cheap per-rank path (`rank=r`)
    derives exactly the sliced stacked result."""
    n_ranks = n_outer * n_inner
    state, dpr = workflow.init_run(jax.random.PRNGKey(seed), n_ranks, wcfg,
                                   _data())
    comm = VmapComm(n_outer, n_inner)
    sched = workflow.make_schedule(wcfg)
    fg = jax.jit(lambda s, d: workflow.rank_grads(s, d, wcfg))
    fa = jax.jit(lambda s, g, ns: workflow.rank_apply(s, g, ns, wcfg))
    per = [jax.tree.map(lambda x: x[r], state) for r in range(n_ranks)]
    for _ in range(n_epochs):
        outs = [fg(per[r], dpr[r]) for r in range(n_ranks)]
        ns = jax.tree.map(lambda *xs: jnp.stack(xs), *[o[0] for o in outs])
        g = jax.tree.map(lambda *xs: jnp.stack(xs), *[o[1] for o in outs])
        synced, new_sync = sched.exchange(comm, g, ns["sync"],
                                          ns["epoch"][0])
        per = [fa(jax.tree.map(lambda x: x[r], ns),
                  jax.tree.map(lambda x: x[r], synced),
                  jax.tree.map(lambda x: x[r], new_sync))
               for r in range(n_ranks)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per)


@pytest.fixture(scope="module")
def proc_run_1x2():
    """One shared 3-epoch lock-step proc run (1 pod x 2 ranks, rma)."""
    wcfg = small_wcfg(SyncConfig(mode="rma_arar_arar", h=2))
    out = run_proc(wcfg, O, I, 3, _data(), seed=0, lockstep=True,
                   timeout=420)
    return wcfg, out


@pytest.mark.slow
def test_proc_lockstep_bitwise_vs_vmapcomm_engine(proc_run_1x2):
    """Acceptance pin 1: the zero-jitter lock-step ProcComm run is BITWISE
    the VmapComm exchange engine's trajectory — every transferred byte,
    mailbox slot and deposit ordering identical across real process
    boundaries."""
    wcfg, out = proc_run_1x2
    ref = _reference_lockstep(wcfg, O, I, 3)
    for k in ("gen", "gen_opt", "disc", "disc_opt", "sync", "rng", "epoch"):
        assert_trees_equal(ref[k], out["state"][k], err=f"state[{k!r}]")
    assert all(s["distributed"] for s in out["summaries"]), \
        "workers must join the jax.distributed CPU cluster"
    assert all(s["backend"] == "cpu" for s in out["summaries"]), \
        "proc workers are CPU-only whatever the caller's JAX_PLATFORMS"
    assert all(s["lockstep"] for s in out["summaries"])


@pytest.mark.slow
def test_proc_lockstep_matches_vmap_golden_at_backend_tolerance(
        proc_run_1x2):
    """Acceptance pin 1b: against the `train_vmap` StaticSchedule golden
    trajectory itself, the proc run matches at the SAME tolerance the
    repo pins vmap-vs-shard (test_workflow_dist: 1e-6) — the only
    residual is batched-vs-unbatched matmul accumulation in the local
    discriminator, which every per-rank backend shares."""
    wcfg, out = proc_run_1x2
    sv, _ = workflow.train_vmap(jax.random.PRNGKey(0), wcfg, O, I, 3,
                                _data(), chunk=1)
    worst = max(float(jnp.max(jnp.abs(a - jnp.asarray(b))))
                for a, b in zip(jax.tree.leaves(sv["gen"]),
                                jax.tree.leaves(out["state"]["gen"])))
    assert worst < 1e-6, f"proc diverged from vmap golden by {worst}"


@pytest.mark.slow
def test_proc_lockstep_adaptive_overlap_bitwise_across_pods():
    """The hard composition: 2 pods x 1 rank — outer ring, overlap ship
    mailbox (ProcComm.cond_ship's Python gate), adaptive bundled
    payload+tag deposits and the pmean bulletin board, all bitwise vs the
    VmapComm engine."""
    wcfg = small_wcfg(SyncConfig(mode="rma_arar_arar", h=2, staleness=3,
                                 adaptive=True, overlap=True))
    ref = _reference_lockstep(wcfg, 2, 1, 3)
    out = run_proc(wcfg, 2, 1, 3, _data(), seed=0, lockstep=True,
                   timeout=420)
    for k in ("gen", "gen_opt", "sync"):
        assert_trees_equal(ref[k], out["state"][k], err=f"state[{k!r}]")
    # lock-step: tags arrive but skew is exactly zero, k_eff pinned at 1
    assert all(s["max_skew_ema"] == 0.0 for s in out["summaries"])
    assert all(s["max_k_eff"] == 1 for s in out["summaries"])


@pytest.mark.slow
def test_proc_per_process_checkpoint_resume_bitwise(proc_run_1x2, tmp_path):
    """ISSUE 5 checkpoint thread: each worker saves/restores ITS OWN
    state; interrupting at epoch 2 of 3 and resuming reproduces the
    uninterrupted proc run bit for bit (the launcher negotiates the
    common resume step across ranks)."""
    wcfg, full = proc_run_1x2
    d = str(tmp_path / "run")
    run_proc(wcfg, O, I, 2, _data(), seed=0, lockstep=True, run_dir=d,
             ckpt_every=1, timeout=420)
    res = run_proc(wcfg, O, I, 3, _data(), seed=0, lockstep=True,
                   run_dir=d, ckpt_every=1, resume=True, timeout=420)
    assert res["summaries"][0]["start_epoch"] == 2
    assert_trees_equal(full["state"], res["state"],
                       err="resumed proc run diverged")
    shutil.rmtree(d, ignore_errors=True)


@pytest.mark.slow
def test_proc_freerun_jitter_measures_skew_and_widens_k_eff():
    """Acceptance pin 2: under injected deterministic jitter the 2-process
    free-running run completes end-to-end, stays finite, the adaptive
    controller observes NONZERO deposit-age skew through the mailbox
    tags, and k_eff moves off 1 — the asynchrony the SPMD simulators can
    never produce (they hold k_eff at 1 forever, see test_schedule)."""
    wcfg = small_wcfg(SyncConfig(mode="rma_arar_arar", h=1000, staleness=4,
                                 adaptive=True))
    out = run_proc(wcfg, O, I, 30, _data(), seed=0, lockstep=False,
                   jitter=JitterConfig(rank_lag_ms=60.0), timeout=420)
    assert all(s["distributed"] for s in out["summaries"])
    assert all(not s["lockstep"] for s in out["summaries"])
    for leaf in jax.tree.leaves(out["state"]):
        assert np.isfinite(np.asarray(leaf, np.float64)).all()
    h = out["history"]
    assert h["d_loss"].shape == (30, R) and np.isfinite(h["d_loss"]).all()
    assert max(s["max_skew_ema"] for s in out["summaries"]) > 0.0
    assert max(s["max_k_eff"] for s in out["summaries"]) > 1
    # the controller stays inside its hard bounds under real skew too
    assert h["k_eff"].min() >= 1 and h["k_eff"].max() <= 4
