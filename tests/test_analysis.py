"""Tier-1 tests for the static-analysis lane (ISSUE 6).

Three layers:

  * the protocol MODEL CHECKER (`repro.analysis`) exhaustively passes
    every safety invariant at the bounded model sizes (>= 2 entries,
    >= 2 readers for the Board), and — the teeth test — demonstrably
    FAILS when either ISSUE 6 crash-recovery bug is re-introduced into
    the abstract model;
  * the FAULT-INJECTION harness drives the real `runtime/mailbox.py`
    mmap code through the adversarial interleavings the explorer found
    (reader paused mid-snapshot across writer overwrites and across a
    crash/re-attach) and pins that the shipped code survives them;
  * the REPO-INVARIANT LINTER (`scripts/repro_lint.py`) runs clean on
    the repo — which wires the `--analysis` lane into the default full
    pytest gate — and each of its five checks is pinned against a
    synthetic violation so none can silently no-op.
"""
import importlib.util
import os
import struct
import threading

from repro.analysis import (ANCHORS, InterleavingDriver, barrier_model,
                            board_model, crashed_board_state, explore,
                            line_of, mailbox_freerun_model,
                            mailbox_lockstep_model)
from repro.runtime import mailbox as mbx_mod
from repro.runtime.mailbox import (_MBX_HDR, _SLOT_HDR, Board, Mailbox,
                                   field_offsets)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_lint():
    spec = importlib.util.spec_from_file_location(
        "repro_lint", os.path.join(ROOT, "scripts", "repro_lint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


lint = _load_lint()
RING_SRC = open(os.path.join(ROOT, "src", "repro", "core", "ring.py")).read()


def _assert_clean(res, what):
    assert res.complete, f"{what}: state space truncated ({res.states})"
    assert not res.violations, f"{what}:\n{res.report()}"
    assert not res.deadlocks, f"{what}:\n{res.report()}"
    assert res.completion_reached, f"{what}: completion unreachable"


# ---------------------------------------------------------------------------
# model checker: every protocol invariant passes exhaustively


def test_mailbox_freerun_exhaustive():
    res = explore(*mailbox_freerun_model(n_entries=2, n_readers=1))
    _assert_clean(res, "mailbox free-run (2 entries)")
    # breadth: two concurrent snapshot readers on one window
    res2 = explore(*mailbox_freerun_model(n_entries=2, n_readers=2,
                                          attempts=1, retries=1))
    _assert_clean(res2, "mailbox free-run (2 readers)")


def test_mailbox_lockstep_exact_and_deadlock_free():
    res = explore(*mailbox_lockstep_model(n_entries=3))
    _assert_clean(res, "mailbox lock-step (3 entries)")


def test_mailbox_resume_fixed_model_passes():
    res = explore(*mailbox_freerun_model(n_entries=2, resume="fixed"))
    _assert_clean(res, "mailbox free-run crash + fixed resume")


def test_board_lockstep_exhaustive():
    res = explore(*board_model(n_entries=4, n_readers=2, lockstep=True))
    _assert_clean(res, "board lock-step (4 entries, 2 readers)")


def test_board_freerun_exhaustive():
    res = explore(*board_model(n_entries=3, n_readers=2, lockstep=False))
    _assert_clean(res, "board free-run (3 entries, 2 readers)")


def test_board_crashed_attach_recover_passes():
    res = explore(*board_model(n_entries=2, n_readers=2, lockstep=False,
                               crashed_slot=crashed_board_state(),
                               attach_fix=True))
    _assert_clean(res, "board crash + fixed re-attach")


def test_barrier_deadlock_free():
    res = explore(*barrier_model(n_ranks=3, rounds=2))
    _assert_clean(res, "barrier (3 ranks, 2 rounds)")


def test_freerun_writers_never_block():
    # structural statement of "free-run writers never wait": no free-run
    # writer step carries a guard, in either protocol's model
    for shared, procs in (mailbox_freerun_model(n_entries=2),
                          board_model(n_entries=3, lockstep=False)):
        writer = procs[0]
        assert all(s.guard is None for s in writer.steps), \
            f"{writer.name} has blocking steps"


# ---------------------------------------------------------------------------
# model checker teeth: re-introducing either ISSUE 6 bug must fail


def test_resume_bug_reintroduced_is_caught():
    # satellite 1: re-attached writer restarts _seq at 0 -> the seqlock
    # replays old values and a paused reader accepts a torn ABA snapshot
    res = explore(*mailbox_freerun_model(n_entries=1, resume="bug"))
    assert res.violations, "checker lost its teeth: resume bug not found"
    assert any("torn mailbox read" in msg for msg, _ in res.violations)
    # the adversarial schedule is replayable: cross-linked to real lines
    msg, trace = res.violations[0]
    assert any("mailbox.py:" in step for step in trace)


def test_odd_lock_bug_reintroduced_is_caught():
    # satellite 2: blind `lock + 1` over a crashed writer's odd slot lock
    # word makes the slot read as published mid-write
    res = explore(*board_model(n_entries=2, n_readers=2, lockstep=False,
                               crashed_slot=crashed_board_state(),
                               attach_fix=False))
    assert res.violations, "checker lost its teeth: odd-lock bug not found"
    assert any("torn board read" in msg for msg, _ in res.violations)


# ---------------------------------------------------------------------------
# cross-links and layout ground truth


def test_step_line_anchors_resolve_uniquely():
    # every abstract step's claimed concrete line must still exist in
    # runtime/mailbox.py — a refactor that moves the protocol breaks
    # this loudly instead of letting the model drift from the code
    for kind in ANCHORS:
        ln = line_of(kind)
        assert ln >= 1, kind


def test_struct_offsets_match_derivation():
    assert field_offsets(_MBX_HDR) == (0, 8, 16, 24)
    assert field_offsets(_SLOT_HDR) == (0, 8, 16)


def test_window_layout_matches_model_across_itemsizes(tmp_path):
    # ISSUE 7: window/slot byte sizes derive from the payload dtype's
    # itemsize.  Pin the REAL constructors against the independent
    # layout model at bf16 (2), fp32 (4) and fp64 (8) itemsizes, so the
    # checker's line anchors keep covering the resized windows.
    import numpy as np
    from repro.analysis import window_layout_model
    from repro.runtime.mailbox import payload_nbytes
    for dtype, itemsize in (("bfloat16", 2), ("float32", 4),
                            ("float64", 8)):
        n_elems = 7
        nbytes = payload_nbytes(n_elems, dtype)
        model = window_layout_model(n_elems, itemsize, n_ranks=3)
        assert nbytes == model["nbytes"] == n_elems * itemsize
        mbx = Mailbox(str(tmp_path / f"m_{itemsize}.bin"), nbytes,
                      timeout=1.0)
        assert mbx._size == model["mailbox_size"]
        brd = Board(str(tmp_path / f"b_{itemsize}.bin"), nbytes,
                    n_ranks=3, timeout=1.0)
        assert brd._stride == model["board_stride"]
        assert brd._acks_off == model["board_acks_off"]
        assert brd._size == model["board_size"]
    # bfloat16 itemsize really is 2 on this interpreter (ml_dtypes)
    import ml_dtypes  # noqa: F401
    assert np.dtype("bfloat16").itemsize == 2


def test_bf16_mailbox_roundtrip_bit_exact(tmp_path):
    # a bf16 payload ships through a dtype-sized window and comes back
    # BIT-exact — the wire must never widen or re-round the halves
    import numpy as np
    from repro.runtime.mailbox import payload_nbytes
    import ml_dtypes  # noqa: F401
    bf16 = np.dtype("bfloat16")
    vals = np.array([1.0, -2.5, 3.0e-3, 65280.0, -0.1875, 7.0, 0.0,
                     1.5e-2], dtype=np.float32).astype(bf16)
    payload = vals.tobytes()
    assert len(payload) == payload_nbytes(vals.size, bf16)
    p = str(tmp_path / "bf16.bin")
    wr = Mailbox.for_writer(p, len(payload), timeout=5.0)
    rd = Mailbox.for_reader(p, len(payload), timeout=5.0)
    wr.write(payload, tag=3, lockstep=True)
    out, tag = rd.read(lockstep=True)
    assert tag == 3
    assert out == payload                     # byte-for-byte
    back = np.frombuffer(out, dtype=bf16)
    assert back.tobytes() == vals.tobytes()   # and bit-exact as bf16
    # board path too: depth-2 slots sized from the same derivation
    bp = str(tmp_path / "bf16_board.bin")
    bwr = Board.for_writer(bp, len(payload), n_ranks=1, timeout=5.0)
    brd = Board.for_reader(bp, len(payload), n_ranks=1, timeout=5.0)
    bwr.write(payload, readers=[0], lockstep=True)
    buf = brd.read(0, lockstep=True)
    assert buf == payload


# ---------------------------------------------------------------------------
# fault injection: the real mmap code under adversarial interleavings


def test_fault_reader_paused_across_overwrite_retries(tmp_path):
    # explorer-found window: reader takes seq, pauses before the payload
    # copy, writer overwrites the whole entry; the seqlock re-check must
    # force a retry and the reader must return the NEW complete payload
    p = str(tmp_path / "edge.bin")
    wr = Mailbox.for_writer(p, 8, timeout=5.0)
    rd = Mailbox.for_reader(p, 8, timeout=5.0)
    wr.write(struct.pack("<d", 1.0), tag=1, lockstep=False)
    got = []
    with InterleavingDriver() as drv:
        gate = drv.gate("mbx.read.snap")
        t = threading.Thread(
            target=lambda: got.append(rd.read(lockstep=False)))
        t.start()
        gate.wait_reached()           # reader mid-snapshot of entry 1
        wr.write(struct.pack("<d", 2.0), tag=2, lockstep=False)
        gate.release()
        t.join(timeout=10)
    assert got == [(struct.pack("<d", 2.0), 2)]


def test_fault_resume_aba_is_defeated(tmp_path):
    # the satellite-1 adversarial schedule on real code: reader snapshots
    # seq, pauses; the writer CRASHES and RE-ATTACHES, then publishes new
    # bytes.  With the resume fix the new publish moves the seqlock
    # strictly forward, the paused reader's re-check fails, and it
    # retries into the new complete payload — never the torn ABA mix.
    p = str(tmp_path / "edge.bin")
    wr = Mailbox.for_writer(p, 8, timeout=5.0)
    wr.write(struct.pack("<d", 1.0), tag=1, lockstep=False)
    rd = Mailbox.for_reader(p, 8, timeout=5.0)
    got = []
    with InterleavingDriver() as drv:
        gate = drv.gate("mbx.read.snap")
        t = threading.Thread(
            target=lambda: got.append(rd.read(lockstep=False)))
        t.start()
        gate.wait_reached()           # reader holds s1 == 2 (entry 1)
        wr2 = Mailbox.for_writer(p, 8, timeout=5.0)   # crash + re-attach
        wr2.write(struct.pack("<d", 2.0), tag=2, lockstep=False)
        gate.release()
        t.join(timeout=10)
    assert got == [(struct.pack("<d", 2.0), 2)]
    # the resumed seqlock continued (entry 2 -> header 4), never replayed
    assert wr2._get(mbx_mod._MBX_OFF_WSEQ) == 4


def test_fault_board_snapshot_window_discards_torn(tmp_path):
    # reader pauses inside a slot snapshot; the writer laps that slot
    # (entries 2 and 4 share slot 0); the re-check must discard the torn
    # slot and the read must fall back to a complete published entry
    p = str(tmp_path / "board.bin")
    wr = Board.for_writer(p, 8, n_ranks=2, timeout=5.0)
    rd = Board.for_reader(p, 8, n_ranks=2, timeout=5.0)
    for n in (1, 2):
        wr.write(struct.pack("<q", n), readers=[1], lockstep=False)
    got = []
    with InterleavingDriver() as drv:
        gate = drv.gate("board.read.snap")    # traps the slot-0 snapshot
        t = threading.Thread(
            target=lambda: got.append(rd.read(1, lockstep=False)))
        t.start()
        gate.wait_reached()
        for n in (3, 4):                      # 4 overwrites slot 0
            wr.write(struct.pack("<q", n), readers=[1], lockstep=False)
        gate.release()
        t.join(timeout=10)
    (buf,) = got
    assert buf is not None
    assert struct.unpack("<q", buf)[0] in (3, 4)   # complete, published


# ---------------------------------------------------------------------------
# repo-invariant linter: clean on the repo, and every check has teeth


def test_repro_lint_repo_clean():
    problems = lint.lint_sources(lint.repo_sources())
    assert problems == [], "\n".join(problems)


def test_repro_lint_repo_clean_with_test_corpus():
    """The armed form of check 8 (agreement tests required) is what
    scripts/check.sh runs — it must hold on the real tests/ corpus."""
    problems = lint.lint_sources(lint.repo_sources(), lint.test_corpus())
    assert problems == [], "\n".join(problems)


KERNEL_SRC = (
    "from jax.experimental import pallas as pl\n"
    "def fancy_op(x):\n"
    "    return pl.pallas_call(_k, out_shape=None)(x)\n"
    "def _private_helper(x):\n"
    "    return pl.pallas_call(_k, out_shape=None)(x)\n")


def test_lint_kernel_oracle_missing_ref_flagged():
    problems = lint.lint_sources({"core/ring.py": RING_SRC,
                                  "kernels/fancy.py": KERNEL_SRC,
                                  "kernels/ref.py": "def other_ref(x):\n"
                                                    "    return x\n"})
    assert any("`fancy_op` has no jnp oracle" in p for p in problems), \
        problems
    # private helpers launching pallas_call are not entry points
    assert not any("_private_helper" in p for p in problems), problems


def test_lint_kernel_oracle_agreement_test_required_when_armed():
    srcs = {"core/ring.py": RING_SRC,
            "kernels/fancy.py": KERNEL_SRC,
            "kernels/ref.py": "def fancy_op_ref(x):\n    return x\n"}
    # unarmed (no test corpus): oracle registration alone satisfies it
    assert lint.lint_sources(srcs) == []
    # armed with a corpus that never compares the pair: flagged
    problems = lint.lint_sources(srcs, {"test_other.py": "x = 1\n"})
    assert any("no agreement test" in p and "fancy_op" in p
               for p in problems), problems
    # armed with a genuine agreement test: clean
    good = {"test_kernels.py":
            "y = fancy_op(x)\nr = ref.fancy_op_ref(x)\n"}
    assert lint.lint_sources(srcs, good) == []


def test_lint_comm_surface_missing_and_drift():
    bad = (
        "from ..core.ring import Comm\n"
        "class TcpComm(Comm):\n"
        "    def recv_ring_all(self, tree): return tree\n"
        "    def recv_ring_inner(self, tree): return tree\n"
        "    def recv_ring_outer(self, payload): return payload\n"
        "    def pmean_all(self, tree): return tree\n"
        "    def recv_hypercube(self, tree, stage): return tree\n"
        "    def inner_index(self, like): return 0\n"
        "    def mask_where(self, cond_scalar, a, b): return a\n")
    problems = lint.lint_sources({"core/ring.py": RING_SRC,
                                  "runtime/tcpcomm.py": bad})
    assert any("does not implement Comm.ship_outer" in p
               for p in problems), problems
    assert any("recv_ring_outer(payload) drifts" in p
               for p in problems), problems
    # suffix refinement (cond -> cond_scalar) is conformant, not drift
    assert not any("mask_where" in p for p in problems), problems


def test_lint_comm_surface_repo_backends_conform():
    # the real conformance statement: all three backends implement the
    # full declared surface (the coming TCP backend inherits this gate)
    srcs = {rel: src for rel, src in lint.repo_sources().items()
            if rel in ("core/ring.py", "runtime/proccomm.py")}
    assert lint.lint_sources(srcs) == []


def test_lint_donation_reuse_flagged_and_rebind_allowed():
    bad = (
        "import jax\n"
        "def make_fn(f):\n"
        "    return jax.jit(f, donate_argnums=(0,))\n"
        "def driver(state, data):\n"
        "    step = make_fn(lambda s, d: s)\n"
        "    new = step(state, data)\n"
        "    return state\n")
    problems = lint.lint_sources({"core/ring.py": RING_SRC,
                                  "core/bad.py": bad})
    assert any("donated buffer `state`" in p for p in problems), problems
    good = bad.replace("new = step(state, data)",
                       "state = step(state, data)").replace(
        "return state\n", "return state, None\n")
    assert lint.lint_sources({"core/ring.py": RING_SRC,
                              "core/good.py": good}) == []


def test_lint_host_calls_in_traced_core():
    bad = (
        "import os, time\n"
        "import numpy as np\n"
        "def f(x):\n"
        "    time.sleep(0)\n"
        "    np.random.seed(0)\n"
        "    print(x)\n"
        "    os.getcwd()\n"
        "    os.environ.get('JAX_COMPILATION_CACHE_DIR')\n"
        "    return x\n")
    problems = lint.lint_sources({"core/ring.py": RING_SRC,
                                  "core/gan.py": bad})
    assert len([p for p in problems if "core/gan.py" in p]) == 4, problems
    assert not any("environ" in p for p in problems)


def test_lint_traced_branch():
    bad = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    if jnp.any(x > 0):\n"
        "        return x\n"
        "    while jax.lax.lt(x, 1):\n"
        "        x = x + 1\n"
        "    return x if cfg.fused else -x\n")   # static config: allowed
    problems = lint.lint_sources({"core/ring.py": RING_SRC,
                                  "core/sync.py": bad})
    assert len([p for p in problems
                if "branch on traced value" in p]) == 2, problems


def test_lint_struct_offsets():
    bad = (
        "import struct\n"
        "_U64 = struct.Struct('<Q')\n"
        "class M:\n"
        "    def f(self, mm):\n"
        "        self._put(0, 1)\n"
        "        struct.pack_into('<q', mm, 16, 2)\n"
        "        _U64.unpack_from(mm, 24)\n")
    problems = lint.lint_sources({"core/ring.py": RING_SRC,
                                  "runtime/mailbox.py": bad})
    offs = sorted(int(p.split("offset ")[1].split(" ")[0])
                  for p in problems)
    assert offs == [0, 16, 24], problems


def test_lint_payload_dtype_discipline():
    bad = (
        "import jax.numpy as jnp\n"
        "def flatten(self, tree):\n"
        "    return x.astype(jnp.float32)\n"       # silent upcast: flagged
        "def empty(self, shape):\n"
        "    return jnp.zeros(shape, dtype='bfloat16')\n"  # re-hardcoded
        "def payload_dtype_of(p):\n"               # blessed registry site
        "    return jnp.dtype('float32')\n"
        "def unflatten(self, flat, g):\n"
        "    return flat.astype(g.dtype)\n")       # leaf-derived: allowed
    problems = lint.lint_sources({"core/ring.py": RING_SRC,
                                  "core/sync.py": bad})
    dt = [p for p in problems if "hard-coded float dtype" in p]
    assert len(dt) == 2, problems
    assert any("`float32`" in p for p in dt) and \
        any("`bfloat16`" in p for p in dt), dt


def test_lint_fusionspec_build_kwarg():
    bad = (
        "def make_schedule(wcfg):\n"
        "    return sync_lib.FusionSpec.build(example, mask)\n")
    good = bad.replace(
        "(example, mask)", "(example, mask, payload_dtype=dt)")
    problems = lint.lint_sources({"core/ring.py": RING_SRC,
                                  "core/workflow.py": bad})
    assert any("without the payload_dtype= keyword" in p
               for p in problems), problems
    assert lint.lint_sources({"core/ring.py": RING_SRC,
                              "core/workflow.py": good}) == []


def test_lint_serving_jit_discipline():
    """Check 7: jax.jit on the serving surface (outside serving/cache.py)
    is flagged; the blessed cache module and non-serving modules are not."""
    bad = ("import jax\n"
           "def make_step(fn):\n"
           "    return jax.jit(fn)\n")
    for rel in ("serving/engine.py", "serving/service.py",
                "launch/serve.py"):
        problems = lint.lint_sources({"core/ring.py": RING_SRC, rel: bad})
        assert any("warm executable pool" in p and rel in p
                   for p in problems), (rel, problems)
    # blessed: the compile-cache module itself, and modules off the surface
    for rel in ("serving/cache.py", "core/workflow.py", "launch/train.py"):
        assert lint.lint_sources({"core/ring.py": RING_SRC,
                                  rel: bad}) == [], rel
    # routing through jit_compile satisfies the check
    good = ("from .cache import jit_compile\n"
            "def make_step(fn):\n"
            "    return jit_compile(fn)\n")
    assert lint.lint_sources({"core/ring.py": RING_SRC,
                              "serving/engine.py": good}) == []
