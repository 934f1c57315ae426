"""Chunked ring exchange (ISSUE 9, `SyncConfig.ring_chunking`).

The fused flat payload crosses the ring as ceil(bytes/chunk) last-axis
segments instead of one array; storage (mailboxes, checkpoints) stays
flat.  The contract pinned here, on all three comm backends:

  * fp32 chunked ≡ fp32 unchunked, BITWISE — at the schedule level on
    `VmapComm` (every ring mode x depth-k x overlap x adaptive), at the
    exchange level inside `shard_map` (full-trajectory shard parity is
    not the claim: adding concat/slice to the epoch graph re-fuses the
    XLA:CPU executable and costs ~1 ulp in the purely-local Adam math,
    the same cross-compilation artifact test_workflow_dist tolerates at
    1e-6), and across REAL process boundaries on `ProcComm` (per-window
    mmap channels, lock-step);
  * `ring_chunking=0` (the default) keeps the bare flat array — no
    1-tuple wrapper — so the historical programs and mailbox file
    layouts are untouched;
  * segment geometry is computed in payload-dtype ELEMENTS, so bf16
    fits twice the elements per segment.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import workflow
from repro.core.ring import VmapComm
from repro.core.sync import FusionSpec, SyncConfig
from repro.core.workflow import WorkflowConfig
from repro.problems import get_problem

CHUNK = 65536           # 16384 fp32 elements; proxy1d payload -> 4 segments


def small_wcfg(sync):
    return WorkflowConfig(problem="proxy1d", sync=sync,
                          n_param_samples=8, events_per_sample=4)


def assert_trees_equal(a, b, err=""):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb, f"{err}: tree structure {ta} != {tb}"
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=err)


# ----------------------------------------------------------------------------
# FusionSpec segment geometry


def _spec(chunk_bytes, dtype=None, n=100):
    example = {"w": jnp.zeros((n,)), "b": jnp.zeros((3,))}
    return FusionSpec.build(example, {"w": True, "b": False},
                            payload_dtype=dtype, chunk_bytes=chunk_bytes)


def test_segment_geometry_unchunked_and_oversized():
    # chunk 0 and chunk >= payload both degenerate to one segment
    for cb in (0, 400, 4096):
        s = _spec(cb)
        assert s.n_segments == 1
        assert s.segment_bounds() == ((0, 100),)


def test_segment_geometry_splits_in_elements_and_covers():
    s = _spec(128)                       # 32 fp32 elements per segment
    assert s.n_segments == 4             # ceil(100/32)
    bounds = s.segment_bounds()
    assert bounds[0] == (0, 32) and bounds[-1] == (96, 100)
    # contiguous, exhaustive cover
    assert all(b0 == a1 for (_, a1), (b0, _) in zip(bounds, bounds[1:]))


def test_segment_geometry_counts_payload_dtype_elements():
    # bf16 halves the bytes/element: twice the elements fit per segment
    assert _spec(128, jnp.bfloat16).n_segments == 2   # 64 elems/segment
    assert _spec(128, jnp.float32).n_segments == 4


def test_split_join_roundtrip_stacked_and_flat():
    s = _spec(128)
    for shape in ((100,), (5, 100)):     # per-rank and stacked layouts
        v = jax.random.normal(jax.random.PRNGKey(0), shape)
        segs = s.split_payload(v)
        assert len(segs) == s.n_segments
        assert sum(x.shape[-1] for x in segs) == 100
        np.testing.assert_array_equal(np.asarray(s.join_payload(segs)),
                                      np.asarray(v))


def test_config_validation():
    with pytest.raises(ValueError):      # a byte count, not a flag
        SyncConfig(mode="rma_arar_arar", fuse_tensors=True, ring_chunking=-1)
    with pytest.raises(ValueError):      # chunks the FUSED payload only
        SyncConfig(mode="rma_arar_arar", fuse_tensors=False,
                   ring_chunking=CHUNK)
    with pytest.raises(ValueError):      # allreduce has no ring payload
        SyncConfig(mode="allreduce", fuse_tensors=True, ring_chunking=CHUNK)
    SyncConfig(mode="rma_arar_arar", fuse_tensors=True,
               ring_chunking=CHUNK)      # fine
    SyncConfig(mode="dbtree", fuse_tensors=True, ring_chunking=CHUNK)


# ----------------------------------------------------------------------------
# schedule-level bitwise parity on VmapComm (output AND sync-state)

COMBOS = {
    "conv_arar": dict(mode="conv_arar"),
    "arar_arar": dict(mode="arar_arar"),
    "dbtree": dict(mode="dbtree"),
    "rma_k2": dict(mode="rma_arar_arar", staleness=2),
    "rma_overlap": dict(mode="rma_arar_arar", overlap=True),
    "rma_adaptive_k3": dict(mode="rma_arar_arar", staleness=3,
                            adaptive=True),
    "rma_adaptive_overlap_k3": dict(mode="rma_arar_arar", staleness=3,
                                    adaptive=True, overlap=True),
}


@pytest.mark.parametrize("label", sorted(COMBOS))
def test_chunked_bitwise_on_vmap_schedule(label):
    """fp32 chunked (4 segments) ≡ unchunked, bitwise, for 3 epochs of
    every schedule/mode combination — outputs and every sync-state leaf
    (mailboxes, overlap buffers, adaptive controller)."""
    R, O, I = 8, 2, 4
    comm = VmapComm(O, I)
    runs = {}
    for chunk in (0, CHUNK):
        wcfg = small_wcfg(SyncConfig(h=2, fuse_tensors=True,
                                     ring_chunking=chunk, **COMBOS[label]))
        sched = workflow.make_schedule(wcfg)
        if chunk:
            assert sched.spec.n_segments > 1, \
                "test payload must actually split"
        st = sched.init_state(R)
        outs = []
        for e in range(3):
            g = jax.tree.map(
                lambda x: jax.random.normal(jax.random.PRNGKey(17 * e),
                                            x.shape, x.dtype),
                sched._grads_example(R))
            o, st = sched.exchange(comm, g, st, jnp.asarray(e))
            outs.append(o)
        runs[chunk] = (outs, st)
    for e, (a, b) in enumerate(zip(runs[0][0], runs[CHUNK][0])):
        assert_trees_equal(a, b, err=f"{label}: output at epoch {e}")
    # storage stays flat: identical tree structure, identical bytes
    assert_trees_equal(runs[0][1], runs[CHUNK][1],
                       err=f"{label}: sync state after 3 epochs")


# ----------------------------------------------------------------------------
# exchange-level bitwise parity inside shard_map (subprocess: 8 devices)

_SHARD_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.core import workflow
from repro.core.sync import SyncConfig
from repro.core.workflow import WorkflowConfig
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P

mesh = make_mesh((2, 4), ("pod", "data"))
R, CHUNK = 8, 65536
combos = {
    "conv_arar": dict(mode="conv_arar"),
    "arar_arar": dict(mode="arar_arar"),
    "rma_k2": dict(mode="rma_arar_arar", staleness=2),
    "rma_overlap": dict(mode="rma_arar_arar", overlap=True),
    "rma_adaptive_k3": dict(mode="rma_arar_arar", staleness=3,
                            adaptive=True),
}
out = {}
for label, kw in combos.items():
    runs = {}
    for chunk in (0, CHUNK):
        wcfg = WorkflowConfig(
            problem="proxy1d", n_param_samples=8, events_per_sample=4,
            sync=SyncConfig(h=2, fuse_tensors=True, ring_chunking=chunk,
                            **kw))
        sched = workflow.make_schedule(wcfg)
        from repro.core.ring import ShardComm
        comm = ShardComm(2, 4, "pod", "data")
        spec = P(("pod", "data"))

        def body(g, st, e):
            # inside shard_map every leaf keeps a leading local axis of 1
            sq = lambda t: jax.tree.map(lambda x: x[0], t)
            ex = lambda t: jax.tree.map(lambda x: x[None], t)
            o, s = sched.exchange(comm, sq(g), sq(st), e[0])
            return ex(o), ex(s)

        fn = jax.jit(jax.shard_map(body, mesh=mesh,
                                   in_specs=(spec, spec, spec),
                                   out_specs=(spec, spec), check_vma=False))
        st = sched.init_state(R)
        outs = []
        for e in range(3):
            g = jax.tree.map(
                lambda x: jax.random.normal(jax.random.PRNGKey(17 * e),
                                            x.shape, x.dtype),
                sched._grads_example(R))
            ev = jnp.full((R,), e, jnp.int32)
            o, st = fn(g, st, ev)
            outs.append(jax.device_get(o))
        runs[chunk] = (outs, jax.device_get(st))
    diff = 0.0
    for a, b in zip(jax.tree.leaves(runs[0]), jax.tree.leaves(runs[CHUNK])):
        diff = max(diff, float(jnp.max(jnp.abs(
            jnp.asarray(a, jnp.float32) - jnp.asarray(b, jnp.float32)))))
    out[label] = diff
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_chunked_bitwise_on_shard_exchange():
    """On the mesh backend the claim is pinned at the exchange itself:
    chunked and unchunked `ppermute` pipelines move identical bytes
    (diff == 0.0 exactly, not a tolerance)."""
    import json
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run([sys.executable, "-c", _SHARD_CHILD], cwd=repo,
                         capture_output=True, text=True, timeout=900)
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, f"child failed:\n{res.stderr[-3000:]}"
    diffs = json.loads(line[0][len("RESULT "):])
    assert set(diffs) == {"conv_arar", "arar_arar", "rma_k2",
                          "rma_overlap", "rma_adaptive_k3"}
    for label, d in diffs.items():
        assert d == 0.0, f"{label}: chunked shard exchange diverged by {d}"


# ----------------------------------------------------------------------------
# real process boundaries: per-window mmap channels on ProcComm


@pytest.mark.slow
def test_chunked_bitwise_on_proc_lockstep():
    """A lock-step 2-process run with ring_chunking (per-window mailbox
    channels, rendezvoused per window) reproduces the unchunked run's
    full state bit for bit."""
    from repro.runtime.launch import run_proc
    data = get_problem("proxy1d").make_reference_data(
        jax.random.PRNGKey(7), 400)
    states = {}
    for chunk in (0, CHUNK):
        wcfg = small_wcfg(SyncConfig(mode="rma_arar_arar", h=2,
                                     fuse_tensors=True,
                                     ring_chunking=chunk))
        out = run_proc(wcfg, 1, 2, 3, data, seed=0, lockstep=True,
                       timeout=420)
        assert all(s["lockstep"] for s in out["summaries"])
        states[chunk] = out["state"]
    for k in ("gen", "gen_opt", "disc", "disc_opt", "sync", "rng", "epoch"):
        assert_trees_equal(states[0][k], states[CHUNK][k],
                           err=f"proc state[{k!r}]")


@pytest.mark.slow
def test_imaging_trains_on_proc_with_chunked_ring():
    """Acceptance: the imaging problem is trainable end-to-end on the
    proc backend, with its megabyte payload actually segmented (3 windows
    at the default 512 KiB chunk)."""
    from repro.configs import sagips_gan
    from repro.runtime.launch import run_proc
    base = WorkflowConfig(
        sync=SyncConfig(mode="rma_arar_arar", h=2, fuse_tensors=True,
                        ring_chunking=524288),
        n_param_samples=8, events_per_sample=4)
    wcfg = sagips_gan.for_problem("imaging", base)
    spec = workflow.make_schedule(wcfg).spec
    assert spec.n_segments >= 2, "imaging payload must exceed one segment"
    data = get_problem("imaging").make_reference_data(
        jax.random.PRNGKey(3), 256)
    out = run_proc(wcfg, 1, 2, 2, data, seed=0, lockstep=True, timeout=600)
    for leaf in jax.tree.leaves(out["state"]["gen"]):
        assert np.all(np.isfinite(np.asarray(leaf)))
    assert all(s["distributed"] for s in out["summaries"])
