"""Distributed-equivalence tests: the vmap rank simulator and the shard_map
mesh backend must produce identical training trajectories for every mode.
Runs in a subprocess with 8 forced host devices (jax pins the device count
at first init, so the main pytest process keeps its single device).  The
bootstrap draw's vmapped-against-per-rank tests run in process."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core.workflow import _bootstrap

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.core import pipeline, workflow
from repro.core.workflow import WorkflowConfig
from repro.core.sync import SyncConfig
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("pod", "data"))
data = pipeline.make_reference_data(jax.random.PRNGKey(42), 1000)
out = {}
combos = json.loads(sys.argv[1])
for label, (mode, fuse, k, overlap, adaptive, de) in combos.items():
    wcfg = WorkflowConfig(sync=SyncConfig(mode=mode, h=2, fuse_tensors=fuse,
                                          staleness=k, overlap=overlap,
                                          adaptive=adaptive),
                          n_param_samples=8, events_per_sample=4,
                          disc_every=de)
    R = 8
    state_v = workflow.init_state(jax.random.PRNGKey(0), R, wcfg)
    sub_keys = jax.random.split(jax.random.PRNGKey(9), R)
    dpr = jnp.stack([jnp.take(data, jax.random.permutation(k, 1000)[:500], axis=0)
                     for k in sub_keys])
    # both epoch fns donate their state arg: shard a copy out BEFORE the
    # vmap loop consumes state_v's buffers
    ef_s, shardings = workflow.make_epoch_fn_shard(mesh, wcfg)
    ss = jax.device_put(state_v, shardings)
    ds = jax.device_put(dpr, shardings)
    ef_v = workflow.make_epoch_fn_vmap(2, 4, wcfg)
    sv = state_v
    for _ in range(3):
        sv, _ = ef_v(sv, dpr)
    for _ in range(3):
        ss, _ = ef_s(ss, ds)
    diff = max(float(jnp.max(jnp.abs(a - b)))
               for a, b in zip(jax.tree.leaves(sv["gen"]),
                               jax.tree.leaves(jax.device_get(ss["gen"]))))
    out[label] = diff
print("RESULT " + json.dumps(out))
"""

# label: (mode, fuse_tensors, staleness, overlap, adaptive, disc_every) —
# default fused, plus explicit unfused, depth-k mailbox, overlapped
# pod-boundary and adaptive-staleness variants so the fused engine's
# cross-backend equivalence is pinned on every code path and every schedule
COMBOS = {
    "allreduce": ("allreduce", True, 1, False, False, 1),
    "conv_arar": ("conv_arar", True, 1, False, False, 1),
    "arar_arar": ("arar_arar", True, 1, False, False, 1),
    "rma_arar_arar": ("rma_arar_arar", True, 1, False, False, 1),
    "ensemble": ("ensemble", True, 1, False, False, 1),
    "dbtree": ("dbtree", True, 1, False, False, 1),
    "arar_arar_unfused": ("arar_arar", False, 1, False, False, 1),
    "rma_arar_arar_unfused": ("rma_arar_arar", False, 1, False, False, 1),
    "rma_arar_arar_k2": ("rma_arar_arar", True, 2, False, False, 1),
    "arar_arar_overlap": ("arar_arar", True, 1, True, False, 1),
    "rma_arar_arar_overlap_k2": ("rma_arar_arar", True, 2, True, False, 1),
    "rma_arar_arar_adaptive_k3": ("rma_arar_arar", True, 3, False, True, 1),
    "rma_adaptive_overlap_k2": ("rma_arar_arar", True, 2, True, True, 1),
}


def _backend_diffs(combos):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run([sys.executable, "-c", _CHILD, json.dumps(combos)],
                         cwd=repo, capture_output=True, text=True,
                         timeout=900)
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, f"child failed:\n{res.stderr[-3000:]}"
    return json.loads(line[0][len("RESULT "):])


@pytest.mark.slow
def test_vmap_and_shard_backends_identical():
    for mode, d in _backend_diffs(COMBOS).items():
        assert d < 1e-6, f"{mode}: backends diverged by {d}"


def test_vmap_and_shard_identical_with_disc_cadence():
    """disc_every=2 hangs the bootstrap draw in a `lax.cond` branch around
    the vmapped ranks: its batched rule runs there, and the trajectory
    still equals the shard backend's."""
    d, = _backend_diffs(
        {"rma_disc2": ["rma_arar_arar", True, 1, False, False, 2]}).values()
    assert d < 1e-6, f"backends diverged by {d}"


@pytest.mark.parametrize("obs_dim", [2, 15])
@pytest.mark.parametrize("batched", ["data_and_idx", "data", "idx"])
def test_vmapped_bootstrap_bitwise_per_rank(obs_dim, batched):
    """The vmapped bootstrap draw equals each rank's own draw, stacked,
    bit for bit, whichever of the table and the indices is batched (the
    indices are batched with the key)."""
    R, rows, n_draw = 4, 257, 300
    keys = jax.random.split(jax.random.PRNGKey(3), R)
    tables = jax.random.normal(jax.random.PRNGKey(1), (R, rows, obs_dim))
    k_ax = None if batched == "data" else 0
    d_ax = None if batched == "idx" else 0
    got = jax.jit(jax.vmap(lambda k, d: _bootstrap(k, d, n_draw),
                           in_axes=(k_ax, d_ax), axis_size=R))(
        keys if k_ax == 0 else keys[0], tables if d_ax == 0 else tables[0])
    want = np.stack([_bootstrap(keys[r if k_ax == 0 else 0],
                                tables[r if d_ax == 0 else 0], n_draw)
                     for r in range(R)])
    np.testing.assert_array_equal(np.asarray(got), want)
