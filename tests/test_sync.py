"""Unit + property tests for the SAGIPS sync strategies (vmap backend —
bitwise-identical to the mesh backend, see test_workflow_dist.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.ring import VmapComm
from repro.core.sync import (FusionSpec, SyncConfig, init_mailbox,
                             sync_gradients)


def grads_like(R, key=0, shape=(3, 4)):
    ks = jax.random.split(jax.random.PRNGKey(key), 2)
    return {"w": jax.random.normal(ks[0], (R,) + shape),
            "b": jax.random.normal(ks[1], (R, shape[-1]))}


MASK = {"w": True, "b": False}


def test_conv_arar_matches_algorithm1():
    """g_i <- g_i + g_{i-1} around the global ring (Algorithm 1)."""
    R = 6
    comm = VmapComm(2, 3)
    g = grads_like(R)
    out, _ = sync_gradients(comm, SyncConfig(mode="conv_arar"), g,
                            init_mailbox(g), jnp.zeros((), jnp.int32), MASK)
    expect = np.asarray(g["w"]) + np.roll(np.asarray(g["w"]), 1, axis=0)
    np.testing.assert_allclose(np.asarray(out["w"]), expect, rtol=1e-6)
    # biases never ride the ring (§V-C)
    np.testing.assert_array_equal(np.asarray(out["b"]), np.asarray(g["b"]))


def test_arar_grouped_inner_ring_and_outer_period():
    R, O, I = 8, 2, 4
    comm = VmapComm(O, I)
    g = grads_like(R)
    cfg = SyncConfig(mode="arar_arar", h=10)
    # epoch 3: not due -> inner ring only
    out, _ = sync_gradients(comm, cfg, g, init_mailbox(g),
                            jnp.asarray(3), MASK)
    w = np.asarray(g["w"]).reshape(O, I, 3, 4)
    inner = w + np.roll(w, 1, axis=1)
    np.testing.assert_allclose(np.asarray(out["w"]).reshape(O, I, 3, 4),
                               inner, rtol=1e-6)
    # epoch 10: due -> inner-rank-0 members also add the outer ring value
    out10, _ = sync_gradients(comm, cfg, g, init_mailbox(g),
                              jnp.asarray(10), MASK)
    outer = inner + np.roll(inner, 1, axis=0)
    expect = inner.copy()
    expect[:, 0] = outer[:, 0]
    np.testing.assert_allclose(np.asarray(out10["w"]).reshape(O, I, 3, 4),
                               expect, rtol=1e-6)


def test_rma_staleness_semantics():
    """RMA reads last epoch's deposit; deposit is this epoch's fresh grads."""
    comm = VmapComm(1, 4)
    g1 = grads_like(4, key=1)
    g2 = grads_like(4, key=2)
    cfg = SyncConfig(mode="rma_arar_arar", h=1000)
    mb0 = init_mailbox(g1)
    out1, mb1 = sync_gradients(comm, cfg, g1, mb0, jnp.asarray(1), MASK)
    # first epoch: mailbox empty -> g unchanged
    np.testing.assert_allclose(np.asarray(out1["w"]), np.asarray(g1["w"]))
    # mailbox now holds ring-shifted fresh g1
    np.testing.assert_allclose(np.asarray(mb1["w"]),
                               np.roll(np.asarray(g1["w"]), 1, axis=0))
    out2, mb2 = sync_gradients(comm, cfg, g2, mb1, jnp.asarray(2), MASK)
    expect = np.asarray(g2["w"]) + np.roll(np.asarray(g1["w"]), 1, axis=0)
    np.testing.assert_allclose(np.asarray(out2["w"]), expect, rtol=1e-6)


def test_allreduce_is_pmean():
    comm = VmapComm(2, 2)
    g = grads_like(4)
    out, _ = sync_gradients(comm, SyncConfig(mode="allreduce"), g,
                            init_mailbox(g), jnp.asarray(0), MASK)
    mean = np.asarray(g["w"]).mean(axis=0, keepdims=True)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.broadcast_to(mean, g["w"].shape), rtol=1e-6)


def test_ensemble_no_communication():
    comm = VmapComm(2, 2)
    g = grads_like(4)
    out, _ = sync_gradients(comm, SyncConfig(mode="ensemble"), g,
                            init_mailbox(g), jnp.asarray(0), MASK)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(g["w"]))


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 4), st.integers(2, 6), st.integers(0, 99),
       st.sampled_from(["conv_arar", "arar_arar", "rma_arar_arar"]))
def test_ring_conserves_gradient_mass(O, I, epoch, mode):
    """Property: summed over ranks, ring exchange preserves total gradient
    'information' — sum_i synced_i = sum_i g_i + sum_i received_i, and with
    combine='mean' the global mean is invariant for ring modes every epoch
    where only the ring runs."""
    R = O * I
    comm = VmapComm(O, I)
    g = grads_like(R, key=epoch)
    cfg = SyncConfig(mode=mode, h=7, combine="mean")
    out, _ = sync_gradients(comm, cfg, g, init_mailbox(g),
                            jnp.asarray(epoch), MASK)
    if mode == "rma_arar_arar":
        return  # first-epoch mailbox is zero: mean halves by design
    due_outer = (epoch % 7 == 0) and O > 1
    if not due_outer:
        np.testing.assert_allclose(np.asarray(out["w"]).mean(axis=0),
                                   np.asarray(g["w"]).mean(axis=0),
                                   rtol=1e-5, atol=1e-6)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 5), st.integers(2, 5))
def test_ring_all_visits_every_rank(O, I):
    """R applications of the global ring accumulate every rank's gradient
    (diffusion closure of Algorithm 1)."""
    R = O * I
    comm = VmapComm(O, I)
    g = {"w": jnp.eye(R)}           # rank i holds basis vector e_i
    cur = g
    for _ in range(R - 1):
        recv = comm.recv_ring_all(cur)
        cur = jax.tree.map(lambda a, b: a + b, g, recv)
    # after R-1 hops, every rank has accumulated every basis vector
    assert np.all(np.asarray(cur["w"]) > 0)


def test_tensor_fusion_matches_unfused():
    """Paper §VII: fused ring payload is bitwise identical on VmapComm."""
    R = 8
    comm = VmapComm(2, 4)
    g = {"l1": {"w": jax.random.normal(jax.random.PRNGKey(0), (R, 3, 4)),
                "b": jax.random.normal(jax.random.PRNGKey(1), (R, 4))},
         "l2": {"w": jax.random.normal(jax.random.PRNGKey(2), (R, 5, 2)),
                "b": jax.random.normal(jax.random.PRNGKey(3), (R, 2))}}
    mask = {"l1": {"w": True, "b": False}, "l2": {"w": True, "b": False}}
    for mode in ("conv_arar", "arar_arar", "rma_arar_arar"):
        o1, _ = sync_gradients(comm, SyncConfig(mode=mode, h=2), g,
                               init_mailbox(g), jnp.asarray(2), mask)
        o2, _ = sync_gradients(comm, SyncConfig(mode=mode, h=2,
                                                fuse_tensors=True), g,
                               init_mailbox(g), jnp.asarray(2), mask)
        for a, b in zip(jax.tree.leaves(o1), jax.tree.leaves(o2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dbtree_equals_allreduce():
    """Tree exchange (paper §VII future work via [18]) = full mean reduce."""
    R = 8
    comm = VmapComm(2, 4)
    g = grads_like(R)
    o_tree, _ = sync_gradients(comm, SyncConfig(mode="dbtree"), g,
                               init_mailbox(g), jnp.asarray(0), MASK)
    o_ar, _ = sync_gradients(comm, SyncConfig(mode="allreduce"), g,
                             init_mailbox(g), jnp.asarray(0), MASK)
    np.testing.assert_allclose(np.asarray(o_tree["w"]), np.asarray(o_ar["w"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(o_tree["b"]), np.asarray(g["b"]))


def test_tensor_fusion_parity_all_ring_modes_and_masks():
    """fuse_tensors=True ≡ fuse_tensors=False, bitwise, across every ring
    mode and several mask shapes (incl. dbtree and a fully-masked tree)."""
    R = 8
    comm = VmapComm(2, 4)
    g = {"l1": {"w": jax.random.normal(jax.random.PRNGKey(0), (R, 3, 4)),
                "b": jax.random.normal(jax.random.PRNGKey(1), (R, 4))},
         "l2": {"w": jax.random.normal(jax.random.PRNGKey(2), (R, 5, 2)),
                "b": jax.random.normal(jax.random.PRNGKey(3), (R, 2))}}
    masks = [
        {"l1": {"w": True, "b": False}, "l2": {"w": True, "b": False}},
        {"l1": {"w": True, "b": True}, "l2": {"w": True, "b": True}},
        {"l1": {"w": False, "b": False}, "l2": {"w": True, "b": False}},
        # all-False: nothing rides the ring — fused must be a no-op too
        {"l1": {"w": False, "b": False}, "l2": {"w": False, "b": False}},
    ]
    for mask in masks:
        for mode in ("conv_arar", "arar_arar", "rma_arar_arar", "dbtree"):
            for epoch in (0, 2, 3):
                a, mb_a = sync_gradients(
                    comm, SyncConfig(mode=mode, h=2, fuse_tensors=False), g,
                    init_mailbox(g), jnp.asarray(epoch), mask)
                b, mb_b = sync_gradients(
                    comm, SyncConfig(mode=mode, h=2, fuse_tensors=True), g,
                    init_mailbox(g), jnp.asarray(epoch), mask)
                for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
                for x, y in zip(jax.tree.leaves(mb_a), jax.tree.leaves(mb_b)):
                    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_fusion_spec_precomputed_layout():
    """FusionSpec caches offsets/shapes once; flatten/unflatten roundtrip."""
    example = [{"w": jnp.zeros((3, 4)), "b": jnp.zeros((4,))},
               {"w": jnp.zeros((5, 2)), "b": jnp.zeros((2,))}]
    mask = [{"w": True, "b": False}, {"w": True, "b": False}]
    spec = FusionSpec.build(example, mask)
    assert spec.total == 3 * 4 + 5 * 2
    offs = [s.offset for s in spec.slots if s.masked]
    assert offs == [0, 12]

    R = 4
    tree = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(x.size), (R,) + x.shape),
        example)
    flat = spec.flatten(tree, stacked=True)
    assert flat.shape == (R, spec.total)
    back = spec.unflatten(flat, tree, stacked=True)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # per-rank (ShardComm) layout
    tree1 = jax.tree.map(lambda x: x[0], tree)
    flat1 = spec.flatten(tree1, stacked=False)
    assert flat1.shape == (spec.total,)
    back1 = spec.unflatten(flat1, tree1, stacked=False)
    for a, b in zip(jax.tree.leaves(back1), jax.tree.leaves(tree1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_rma_mailbox_depth_k_reads_exactly_k_epochs_old():
    """Regression (SyncConfig.staleness was a dead field): with depth k the
    rma_arar_arar read at epoch e is the ring deposit from epoch e - k."""
    R, k = 4, 3
    comm = VmapComm(1, R)
    cfg = SyncConfig(mode="rma_arar_arar", h=1000, staleness=k)
    gs = [grads_like(R, key=100 + e) for e in range(6)]
    mb = init_mailbox(gs[0], staleness=k, stacked=True)
    assert mb["w"].shape == (R, k) + gs[0]["w"].shape[1:]
    for e in range(6):
        out, mb = sync_gradients(comm, cfg, gs[e], mb, jnp.asarray(e), MASK)
        if e < k:          # warmup: mailbox slot still zero
            expect = np.asarray(gs[e]["w"])
        else:              # deposit from epoch e-k, ring-shifted by 1
            expect = np.asarray(gs[e]["w"]) + \
                np.roll(np.asarray(gs[e - k]["w"]), 1, axis=0)
        np.testing.assert_allclose(np.asarray(out["w"]), expect, rtol=1e-6)
        # biases stay local regardless of depth
        np.testing.assert_array_equal(np.asarray(out["b"]),
                                      np.asarray(gs[e]["b"]))


def test_staleness_config_validation():
    with pytest.raises(ValueError):
        SyncConfig(mode="rma_arar_arar", staleness=0)
    with pytest.raises(ValueError):
        SyncConfig(mode="arar_arar", staleness=2)
    with pytest.raises(ValueError):
        SyncConfig(mode="nonsense")
    SyncConfig(mode="rma_arar_arar", staleness=4)      # fine
