"""Ahead-of-time compiles of the main path for a described TPU v5e 2x2
(no chip attached): what the TPU compiler would refuse fails here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  Keep every such compile in this one file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

HBM_BYTES = 16e9        # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _paper(**kw):
    import dataclasses
    from repro.configs import sagips_gan
    return dataclasses.replace(sagips_gan.PAPER, **kw)


def _state_and_data(wcfg, n_ranks, sharding):
    """Abstract stacked state and per-rank data, placed on `sharding`."""
    from repro.core import workflow
    state = jax.eval_shape(
        lambda k: workflow.init_state(k, n_ranks, wcfg),
        jax.random.PRNGKey(0))
    n_sub = int(wcfg.data_fraction * 102_400)
    obs = wcfg.problem_obj.obs_dim
    place = lambda x: _sds(x.shape, sharding, x.dtype)
    return (jax.tree.map(place, state),
            _sds((n_ranks, n_sub, obs), sharding))


def test_inverse_cdf_forward_and_vjp_compile(one_chip):
    from repro.kernels import ops
    K, E = 1024, 100
    args = [_sds((K, E), one_chip)] + [_sds((K,), one_chip)] * 3

    def fwd_vjp(u, mu, s, k):
        y, vjp = jax.vjp(lambda *a: ops.inverse_cdf(*a, False), u, mu, s, k)
        return y, vjp(y)

    text = jax.jit(fwd_vjp).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name,shape", [("mask_apply", (64, 1024)),
                                        ("blur2d", (64, 32, 32))])
def test_imaging_kernels_compile(one_chip, name, shape):
    from repro.kernels import imaging
    x = _sds(shape, one_chip)
    if name == "mask_apply":
        m = _sds(shape[1:], one_chip)
        fn = jax.jit(lambda x, m: imaging.mask_apply(x, m, interpret=False))
        lowered = fn.lower(x, m)
    else:
        fn = jax.jit(lambda x: imaging.blur2d(x, interpret=False))
        lowered = fn.lower(x)
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_paper_chunk_with_pallas_sampler_fits_one_chip(one_chip):
    from repro.core import workflow
    wcfg = _paper(sampler_impl="pallas", sampler_interpret=False)
    fn = workflow.make_chunk_fn_vmap(2, 4, wcfg, 1)
    compiled = fn.lower(*_state_and_data(wcfg, 8, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < HBM_BYTES, f"temp {temp / 1e9:.2f} GB"


def test_shard_epoch_on_2x2_mesh_has_collective_permute(topo):
    from repro.core import workflow
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("pod", "data"))
    wcfg = _paper()
    fn, sharding = workflow.make_epoch_fn_shard(mesh, wcfg)
    assert isinstance(sharding, NamedSharding)
    compiled = fn.lower(*_state_and_data(wcfg, 4, sharding)).compile()
    assert "collective-permute" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < HBM_BYTES, f"temp {temp / 1e9:.2f} GB per device"
