"""Ahead-of-time compiles of the main path for a described TPU v5e 2x2
(no chip attached): what the TPU compiler would refuse fails here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  Keep every such compile in this one file.
"""
import os
import re

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


def _state_and_data(wcfg, n_ranks, sharding, n_sub=None):
    """Abstract stacked state and per-rank data, placed on `sharding`."""
    from repro.core import workflow
    state = jax.eval_shape(
        lambda k: workflow.init_state(k, n_ranks, wcfg),
        jax.random.PRNGKey(0))
    n_sub = n_sub or int(wcfg.data_fraction * 102_400)
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


def _sample_gathers(hlo: str) -> list:
    """The form of each fusion of a compiled module that holds a gather of
    the `sagips_sample` scope: its result with layout, the TPU compiler's
    `integer_config`, its scoped fast-memory bytes, the fused computation's
    parameters, the gather's `start_index_map`, and whether the (rank, row)
    indices were bit-packed for it."""
    comps = {b.split(" ", 1)[0].lstrip("%"): b
             for b in re.split(r"\n(?=\S)", hlo)}
    forms = []
    for line in hlo.splitlines():
        m = re.search(r" fusion\(.*calls=%([\w.\-]+)", line)
        body = comps.get(m.group(1), "") if m else ""
        gathers = [g for g in body.splitlines()
                   if " gather(" in g and "sagips_sample" in g]
        if not gathers:
            continue
        forms.append({
            "result": line.split(" = ", 1)[1].split(" fusion(")[0],
            "integer_config": re.search(
                r'"integer_config":\{"integer":"(\d+)"', line).group(1),
            "scoped_bytes": re.search(
                r'"used_scoped_memory_configs":\[\{[^]]*"size":"(\d+)"',
                line).group(1),
            "params": re.findall(r"= (\S+) parameter\(", body),
            "index_map": [re.search(r"start_index_map=\{([\d,]*)\}",
                                    g).group(1) for g in gathers],
            "bitpacked": "GatherScatterIndicesBitpacked" in body,
        })
    return forms


def test_vmapped_bootstrap_gather_has_the_shard_form(topo, one_chip):
    """Tab. III's 16 vmapped ranks draw their bootstrap batch with the
    unbatched gather the shard program runs (one rank's 25,000-row table
    staged in fast memory, a 1-D index of its own draws), not a gather
    over bit-packed (rank, row) indices."""
    from repro.core import workflow
    wcfg = _paper()
    n_sub = 25_000
    chunk = workflow.make_chunk_fn_vmap(4, 4, wcfg, 1)
    vmapped = chunk.lower(*_state_and_data(wcfg, 16, one_chip, n_sub)
                          ).compile().as_text()
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("pod", "data"))
    fn, sharding = workflow.make_epoch_fn_shard(mesh, wcfg)
    shard = fn.lower(*_state_and_data(wcfg, 4, sharding, n_sub)
                     ).compile().as_text()
    (got,), (want,) = _sample_gathers(vmapped), _sample_gathers(shard)
    assert not re.search(r"GatherScatterIndicesBitpacked.*sagips_sample",
                         vmapped)
    assert not got["bitpacked"] and got["index_map"] == ["0"]
    idx = [p for p in got["params"] if p.startswith("s32")]
    assert [p.split("{")[0] for p in idx] == [f"s32[{wcfg.disc_batch}]"]
    assert got == want
