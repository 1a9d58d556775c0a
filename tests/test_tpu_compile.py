"""Compile rehearsals for one TPU v5e chip: the exchange kernels of the
CNN training path, at the paper's real shapes (K=5 sites, gn-lenet at
32x32 — 145834 floats per site, largest leaf 1024x64), with the block
size TPU dispatch picks.  Nothing runs; the TPU compiler either accepts
the kernel or raises what the chip would raise.

The topology is described inside a module fixture (never at import), so
every xdist worker collects the same tests and only the worker running
this file loads the TPU library."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dgc_topk, gaia_select, neighbor_mix, ops

K = 5
N_SITE = 145_834            # gn-lenet, 32x32 input, flattened per site
LEAF = (K, 1024, 64)        # its largest parameter leaf, stacked over K
STALE_SLOTS = 3             # AD-PSGD max_staleness=2


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # repro-allow: RA104 — any failure means no
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _cases(sds):
    f32, i32 = jnp.float32, jnp.int32
    b_mix = ops._block_rows_for(N_SITE, 256)
    b_leaf = ops._block_rows_for(LEAF[0] * LEAF[1] * LEAF[2], 256)
    mix_ops = (sds((K, 2), i32), sds((K, 2), f32), sds((K,), f32))
    return {
        "neighbor_mix": (
            lambda x, i, w, s: neighbor_mix.neighbor_mix(
                x, i, w, s, block_rows=b_mix),
            (sds((K, N_SITE), f32),) + mix_ops),
        "neighbor_mix_src": (
            lambda x, i, w, s, src: neighbor_mix.neighbor_mix(
                x, i, w, s, src=src, block_rows=b_mix),
            (sds((K, N_SITE), f32),) + mix_ops
            + (sds((STALE_SLOTS * K, N_SITE), f32),)),
        "gaia_select": (
            lambda v, w, t: gaia_select.gaia_select(v, w, t,
                                                    block_rows=b_leaf),
            (sds(LEAF, f32), sds(LEAF, f32), sds((), f32))),
        "rand_k_select": (
            lambda v, p, s: dgc_topk.rand_k_select(v, p, s,
                                                   block_rows=b_leaf),
            (sds(LEAF, f32), sds((), f32), sds((), i32))),
        "dgc_select": (
            lambda v, t: dgc_topk.dgc_select(v, t, block_rows=b_leaf),
            (sds(LEAF, f32), sds((), f32))),
    }


@pytest.mark.parametrize("kernel", ["neighbor_mix", "neighbor_mix_src",
                                    "gaia_select", "rand_k_select",
                                    "dgc_select"])
def test_kernel_compiles_for_v5e(kernel, one_chip, no_persistent_cache):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fn, args = _cases(sds)[kernel]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
