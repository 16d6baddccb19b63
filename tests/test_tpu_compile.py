"""The main path's Pallas kernels compile for a TPU v5e chip.

Interpret mode accepts kernels that the chip's compiler refuses (a float
iota, blocks off the (8, 128) tiling, a dynamic slice of a loaded value),
so the interpret-mode parity suites cannot see those refusals.  These
tests compile each kernel at the widths the paper's jobs use for a
described ``v5e:2x2`` topology with no chip attached, and check that the
compiled program holds the kernel as a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and under
pytest-xdist every worker imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import (
    aggregate_sparse_gridded,
    krum_scores_fused,
    sort_workers_fused,
    topk_compress_sharded,
    topk_compress_tiled,
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


# name -> (kernel program with the Mosaic lowering forced, argument
# shapes and dtypes); m=20 workers and d=300 are the paper's w8a job,
# d=1408 the widest single-tile launch, d=65536 the gridded launches
CASES = {
    "topk_single_tile_d300_vmap_m20": (
        jax.vmap(functools.partial(topk_compress_tiled, k=30,
                                   interpret=False)),
        [((20, 300), jnp.float32)]),
    "topk_single_tile_d1408": (
        functools.partial(topk_compress_tiled, k=141, interpret=False),
        [((1408,), jnp.float32)]),
    "topk_gridded_d65536_k6554": (
        functools.partial(topk_compress_sharded, k=6554, interpret=False),
        [((65536,), jnp.float32)]),
    "sparse_merge_m20_d65536": (
        functools.partial(aggregate_sparse_gridded, d=65536,
                          interpret=False),
        [((20, 656), jnp.float32), ((20, 656), jnp.int32)]),
    "krum_fused_m20_d300": (
        functools.partial(krum_scores_fused, n_byz=4, interpret=False),
        [((20, 300), jnp.float32)]),
    "row_sort_m20_d300": (
        functools.partial(sort_workers_fused, interpret=False),
        [((20, 300), jnp.float32)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, arg_specs = CASES[case]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in arg_specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
