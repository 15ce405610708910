"""Compile the Pallas cores and the main ``jnp`` executable for a v5e.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, so the layouts, tilings and VMEM budgets the chip's compiler would
refuse fail here, under ``interpret=False``, at real widths and tiles. Every
Pallas kernel's compiled program must contain its ``tpu_custom_call``.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and under several test workers
only the worker that runs this file may try.
"""

from __future__ import annotations

import functools
import os

import pytest

import jax
import jax.numpy as jnp

# one real bucket extent: rows a multiple of the 256-edge Pallas tile
_EDGES = 4096
_V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pallas_call(kernel: str, width: int):
    """(function, [(shape, dtype)] of its arguments) for one Pallas core at
    width W."""
    from repro.kernels.hash_tc.ops import hash_probe_counts
    from repro.kernels.intersect.ops import intersect_counts
    from repro.kernels.masked_spgemm.ops import masked_spgemm_counts

    rows = ((_EDGES, width), jnp.int32)
    pair = [rows, rows]
    if kernel in ("broadcast", "probe"):
        return functools.partial(intersect_counts, strategy=kernel,
                                 backend="pallas", interpret=False), pair
    if kernel == "bitmap":
        return functools.partial(intersect_counts, strategy="bitmap",
                                 backend="pallas", interpret=False,
                                 bitmap_bits=width), pair
    if kernel == "hash":  # B = W buckets, depth 4, a 2^14-vertex table
        return functools.partial(hash_probe_counts, backend="pallas",
                                 interpret=False), \
            [rows, ((_EDGES,), jnp.int32), ((1 << 14, width, 4), jnp.int32)]
    assert kernel == "matrix"  # 64 tile triples of 128x128 blocks
    tiles = ((64, width, width), jnp.float32)
    return functools.partial(masked_spgemm_counts, backend="pallas",
                             interpret=False), [tiles] * 3


@pytest.mark.parametrize("kernel,width", [
    ("broadcast", 128), ("broadcast", 512),
    ("probe", 128), ("probe", 512),
    ("bitmap", 128), ("bitmap", 512),
    ("hash", 128), ("hash", 512),
    ("matrix", 128),
])
def test_pallas_kernel_compiles_for_v5e(one_chip, kernel, width):
    fn, shapes = _pallas_call(kernel, width)
    args = [_spec(one_chip, shape, dtype) for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_main_jnp_executable_fits_v5e(one_chip):
    """The auto lane's widest streamed chunk at Graph500 scale 18, as
    ``TriangleCounter`` plans it on a v5e: the strategy the cost model picks
    for the ``jnp`` core on a TPU, over the chunk rows the default budget
    gives the (2^22, 512) bucket."""
    from repro.core import engine, prep
    from repro.kernels.intersect.ops import choose_strategy

    strategy = choose_strategy(512, None, "jnp", "tpu")
    assert strategy == "broadcast"
    run = engine._build_intersect_executable(strategy, "jnp", False, None)
    chunk = engine._tile_chunk_rows(
        1 << 22, prep.bucket_nbytes(1, 512),
        _V5E_HBM_BYTES // engine._BUCKET_MEMORY_SHARE)
    assert chunk == 1 << 18
    rows = _spec(one_chip, (chunk, 512), jnp.int32)
    mem = run.lower(rows, rows).compile().memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < _V5E_HBM_BYTES // 2, used
