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


def test_streamed_w128_chunk_gathers_without_a_loop(one_chip):
    """The fused gather-and-count of a W=128 chunk at Graph500 scale 18, as
    the default budget streams it on a v5e, reads the bucket's own 128-wide
    neighbor table: its row gathers compile to gathers, and no ``while``
    loop of one row per iteration comes from them (a 128-wide window of a
    512-wide table compiles to two such loops)."""
    from repro.core import engine, prep

    n = 1 << 18
    run = engine._build_intersect_executable("broadcast", "jnp", False, None)
    chunk = engine._tile_chunk_rows(
        1 << 21, prep.bucket_nbytes(1, 128),
        _V5E_HBM_BYTES // engine._BUCKET_MEMORY_SHARE)
    assert chunk == 1 << 20
    edges = _spec(one_chip, (1 << 22,), jnp.int32)
    scalar = _spec(one_chip, (), jnp.int32)
    table = _spec(one_chip, (n, 128), jnp.int32)
    text = prep.gathered_count(run).lower(
        edges, edges, scalar, scalar, table, n=n, rows=chunk
    ).compile().as_text()
    lines = text.splitlines()
    assert sum(" gather(" in line and "slice_sizes={1,128}" in line
               for line in lines) == 2
    loops = [line for line in lines if " while(" in line]
    assert not any("_gather_bucket_dev" in line for line in loops), loops


@pytest.mark.parametrize("width", [512, 1024])
def test_vertex_chunk_runs_the_broadcast_mask_at_scale_20(one_chip,
                                                          monkeypatch, width):
    """The fused gather and per-vertex pass of one streamed chunk at
    Graph500 scale 20, as the default budget streams it on a v5e: the mask
    rule takes the row-chunked broadcast core on a TPU (no ``searchsorted``
    from the probe core), and its temporaries fit beside the neighbor
    tables the plan keeps (n × 4 B × (8 + 32 + 128 + 512 + 1024))."""
    from repro.core import engine, prep

    # the rule asks JAX's default backend, which is the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = 1 << 20
    chunk = engine._tile_chunk_rows(
        1 << 24, prep.bucket_nbytes(1, width),
        _V5E_HBM_BYTES // engine._BUCKET_MEMORY_SHARE)
    assert chunk == (1 << 18 if width == 512 else 1 << 17)
    run = prep.gathered_count(
        engine._build_vertex_executable(n, f"tc_vertex_w{width}"),
        endpoints=True)
    edges = _spec(one_chip, (1 << 24,), jnp.int32)
    scalar = _spec(one_chip, (), jnp.int32)
    compiled = run.lower(
        edges, edges, scalar, scalar, _spec(one_chip, (n, width), jnp.int32),
        _spec(one_chip, (n,), jnp.int32), n=n, rows=chunk).compile()
    text = compiled.as_text()
    assert "searchsorted" not in text and "reduce_or" in text
    tables = n * 4 * (8 + 32 + 128 + 512 + 1024)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 0 < temp < _V5E_HBM_BYTES - tables - (1 << 30), temp
