"""Tiled out-of-core execution == the monolithic plan, bit for bit.

``CountOptions.max_device_bytes`` bounds the bytes any one bucket may hold
resident; buckets over the budget stream through the SAME cached
executables chunk-by-chunk (pow2 chunk rows, inert tail padding, host
accumulation). This module is the differential harness:

* strategy × prep_backend × budget sweep on the intersection lane — every
  cell asserts tiled == monolithic == scipy, and forced-small budgets
  assert the plan REALLY streamed (≥2 chunks in the meta);
* the matrix lane's (T, B, B) tile-stack streaming (float partials are
  exact small integers, so host accumulation is bit-identical);
* the subgraph lane inheriting streaming through its inner intersection;
* the zero-recompile contract: steady-state replays of a tiled plan hit
  the executable cache only (chunk shapes are pow2 classes, so ONE compile
  per (chunk, width) then pure replays);
* ``triangles_per_vertex`` over a tiled filtered plan (the vertex
  executable streams the same chunks);
* chunks past a device bucket's real rows hold only padding and leave the
  count exact;
* budget semantics: a budget big enough for everything tiles nothing and
  keys a distinct plan from the unbudgeted options;
* device prep gathers every bucket, resident or streamed, as whole rows of
  a neighbor table exactly as wide as the bucket.
"""

import re

import numpy as np
import pytest

from repro.core import (
    CountOptions,
    TriangleCounter,
    executable_cache_info,
    triangle_count_scipy,
)
from repro.graphs import erdos_renyi_graph, rmat_graph

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(scope="module")
def g_rmat():
    return rmat_graph(8, edge_factor=8, seed=21)


@pytest.fixture(scope="module")
def g_er():
    return erdos_renyi_graph(400, avg_degree=10.0, seed=4)


def _count(g, **kw):
    return TriangleCounter(g, CountOptions(**kw)).count()


@pytest.mark.parametrize("strategy", ["broadcast", "probe", "bitmap"])
@pytest.mark.parametrize("prep_backend", ["device", "host"])
@pytest.mark.parametrize("budget", [1 << 13, 1 << 16])
def test_tiled_intersection_sweep(g_rmat, strategy, prep_backend, budget):
    oracle = int(triangle_count_scipy(g_rmat))
    mono = _count(g_rmat, algorithm="intersection", strategy=strategy,
                  prep_backend=prep_backend)
    tiled = _count(g_rmat, algorithm="intersection", strategy=strategy,
                   prep_backend=prep_backend, max_device_bytes=budget)
    assert int(mono) == int(tiled) == oracle
    if budget <= 1 << 13:
        assert tiled.meta["num_chunks"] >= 2, tiled.meta
        assert tiled.meta["tiled_buckets"], tiled.meta
    for tb in tiled.meta["tiled_buckets"]:
        # chunk rows are pow2 and respect the budget per-row cost
        c = tb["chunk_rows"]
        assert c >= 1 and (c & (c - 1)) == 0
        assert tb["num_chunks"] >= 2


@pytest.mark.parametrize("variant", ["filtered", "full"])
def test_tiled_variants(g_er, variant):
    oracle = int(triangle_count_scipy(g_er))
    tiled = _count(g_er, algorithm="intersection", variant=variant,
                   max_device_bytes=1 << 13)
    assert int(tiled) == oracle
    assert tiled.meta["num_chunks"] >= 2


def test_tiled_matrix(g_er):
    oracle = int(triangle_count_scipy(g_er))
    mono = _count(g_er, algorithm="matrix")
    tiled = _count(g_er, algorithm="matrix", max_device_bytes=1 << 14)
    assert int(mono) == int(tiled) == oracle
    assert tiled.meta["num_chunks"] >= 2


def test_tiled_subgraph(g_er):
    oracle = int(triangle_count_scipy(g_er))
    tiled = _count(g_er, algorithm="subgraph", max_device_bytes=1 << 13)
    assert int(tiled) == oracle
    assert tiled.meta["num_chunks"] >= 2


def _padding_only_chunks(meta) -> int:
    """Chunks of the streamed buckets' padded extents that start past the
    buckets' real rows, whether or not the plan dispatches them."""
    total = 0
    for tb in meta["tiled_buckets"]:
        rows, chunk = tb["shape"][0], tb["chunk_rows"]
        edges = meta["bucket_edges"][meta["bucket_shapes"].index(tb["shape"])]
        total += -(-rows // chunk) - -(-edges // chunk)
    return total


# g_er's W=8 bucket holds 1,936 real rows and its W=32 bucket 37; device
# prep pads them to 2,048 and 64 rows, and each budget picks the chunk rows
# that leave this many whole chunks of padding past them
@pytest.mark.parametrize("budget,padding_chunks",
                         [(1 << 14, 0), (8704, 1), (1 << 12, 6)])
@pytest.mark.parametrize("prep_backend", ["device", "host"])
def test_padding_only_chunks_leave_the_count_exact(g_er, prep_backend,
                                                   budget, padding_chunks):
    """Chunks that hold only padding add nothing: the streamed count equals
    the untiled plan and scipy. Host prep keeps only a bucket's real rows
    (its tail chunk is padded on upload), so its plan has no such chunk."""
    oracle = int(triangle_count_scipy(g_er))
    mono = _count(g_er, algorithm="intersection", prep_backend=prep_backend)
    tiled = _count(g_er, algorithm="intersection", prep_backend=prep_backend,
                   max_device_bytes=budget)
    assert tiled.meta["tiled_buckets"], tiled.meta
    assert _padding_only_chunks(tiled.meta) == (
        padding_chunks if prep_backend == "device" else 0)
    assert int(tiled) == int(mono) == oracle


def test_tiled_steady_state_never_recompiles(g_rmat):
    tc = TriangleCounter(g_rmat, CountOptions(algorithm="intersection",
                                              max_device_bytes=1 << 13))
    first = tc.count()
    assert first.meta["num_chunks"] >= 2
    before = executable_cache_info()["misses"]
    for _ in range(3):
        assert int(tc.plan.count()) == int(first)
    assert executable_cache_info()["misses"] == before, \
        "steady-state tiled replays must be pure cache hits"


def test_tiled_vertex_counts_match_monolithic(g_rmat):
    mono = TriangleCounter(g_rmat, CountOptions(algorithm="intersection"))
    tiled = TriangleCounter(g_rmat, CountOptions(algorithm="intersection",
                                                 max_device_bytes=1 << 13))
    pv_m = mono.triangles_per_vertex()
    pv_t = tiled.triangles_per_vertex()
    assert pv_m.shape == pv_t.shape == (g_rmat.n,)
    np.testing.assert_array_equal(pv_m, pv_t)
    assert int(pv_t.sum()) == 3 * int(triangle_count_scipy(g_rmat))


def test_generous_budget_tiles_nothing(g_er):
    res = _count(g_er, algorithm="intersection", max_device_bytes=1 << 30)
    assert int(res) == int(triangle_count_scipy(g_er))
    assert res.meta["num_chunks"] == 0
    assert res.meta["tiled_buckets"] == []


def test_budget_is_part_of_the_options_key():
    a = CountOptions(algorithm="intersection")
    b = CountOptions(algorithm="intersection", max_device_bytes=1 << 13)
    c = CountOptions(algorithm="intersection", max_device_bytes=1 << 16)
    assert len({a.key(), b.key(), c.key()}) == 3
    with pytest.raises(ValueError):
        CountOptions(max_device_bytes=0)
    with pytest.raises(ValueError):
        CountOptions(max_device_bytes=-5)


@pytest.mark.parametrize("algorithm", ["intersection", "subgraph"])
def test_device_streamed_buckets_match_monolithic(g_rmat, algorithm):
    """With device prep, over-budget buckets are gathered chunk by chunk on
    device (``prep.StreamedBucket``) and never held whole; the counts and
    per-vertex counts still equal the monolithic plan's."""
    mono = TriangleCounter(g_rmat, CountOptions(algorithm=algorithm))
    tiled = TriangleCounter(g_rmat, CountOptions(algorithm=algorithm,
                                                 max_device_bytes=1 << 13))
    res = tiled.count()
    assert int(res) == int(mono.count()) == int(triangle_count_scipy(g_rmat))
    streamed = [st for st in tiled.plan.stages
                if getattr(st, "source", None) is not None]
    assert streamed and res.meta["num_chunks"] >= 2
    np.testing.assert_array_equal(tiled.triangles_per_vertex(),
                                  mono.triangles_per_vertex())


def test_streamed_chunk_executables_live_in_the_engine_cache(g_rmat):
    """A streamed chunk's fused gather-and-count is an entry of the engine's
    executable cache, beside its chunk executable: plans of the same shapes
    share it, and clearing the cache releases it."""
    from repro.core import engine

    opts = CountOptions(algorithm="intersection", max_device_bytes=1 << 13)
    a, b = TriangleCounter(g_rmat, opts), TriangleCounter(g_rmat, opts)
    a.count(), b.count()
    streamed = [(sa, sb) for sa, sb in zip(a.plan.stages, b.plan.stages)
                if getattr(sa, "source", None) is not None]
    assert streamed
    keys = engine.cache_info()["keys"]
    for sa, sb in streamed:
        assert sa.executable is sb.executable
        key = ("intersection_gathered", sa.strategy, a.plan.backend,
               a.plan.interpret, sa.bitmap_bits, sa.chunk_shape_key)
        assert key in keys and engine._EXECUTABLE_CACHE[key] is sa.executable
    engine.clear_executable_cache()
    assert not any(k[0] == "intersection_gathered"
                   for k in engine.cache_info()["keys"])


@pytest.mark.parametrize("variant", ["filtered", "full"])
def test_each_bucket_gathers_from_a_table_as_wide_as_itself(
        g_rmat, variant, monkeypatch):
    """Device prep gathers each bucket, resident or streamed, from a padded
    neighbor table exactly as wide as the bucket, and the plan's meta lists
    those widths; the counts and per-vertex counts still equal the
    monolithic and host-prep plans' bit for bit."""
    from repro.core import prep

    gathers = []  # (table width, gathered row width) of every device gather
    real = prep._gather_bucket_dev

    def recording(*args, **kw):
        out = real(*args, **kw)
        gathers.append((int(args[4].shape[1]), int(out[0].shape[1])))
        return out

    monkeypatch.setattr(prep, "_gather_bucket_dev", recording)
    opts = dict(algorithm="intersection", variant=variant)
    tiled = TriangleCounter(g_rmat, CountOptions(max_device_bytes=1 << 15,
                                                 **opts))
    res = tiled.count()
    streamed = [st.source for st in tiled.plan.stages
                if getattr(st, "source", None) is not None]
    assert len({s.width for s in streamed}) >= 2, res.meta["bucket_shapes"]
    for s in streamed:
        assert s.nbrs.shape == (g_rmat.n, s.width)
    widths = tuple(w for _, w in res.meta["bucket_shapes"])
    assert res.meta["neighbor_table_widths"] == widths
    pv = tiled.triangles_per_vertex()
    assert gathers and all(t == w for t, w in gathers), gathers

    mono = TriangleCounter(g_rmat, CountOptions(**opts))
    host = TriangleCounter(g_rmat, CountOptions(
        prep_backend="host", max_device_bytes=1 << 15, **opts))
    assert int(res) == int(mono.count()) == int(host.count()) \
        == int(triangle_count_scipy(g_rmat))
    assert host.count().meta["neighbor_table_widths"] == widths
    np.testing.assert_array_equal(pv, mono.triangles_per_vertex())
    np.testing.assert_array_equal(pv, host.triangles_per_vertex())


_GATHER = re.compile(r'"stablehlo\.gather".*slice_sizes = array<i64: '
                     r'([\d, ]+)>.*?: \(tensor<([\dx]+)x[a-z]\w*>')


def test_streamed_chunk_gathers_whole_table_rows(g_rmat):
    """The fused gather-and-count of a W=128 chunk gathers whole rows of its
    table: each gather's ``slice_sizes`` spans the operand's trailing
    dimensions. A narrower window ([1, 128] of a 512-wide table) is what
    the TPU compiler expands into a loop of one row per iteration."""
    tc = TriangleCounter(g_rmat, CountOptions(
        algorithm="intersection", variant="full", strategy="broadcast",
        max_device_bytes=1 << 15))
    st = next(st for st in tc.plan.stages
              if getattr(st, "source", None) is not None
              and st.source.width == 128)
    lowered = st.source.count(st.executable.lower, 0, st.chunk_rows)
    text = lowered.as_text()
    assert "tc_intersect_broadcast_w128_gathered" in text
    gathers = [([int(x) for x in sizes.split(",")],
                [int(x) for x in operand.split("x")])
               for sizes, operand in _GATHER.findall(text)]
    assert [1, 128] in [sizes for sizes, _ in gathers], gathers
    for sizes, dims in gathers:
        assert sizes[1:] == dims[1:], (sizes, dims)
