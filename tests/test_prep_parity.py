"""Device prep == numpy prep, and the batched ``count_many`` lane.

The device-resident prep pipeline (``repro.core.prep`` over the jitted
stages in ``repro.graphs.device``) must reproduce the numpy parity path
bit-for-bit: orientation (row_ptr + ordered edge list), bucket contents
(u/v neighbor lists, edge endpoints, widths, sentinel padding), the 2-core
peel mask, and the sort-based CSR build — on adversarial graphs (empty,
isolated vertices, star, clique with its all-equal degree ties, paths) and
on a hypothesis sweep of random multigraph edge lists.

The batching half covers ``TriangleCounter.count_many``: batch-vs-loop
agreement, lazy (chunked) consumption of generators, and the acceptance
assertion that ≥ 8 same-policy graphs are counted by ONE vmapped dispatch
from the shape-policy-keyed batch-executable cache.
"""

import numpy as np
import pytest

from repro.graphs import (
    complete_graph,
    edges_to_csr,
    grid_graph,
    path_graph,
    rmat_graph,
    star_graph,
)
from repro.graphs.device import (
    DEFAULT_SHAPE_POLICY,
    DeviceCSR,
    DeviceGraph,
    ShapePolicy,
    next_pow2,
)
from repro.graphs.formats import csr_to_padded_neighbors, orient_forward
from repro.core import (
    CountOptions,
    GraphBatch,
    TriangleCounter,
    executable_cache_info,
    plan_triangle_count,
    prep,
    triangle_count_scipy,
)
import repro.core.api as api_module

# duplicate-degree ties everywhere (clique), leaf cascades (star/path/grid
# spurs), empty rows (isolated vertices), zero edges (empty)
ADVERSARIAL = [
    edges_to_csr([], [], n=6, name="empty6"),
    edges_to_csr([0, 1], [1, 2], n=9, name="isolated9"),
    star_graph(16),
    complete_graph(9),
    path_graph(10),
    grid_graph(5, spur_fraction=0.5, seed=3),
    rmat_graph(6, 8, seed=7),
]
_IDS = [g.name for g in ADVERSARIAL]


@pytest.mark.parametrize("g", ADVERSARIAL, ids=_IDS)
@pytest.mark.parametrize("variant", ["filtered", "full"])
def test_device_buckets_match_host(g, variant):
    host = prep.prepare_intersection_buckets_host(g, variant=variant)
    dev = prep.prepare_intersection_buckets_device(g, variant=variant)
    assert len(host) == len(dev)
    for hb, db in zip(host, dev):
        e = hb["u_lists"].shape[0]
        assert db.width == hb["width"]
        assert db.edges == e
        assert db.e_pad == DEFAULT_SHAPE_POLICY.round_edges(e)
        np.testing.assert_array_equal(np.asarray(db.u_lists)[:e],
                                      hb["u_lists"])
        np.testing.assert_array_equal(np.asarray(db.v_lists)[:e],
                                      hb["v_lists"])
        np.testing.assert_array_equal(np.asarray(db.src)[:e], hb["src"])
        np.testing.assert_array_equal(np.asarray(db.dst)[:e], hb["dst"])
        # whole-row padding uses the repo-wide disjoint sentinels
        assert (np.asarray(db.u_lists)[e:] == -1).all()
        assert (np.asarray(db.v_lists)[e:] == -2).all()


@pytest.mark.parametrize("g", ADVERSARIAL, ids=_IDS)
@pytest.mark.parametrize("oriented", [True, False])
def test_narrow_neighbor_tables_match_host(g, oriented):
    """A neighbor table narrower than one already cached (its leading
    columns) equals the one scattered afresh and the host's, truncated
    rows included."""
    base = orient_forward(g) if oriented else g
    dg = DeviceGraph.from_graph(g)
    dg.padded_neighbors(16, oriented=oriented)
    for w in (4, 2, 1):
        host = csr_to_padded_neighbors(base, pad_to=w, fill=g.n)
        fresh = DeviceGraph.from_graph(g).padded_neighbors(w,
                                                           oriented=oriented)
        np.testing.assert_array_equal(
            np.asarray(dg.padded_neighbors(w, oriented=oriented)), host)
        np.testing.assert_array_equal(np.asarray(fresh), host)


@pytest.mark.parametrize("g", ADVERSARIAL, ids=_IDS)
def test_device_orientation_matches_host(g):
    dag = orient_forward(g)
    fwd = DeviceGraph.from_graph(g).forward()
    kept = dag.m_directed
    assert fwd.m == kept == g.m_directed // 2
    np.testing.assert_array_equal(np.asarray(fwd.row_ptr), dag.row_ptr)
    np.testing.assert_array_equal(np.asarray(fwd.degrees), dag.degrees)
    host_src, host_dst = dag.edge_endpoints()
    np.testing.assert_array_equal(np.asarray(fwd.src)[:kept], host_src)
    np.testing.assert_array_equal(np.asarray(fwd.dst)[:kept], host_dst)
    assert bool(np.asarray(fwd.kvalid)[:kept].all())
    assert not np.asarray(fwd.kvalid)[kept:].any()


@pytest.mark.parametrize("g", ADVERSARIAL, ids=_IDS)
def test_device_peel_matches_host(g):
    host = prep.peel_to_two_core(g)
    dev = np.asarray(prep.peel_to_two_core_device(DeviceGraph.from_graph(g)))
    np.testing.assert_array_equal(dev, host)


@pytest.mark.parametrize("g", ADVERSARIAL, ids=_IDS)
def test_device_csr_from_edges_matches_host(g):
    src, dst = g.edge_endpoints()
    # shuffle to exercise the sort (the builder must not rely on CSR order)
    rng = np.random.default_rng(0)
    order = rng.permutation(src.shape[0])
    csr = DeviceCSR.from_edges(src[order], dst[order], g.n)
    assert csr.m == g.m_directed
    np.testing.assert_array_equal(np.asarray(csr.row_ptr), g.row_ptr)
    np.testing.assert_array_equal(np.asarray(csr.col_idx)[:csr.m], g.col_idx)
    assert (np.asarray(csr.col_idx)[csr.m:] == g.n).all()


@pytest.mark.parametrize("g", ADVERSARIAL, ids=_IDS)
def test_tile_schedule_wrapper_matches_prep(g):
    from repro.core.engine import build_tile_schedule

    l1, u1, a1, s1 = build_tile_schedule(g, block=16)
    l2, u2, a2, s2 = prep.build_tile_schedule(g, block=16)
    np.testing.assert_array_equal(l1, l2)
    np.testing.assert_array_equal(u1, u2)
    np.testing.assert_array_equal(a1, a2)
    assert s1 == s2


@pytest.mark.parametrize("g", ADVERSARIAL, ids=_IDS)
@pytest.mark.parametrize("algorithm", ["intersection", "subgraph"])
def test_device_and_host_plans_agree_with_oracle(g, algorithm):
    truth = triangle_count_scipy(g)
    dev = plan_triangle_count(g, algorithm, prep_backend="device")
    host = plan_triangle_count(g, algorithm, prep_backend="host")
    assert dev.count() == host.count() == truth
    assert dev.meta["prep_backend"] == "device"
    assert host.meta["prep_backend"] == "host"


def test_device_planning_runs_no_host_numpy_prep(monkeypatch):
    """Tentpole acceptance: under ``prep_backend="device"`` (the default)
    plan CONSTRUCTION never touches the numpy prep helpers — the old poison
    test only guarded ``count()`` after planning."""

    def _boom(*a, **k):
        raise AssertionError("host numpy prep ran under prep_backend='device'")

    for name in ("prepare_intersection_buckets_host", "orient_forward",
                 "bucket_edges_by_degree", "csr_to_padded_neighbors",
                 "peel_to_two_core"):
        monkeypatch.setattr(prep, name, _boom)
    g = rmat_graph(6, 6, seed=5)
    truth = triangle_count_scipy(g)
    assert plan_triangle_count(g, "intersection").count() == truth
    assert plan_triangle_count(g, "intersection", variant="full").count() \
        == truth
    assert plan_triangle_count(g, "subgraph").count() == truth


def test_shape_policy_rounding_and_validation():
    p = ShapePolicy()
    assert p.round_edges(0) == p.min_edges
    assert p.round_edges(9) == 16
    assert p.round_edges(1000) == 1024
    assert ShapePolicy(edge_rounding="exact").round_edges(9) == 9
    assert next_pow2(0) == 1 and next_pow2(5) == 8 and next_pow2(8) == 8
    with pytest.raises(ValueError):
        ShapePolicy(edge_rounding="pow3")
    with pytest.raises(ValueError):
        ShapePolicy(min_edges=0)
    # options validation + key participation
    with pytest.raises(ValueError):
        CountOptions(prep_backend="gpu")
    with pytest.raises(ValueError):
        CountOptions(shape_policy="pow2")
    o_def = CountOptions()
    assert o_def.key() == CountOptions(shape_policy=ShapePolicy()).key()
    assert o_def.key() != CountOptions(
        shape_policy=ShapePolicy(edge_rounding="exact")).key()
    assert o_def.key() != CountOptions(prep_backend="host").key()


def test_exact_policy_plans_still_agree():
    g = rmat_graph(6, 6, seed=11)
    truth = triangle_count_scipy(g)
    exact = ShapePolicy(edge_rounding="exact", min_edges=1)
    plan = plan_triangle_count(g, "intersection", shape_policy=exact)
    assert plan.count() == truth
    # exact rounding reproduces the host shapes bit for bit
    host = plan_triangle_count(g, "intersection", prep_backend="host")
    assert plan.shape_keys == host.shape_keys


# --- hypothesis sweep -------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:  # optional dep: skip, don't error
    _HAVE_HYPOTHESIS = False


if _HAVE_HYPOTHESIS:

    def _graph_strategy(max_n=28, max_m=100):
        return st.integers(2, max_n).flatmap(
            lambda n: st.tuples(
                st.just(n),
                st.lists(st.tuples(st.integers(0, n - 1),
                                   st.integers(0, n - 1)),
                         min_size=0, max_size=max_m),
            ))

    @given(_graph_strategy())
    @settings(max_examples=25, deadline=None)
    def test_hypothesis_device_prep_parity(spec):
        n, edges = spec
        src = np.array([e[0] for e in edges], dtype=np.int64)
        dst = np.array([e[1] for e in edges], dtype=np.int64)
        g = edges_to_csr(src, dst, n=n)
        # bucket contents
        host = prep.prepare_intersection_buckets_host(g)
        dev = prep.prepare_intersection_buckets_device(g)
        assert [b["width"] for b in host] == [b.width for b in dev]
        for hb, db in zip(host, dev):
            e = hb["u_lists"].shape[0]
            np.testing.assert_array_equal(np.asarray(db.u_lists)[:e],
                                          hb["u_lists"])
            np.testing.assert_array_equal(np.asarray(db.v_lists)[:e],
                                          hb["v_lists"])
        # peel + end-to-end counts
        np.testing.assert_array_equal(
            np.asarray(prep.peel_to_two_core_device(DeviceGraph.from_graph(g))),
            prep.peel_to_two_core(g))
        truth = triangle_count_scipy(g)
        assert plan_triangle_count(g, "intersection").count() == truth
        assert plan_triangle_count(g, "subgraph").count() == truth


# --- count_many batching ----------------------------------------------------

def test_count_many_batch_agrees_with_loop():
    graphs = ([rmat_graph(6, 5, seed=s) for s in range(5)]
              + [star_graph(12), complete_graph(10),
                 grid_graph(6, spur_fraction=0.3, seed=8)])
    opts = CountOptions(algorithm="intersection")
    tc = TriangleCounter(graphs[0], opts)
    res = tc.count_many(graphs, batch_size=4)
    assert len(res) == len(graphs)
    for g, r in zip(graphs, res):
        assert r == triangle_count_scipy(g), g.name
        assert r == TriangleCounter(g, opts).count()
    # the session's own graph reused the session plan
    assert res[0].plan is tc.plan


def test_count_many_consumes_generators_lazily():
    pulls = []

    def gen():
        for s in range(12):
            pulls.append(s)
            yield rmat_graph(5, 4, seed=s)

    tc = TriangleCounter(rmat_graph(5, 4, seed=99),
                         CountOptions(algorithm="intersection"))
    it = tc.iter_counts(gen(), batch_size=3)
    next(it)
    # only the first chunk was pulled before the first result
    assert len(pulls) == 3
    rest = list(it)
    assert len(rest) == 11 and len(pulls) == 12


def test_count_many_issues_one_vmapped_dispatch(monkeypatch):
    """Acceptance: ≥ 8 same-policy graphs → ONE GraphBatch, ONE device
    dispatch, no per-graph sessions, no host prep — and a second batch of
    the same shape class compiles nothing new (cache-stats assertion)."""
    graphs = [rmat_graph(6, 6, seed=60 + s) for s in range(8)]
    opts = CountOptions(algorithm="intersection")
    tc = TriangleCounter(rmat_graph(6, 6, seed=59), opts)

    def _boom(*a, **k):
        raise AssertionError("per-graph fallback ran for a batchable graph")

    monkeypatch.setattr(api_module, "TriangleCounter", _boom)
    monkeypatch.setattr(prep, "prepare_intersection_buckets_host", _boom)
    res = tc.count_many(iter(graphs), batch_size=8)
    assert len(res) == 8
    batch = res[0].plan
    assert isinstance(batch, GraphBatch)
    assert all(r.plan is batch for r in res)
    assert batch.executions == 1  # one vmapped dispatch for the whole chunk
    for g, r in zip(graphs, res):
        assert r == triangle_count_scipy(g)
        assert r.meta["batched"] and r.meta["batch_size"] == 8

    # same shape class again: the batch-plan cache serves everything
    info1 = executable_cache_info()
    res2 = tc.count_many(iter(graphs), batch_size=8)
    info2 = executable_cache_info()
    assert [int(r) for r in res2] == [int(r) for r in res]
    assert info2["misses"] == info1["misses"]
    assert info2["hits"] > info1["hits"]


def test_count_many_batch_size_validation():
    tc = TriangleCounter(rmat_graph(5, 4, seed=1))
    with pytest.raises(ValueError):
        list(tc.iter_counts([], batch_size=0))


def test_graph_batch_rejects_unbatchable_options():
    graphs = [rmat_graph(5, 4, seed=s) for s in range(2)]
    with pytest.raises(ValueError):
        GraphBatch.from_graphs([], CountOptions(algorithm="intersection"))
    with pytest.raises(ValueError):
        GraphBatch.from_graphs(
            graphs, CountOptions(algorithm="intersection", backend="pallas"))
    with pytest.raises(ValueError):
        GraphBatch.from_graphs(
            graphs,
            CountOptions(algorithm="intersection", prep_backend="host"))


def test_graph_batch_heterogeneous_sizes_and_variants():
    """Mixed n / mixed layouts harmonize via padding; full variant's ×6
    divisor applies per graph."""
    graphs = [star_graph(30), complete_graph(12), rmat_graph(5, 6, seed=2),
              edges_to_csr([], [], n=4, name="empty4")]
    truth = [triangle_count_scipy(g) for g in graphs]
    for variant in ("filtered", "full"):
        batch = GraphBatch.from_graphs(
            graphs, CountOptions(algorithm="intersection", variant=variant))
        assert [int(c) for c in batch.counts()] == truth, variant
