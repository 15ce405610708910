"""Device edge lane == listing.py numpy oracle, bit for bit.

The cross-lane differential harness for the edge-analytics lane
(``algorithm="edge"``): per-edge support, the k-truss peel, and the truss
decomposition computed by the engine's cached edge executables + device peel
loop must reproduce ``repro.core.listing``'s host enumeration exactly — on
adversarial graphs (empty, isolated vertices, star, full clique, two cliques
sharing an edge, duplicate-edge/self-loop inputs) across every match
strategy and both prep backends, plus a hypothesis random-graph sweep. The
poison gate asserts the device peel never calls the host enumeration, and
the RUN_SLOW_TC tier extends the agreement check to the full Table-1
analogue datasets.
"""

import os
import warnings

import numpy as np
import pytest

from repro.graphs import (
    complete_graph,
    edges_to_csr,
    grid_graph,
    load_dataset,
    path_graph,
    rmat_graph,
    star_graph,
)
from repro.core import (
    CountOptions,
    TriangleCounter,
    TrussPlan,
    plan_edge_support,
    triangle_count_scipy,
)
import repro.core.listing as listing
import repro.core.prep as prep_module


def _two_cliques_shared_edge():
    """K6 on {0..5} and K6 on {4..9}, sharing the edge (4, 5)."""
    edges = [(a, b) for a in range(6) for b in range(a + 1, 6)]
    edges += [(a, b) for a in range(4, 10) for b in range(a + 1, 10)]
    src = np.array([e[0] for e in edges])
    dst = np.array([e[1] for e in edges])
    return edges_to_csr(src, dst, n=10, name="two-cliques")


def _dirty_input_graph():
    """Duplicate edges + self loops; ``edges_to_csr`` cleans them, and the
    lane must agree with the oracle on the cleaned graph."""
    src = np.array([0, 0, 0, 1, 1, 2, 2, 3, 4, 4, 4])
    dst = np.array([1, 1, 0, 2, 2, 0, 2, 3, 5, 5, 0])
    return edges_to_csr(src, dst, n=6, name="dirty6")


ADVERSARIAL = [
    edges_to_csr([], [], n=6, name="empty6"),
    edges_to_csr([0, 1], [1, 2], n=9, name="isolated9"),
    star_graph(16),
    complete_graph(9),
    _two_cliques_shared_edge(),
    _dirty_input_graph(),
    path_graph(10),
    grid_graph(5, spur_fraction=0.5, seed=3),
    rmat_graph(6, 8, seed=7),
]
_IDS = [g.name for g in ADVERSARIAL]

_KS = (3, 4, 5)


def _oracle_trussness(g):
    """Per-edge trussness via the listing oracle's peel, level by level."""
    su, sv = g.edge_list_unique()
    keys = su.astype(np.int64) * (g.n + 1) + sv
    truss = np.full(keys.shape[0], 2, dtype=np.int64)
    cur, k = g, 3
    while cur.m_undirected:
        nxt = listing._k_truss_host(cur, k)
        csu, csv = cur.edge_list_unique()
        ck = csu.astype(np.int64) * (g.n + 1) + csv
        nsu, nsv = nxt.edge_list_unique()
        nk = nsu.astype(np.int64) * (g.n + 1) + nsv
        removed = ck[~np.isin(ck, nk)]
        truss[np.searchsorted(keys, removed)] = k - 1
        cur, k = nxt, k + 1
    return su, sv, truss


def _assert_same_graph(a, b, ctx):
    assert a.n == b.n, ctx
    np.testing.assert_array_equal(a.row_ptr, b.row_ptr, err_msg=str(ctx))
    np.testing.assert_array_equal(a.col_idx, b.col_idx, err_msg=str(ctx))


# --- the differential harness -----------------------------------------------

@pytest.mark.parametrize("g", ADVERSARIAL, ids=_IDS)
@pytest.mark.parametrize("prep_backend", ["device", "host"])
def test_edge_support_matches_oracle(g, prep_backend):
    tc = TriangleCounter(g, CountOptions(algorithm="edge",
                                         prep_backend=prep_backend))
    su, sv, supp = tc.edge_support()
    hsu, hsv, hsupp = listing._edge_support_host(g)
    np.testing.assert_array_equal(su, hsu)
    np.testing.assert_array_equal(sv, hsv)
    np.testing.assert_array_equal(supp, hsupp)
    assert supp.dtype == hsupp.dtype == np.int64
    # Σ support = 3Δ, and the lane counts through it
    assert int(supp.sum()) == 3 * triangle_count_scipy(g)
    assert tc.count() == triangle_count_scipy(g)


@pytest.mark.parametrize("g", ADVERSARIAL, ids=_IDS)
@pytest.mark.parametrize("prep_backend", ["device", "host"])
def test_k_truss_bit_identical_to_oracle(g, prep_backend):
    """Tentpole acceptance: the surviving edge set is bit-identical to the
    listing oracle for every k, on every adversarial graph."""
    tc = TriangleCounter(g, CountOptions(algorithm="edge",
                                         prep_backend=prep_backend))
    for k in _KS:
        _assert_same_graph(tc.k_truss(k), listing._k_truss_host(g, k),
                           (g.name, prep_backend, k))


@pytest.mark.parametrize("strategy", ["broadcast", "probe", "bitmap"])
def test_k_truss_agrees_across_strategies(strategy):
    for g in (complete_graph(9), _two_cliques_shared_edge(),
              rmat_graph(6, 8, seed=7)):
        tc = TriangleCounter(g, CountOptions(algorithm="edge",
                                             strategy=strategy))
        _, _, supp = tc.edge_support()
        np.testing.assert_array_equal(supp, listing._edge_support_host(g)[2])
        _assert_same_graph(tc.k_truss(4), listing._k_truss_host(g, 4),
                           (g.name, strategy))


@pytest.mark.parametrize("g", ADVERSARIAL, ids=_IDS)
def test_truss_decomposition_matches_oracle(g):
    su, sv, tr = TriangleCounter(g, algorithm="edge").truss_decomposition()
    osu, osv, otr = _oracle_trussness(g)
    np.testing.assert_array_equal(su, osu)
    np.testing.assert_array_equal(sv, osv)
    np.testing.assert_array_equal(tr, otr)


def test_truss_decomposition_values():
    """Spot values: K9's edges all have trussness 9; two K6s sharing an edge
    are uniformly 6-truss edges; star/path edges sit at 2."""
    _, _, tr = TriangleCounter(complete_graph(9), algorithm="edge") \
        .truss_decomposition()
    assert (tr == 9).all()
    _, _, tr = TriangleCounter(_two_cliques_shared_edge(), algorithm="edge") \
        .truss_decomposition()
    assert (tr == 6).all()
    _, _, tr = TriangleCounter(star_graph(8), algorithm="edge") \
        .truss_decomposition()
    assert (tr == 2).all()


# --- peel semantics ---------------------------------------------------------

def test_k_truss_max_iters_parity_with_oracle():
    """A truncated peel (max_iters smaller than the fixpoint distance) must
    match the oracle truncated at the same round count."""
    g = grid_graph(6, spur_fraction=0.4, seed=9)
    tc = TriangleCounter(g, CountOptions(algorithm="edge"))
    full = tc.k_truss(4)
    assert tc.plan.meta["peel_converged"]
    rounds = tc.plan.meta["peel_rounds"]
    assert rounds >= 2  # the spur cascade takes multiple rounds
    for it in (1, rounds - 1):
        _assert_same_graph(tc.k_truss(4, max_iters=it),
                           listing._k_truss_host(g, 4, max_iters=it), it)
        assert not tc.plan.meta["peel_converged"]
    _assert_same_graph(full, listing._k_truss_host(g, 4), "full")


def test_peel_early_exit_false_same_result():
    """peel_early_exit=False runs exactly max_peel_iters rounds but the
    fixpoint is stable, so the result is unchanged."""
    g = rmat_graph(6, 8, seed=7)
    a = TriangleCounter(g, CountOptions(algorithm="edge")).k_truss(4)
    tc = TriangleCounter(g, CountOptions(algorithm="edge", max_peel_iters=8,
                                         peel_early_exit=False))
    b = tc.k_truss(4)
    _assert_same_graph(a, b, "early-exit")
    assert tc.plan.meta["peel_rounds"] == 8  # ran the full budget
    assert tc.plan.meta["peel_converged"]


def test_truss_decomposition_rejects_truncating_peel_bound():
    """Trussness is only defined at the fixpoint: a max_peel_iters that
    truncates a level must raise, not silently inflate labels."""
    g = grid_graph(6, spur_fraction=0.4, seed=9)  # multi-round cascade
    tc = TriangleCounter(g, CountOptions(algorithm="edge", max_peel_iters=1))
    with pytest.raises(ValueError, match="max_peel_iters"):
        tc.truss_decomposition()
    # a sufficient bound agrees with the oracle again
    tc2 = TriangleCounter(g, CountOptions(algorithm="edge"))
    np.testing.assert_array_equal(tc2.truss_decomposition()[2],
                                  _oracle_trussness(g)[2])


def test_device_peel_never_calls_host_enumeration(monkeypatch):
    """Tentpole acceptance (the PR 4 numpy-poison pattern): under the
    default device prep, edge_support / k_truss / truss_decomposition never
    touch listing's host enumeration NOR the numpy prep helpers."""

    def _boom(*a, **k):
        raise AssertionError("host enumeration ran under the device peel")

    for name in ("enumerate_triangles", "edge_support", "k_truss",
                 "_edge_support_host", "_k_truss_host"):
        monkeypatch.setattr(listing, name, _boom)
    for name in ("prepare_intersection_buckets_host", "forward_edge_keys_host",
                 "orient_forward", "bucket_edges_by_degree",
                 "csr_to_padded_neighbors"):
        monkeypatch.setattr(prep_module, name, _boom)

    g = rmat_graph(6, 8, seed=7)
    tc = TriangleCounter(g, CountOptions(algorithm="edge"))
    assert tc.count() == triangle_count_scipy(g)
    assert int(tc.edge_support()[2].sum()) == 3 * triangle_count_scipy(g)
    t4 = tc.k_truss(4)
    assert t4.m_undirected <= g.m_undirected
    _, _, tr = tc.truss_decomposition()
    assert tr.shape == (g.m_undirected,)


def test_truss_plan_surface():
    """TrussPlan is the session plan for algorithm="edge" and exposes the
    replay/meta surface the facade consumes."""
    g = rmat_graph(6, 6, seed=5)
    tc = TriangleCounter(g, CountOptions(algorithm="edge"))
    res = tc.count()
    assert isinstance(res.plan, TrussPlan)
    assert res.algorithm == "edge"
    assert res.plan is tc._edge_plan()  # no sidecar for the edge session
    assert res.meta["edges"] == g.m_undirected
    assert res.plan.executions >= 1
    # a non-edge session builds ONE memoized sidecar
    tc2 = TriangleCounter(g, CountOptions(algorithm="intersection"))
    assert tc2.k_truss(3) is not None
    assert tc2._edge_plan() is tc2._edge_plan()
    # plan_edge_support is the engine-level entry
    plan = plan_edge_support(g)
    assert plan.count() == triangle_count_scipy(g)
    assert plan.num_stages == len(plan.shape_keys)


def test_edge_lane_rejects_oversized_id_range():
    from repro.graphs import GraphTooLargeError

    # n past the int32 pair-key bound now resolves to the wide lane
    # instead of raising -- that was the capacity-bug class.
    assert prep_module.check_edge_key_range(1 << 20) == "wide"
    # Forcing int32 on an oversized graph still rejects, with the lane
    # named and the typed error (a ValueError subclass, so old callers
    # catching ValueError keep working).
    with pytest.raises(GraphTooLargeError, match="int32"):
        prep_module.check_edge_key_range(1 << 20, "int32")
    # Past even the int64 bound there is no mode left.
    with pytest.raises(GraphTooLargeError, match="int64"):
        prep_module.check_edge_key_range(1 << 40)


def test_listing_shims_warn_and_agree():
    g = rmat_graph(6, 6, seed=5)
    with pytest.warns(DeprecationWarning):
        su, sv, supp = listing.edge_support(g)
    np.testing.assert_array_equal(supp, listing._edge_support_host(g)[2])
    with pytest.warns(DeprecationWarning):
        t = listing.k_truss(g, 4)
    _assert_same_graph(t, listing._k_truss_host(g, 4), "shim")


def test_facade_edge_methods_do_not_warn():
    g = rmat_graph(6, 6, seed=5)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        tc = TriangleCounter(g, CountOptions(algorithm="edge"))
        tc.edge_support()
        tc.k_truss(4)
        tc.truss_decomposition()


# --- hypothesis sweep -------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:  # optional dep: skip, don't error
    _HAVE_HYPOTHESIS = False


if _HAVE_HYPOTHESIS:

    def _graph_strategy(max_n=24, max_m=90):
        # raw edge lists: self loops and duplicates exercised on purpose
        return st.integers(2, max_n).flatmap(
            lambda n: st.tuples(
                st.just(n),
                st.lists(st.tuples(st.integers(0, n - 1),
                                   st.integers(0, n - 1)),
                         min_size=0, max_size=max_m),
            ))

    @given(_graph_strategy(),
           st.sampled_from(["auto", "broadcast", "probe", "bitmap"]),
           st.sampled_from(["device", "host"]),
           st.integers(3, 5))
    @settings(max_examples=25, deadline=None)
    def test_hypothesis_truss_differential(spec, strategy, prep_backend, k):
        n, edges = spec
        src = np.array([e[0] for e in edges], dtype=np.int64)
        dst = np.array([e[1] for e in edges], dtype=np.int64)
        g = edges_to_csr(src, dst, n=n)
        tc = TriangleCounter(g, CountOptions(
            algorithm="edge", strategy=strategy, prep_backend=prep_backend))
        su, sv, supp = tc.edge_support()
        hsu, hsv, hsupp = listing._edge_support_host(g)
        np.testing.assert_array_equal(su, hsu)
        np.testing.assert_array_equal(supp, hsupp)
        _assert_same_graph(tc.k_truss(k), listing._k_truss_host(g, k),
                           (n, strategy, prep_backend, k))


# --- full-dataset agreement (slow tier) -------------------------------------

_SLOW = bool(int(os.environ.get("RUN_SLOW_TC", "0")))

# the host oracle re-enumerates every triangle per peel round, so the dense
# scale-free sets cost minutes of single-core time; tier-1 runs none of
# these — RUN_SLOW_TC=1 opts in (same policy as test_engine's fig5 gate)
_TRUSS_SLOW_SETS = ["coauthors-like", "road-like", "citpatents-like"]


@pytest.mark.parametrize("name", _TRUSS_SLOW_SETS)
def test_full_dataset_truss_agreement(name):
    if not _SLOW:
        pytest.skip("full-dataset truss peel exceeds tier-1 budget; "
                    "RUN_SLOW_TC=1")
    g = load_dataset(name)
    tc = TriangleCounter(g, CountOptions(algorithm="edge"))
    np.testing.assert_array_equal(tc.edge_support()[2],
                                  listing._edge_support_host(g)[2])
    for k in (4, 6):
        _assert_same_graph(tc.k_truss(k), listing._k_truss_host(g, k),
                           (name, k))


def test_listing_and_truss():
    from repro.core import (clustering_coefficients, k_truss, transitivity,
                            enumerate_triangles)
    from repro.graphs import complete_graph, grid_graph

    k4 = complete_graph(4)
    assert enumerate_triangles(k4).shape == (4, 3)
    np.testing.assert_allclose(clustering_coefficients(k4), np.ones(4))
    assert transitivity(k4) == pytest.approx(1.0)
    # k-truss of K4 at k=4: every edge in 2 triangles ⇒ survives; k=5 empty
    assert k_truss(k4, 4).m_undirected == 6
    assert k_truss(k4, 5).m_undirected == 0
    g = grid_graph(8, seed=0)
    assert k_truss(g, 3).m_undirected <= g.m_undirected
