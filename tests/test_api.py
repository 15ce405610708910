"""The front-door facade: CountOptions validation + hash stability,
algorithm="auto" lane choice, session plan caching, count_many batches,
per-vertex analysis through the plan, and the deprecation shims."""

import warnings

import numpy as np
import pytest

from repro.graphs import (
    available_datasets,
    complete_graph,
    grid_graph,
    load_dataset,
    path_graph,
    rmat_graph,
    star_graph,
)
from repro.core import (
    CountOptions,
    CountResult,
    TriangleCounter,
    available_algorithms,
    choose_algorithm,
    executable_cache_info,
    set_auto_chooser,
    triangle_count_scipy,
)
import repro.core.listing as listing


G_SKEWED = rmat_graph(8, 8, seed=41)  # scale-free: high degree skew
G_UNIFORM = grid_graph(12, spur_fraction=0.3, seed=42)  # mesh-like: uniform
G_DENSE = complete_graph(64)  # small dense: MXU tiles fill


# --- CountOptions validation & hashing --------------------------------------

@pytest.mark.parametrize("bad", [
    dict(algorithm="bogus"),
    dict(variant="half"),
    dict(backend="cuda"),
    dict(strategy="hash-join"),
    dict(widths=()),
    dict(widths=(8, 8, 32)),  # not strictly ascending
    dict(widths=(0, 8)),
    dict(block=-1),
    dict(block=1.5),
    dict(bitmap_bits=33),  # not a multiple of 32
    dict(bitmap_bits=1 << 20),  # over BITMAP_MAX_BITS
    dict(interpret="yes"),
    dict(permute="yes"),
])
def test_count_options_validation(bad):
    with pytest.raises(ValueError):
        CountOptions(**bad)


def test_count_options_hash_stability():
    o1 = CountOptions(algorithm="intersection", widths=[8, 32])  # list ok
    o2 = CountOptions(algorithm="intersection", widths=(8, 32))
    assert o1 == o2
    assert hash(o1) == hash(o2)
    assert o1.key() == o2.key()
    assert o1.widths == (8, 32)  # normalized to a tuple
    # interpret=None resolves from the platform inside key()
    from repro.core import resolve_interpret
    assert CountOptions(interpret=None).key() == \
        CountOptions(interpret=resolve_interpret(None)).key()
    # replace() re-validates
    assert o1.replace(strategy="probe").strategy == "probe"
    with pytest.raises(ValueError):
        o1.replace(strategy="nope")


def test_equal_options_share_cached_executables():
    """Acceptance: two counters from equal CountOptions share one cached
    executable — no cache growth, no new misses on the second build."""
    g = rmat_graph(8, 6, seed=43)
    truth = triangle_count_scipy(g)
    o1 = CountOptions(algorithm="intersection")
    o2 = CountOptions(algorithm="intersection")
    assert o1 == o2 and hash(o1) == hash(o2)
    c1 = TriangleCounter(g, o1)
    assert c1.count() == truth
    info1 = executable_cache_info()
    c2 = TriangleCounter(g, o2)
    assert c2.count() == truth
    info2 = executable_cache_info()
    assert info2["size"] == info1["size"]
    assert info2["misses"] == info1["misses"]
    assert info2["hits"] > info1["hits"]


# --- the edge lane's cache keys ---------------------------------------------

@pytest.mark.parametrize("bad", [
    dict(max_peel_iters=0),
    dict(max_peel_iters=-3),
    dict(max_peel_iters=2.5),
    dict(max_peel_iters=True),  # bools are not iteration counts
    dict(peel_early_exit="yes"),
])
def test_peel_knob_validation(bad):
    with pytest.raises(ValueError):
        CountOptions(**bad)


def test_peel_knobs_participate_in_options_key():
    base = CountOptions(algorithm="edge")
    assert base.key() == CountOptions(algorithm="edge").key()
    assert base.key() != CountOptions(algorithm="edge",
                                      max_peel_iters=7).key()
    assert base.key() != CountOptions(algorithm="edge",
                                      peel_early_exit=False).key()


def test_equal_edge_options_share_cached_edge_executables():
    """Satellite acceptance (the test_prep_parity one-dispatch pattern):
    two sessions from equal CountOptions — peel knobs included — share the
    cached edge executables: no cache growth, no new misses, hits grow."""
    g = rmat_graph(7, 6, seed=46)
    truth = triangle_count_scipy(g)
    o1 = CountOptions(algorithm="edge", max_peel_iters=50)
    o2 = CountOptions(algorithm="edge", max_peel_iters=50)
    assert o1 == o2 and hash(o1) == hash(o2) and o1.key() == o2.key()
    c1 = TriangleCounter(g, o1)
    assert c1.count() == truth
    info1 = executable_cache_info()
    c2 = TriangleCounter(g, o2)
    assert c2.count() == truth
    info2 = executable_cache_info()
    assert info2["size"] == info1["size"]
    assert info2["misses"] == info1["misses"]
    assert info2["hits"] > info1["hits"]


@pytest.mark.parametrize("knobs", [
    dict(max_peel_iters=51),
    dict(peel_early_exit=False),
], ids=lambda d: next(iter(d)))
def test_unequal_peel_knobs_miss_the_edge_executable_cache(knobs):
    """Unequal peel knobs are distinct cache keys: a session differing only
    in a peel knob compiles its own edge executables (cache misses grow)."""
    g = rmat_graph(7, 6, seed=46)
    truth = triangle_count_scipy(g)
    base = CountOptions(algorithm="edge", max_peel_iters=50)
    assert TriangleCounter(g, base).count() == truth
    info1 = executable_cache_info()
    other = base.replace(**knobs)
    assert other.key() != base.key()
    assert TriangleCounter(g, other).count() == truth
    info2 = executable_cache_info()
    assert info2["misses"] > info1["misses"]
    assert info2["size"] > info1["size"]


def test_edge_sidecar_shares_session_options_executables():
    """k_truss from a non-edge session builds its sidecar from the SAME
    options, so a second session's sidecar compiles nothing new."""
    g = rmat_graph(7, 6, seed=47)
    t1 = TriangleCounter(g, CountOptions(algorithm="intersection"))
    t1.k_truss(3)
    info1 = executable_cache_info()
    t2 = TriangleCounter(g, CountOptions(algorithm="intersection"))
    t2.k_truss(3)
    info2 = executable_cache_info()
    assert info2["size"] == info1["size"]
    assert info2["misses"] == info1["misses"]


# --- algorithm="auto" -------------------------------------------------------

def test_auto_lane_choice_by_graph_shape():
    """The documented cost model: skewed scale-free -> intersection,
    uniform mesh-like -> subgraph, small dense -> matrix."""
    assert choose_algorithm(G_SKEWED) == "intersection"
    assert choose_algorithm(G_UNIFORM) == "subgraph"
    assert choose_algorithm(G_DENSE) == "matrix"


@pytest.mark.parametrize("g", [G_SKEWED, G_UNIFORM, G_DENSE,
                               star_graph(40), path_graph(40),
                               rmat_graph(9, 4, seed=44)],
                         ids=lambda g: g.name)
def test_auto_matches_oracle_and_reports_lane(g):
    res = TriangleCounter(g).count()
    assert isinstance(res, CountResult)
    assert res == triangle_count_scipy(g)
    assert res.algorithm in available_algorithms()
    assert res.options.algorithm == "auto"  # as written; resolution separate


@pytest.mark.parametrize("name", ["tiny-rmat", "tiny-grid"])
def test_auto_matches_oracle_on_datasets(name):
    g = load_dataset(name)
    res = TriangleCounter(g).count()
    assert res == triangle_count_scipy(g)


def test_auto_chooser_is_overridable():
    prev = set_auto_chooser(lambda g: "matrix")
    try:
        tc = TriangleCounter(G_SKEWED)  # would be intersection by default
        assert tc.algorithm == "matrix"
        assert tc.count() == triangle_count_scipy(G_SKEWED)
    finally:
        set_auto_chooser(prev)
    assert choose_algorithm(G_SKEWED) == "intersection"


# --- the session object -----------------------------------------------------

def test_session_owns_one_plan():
    tc = TriangleCounter(G_SKEWED, CountOptions(algorithm="intersection"))
    truth = triangle_count_scipy(G_SKEWED)
    r1, r2 = tc.count(), tc.count()
    assert r1 == r2 == truth
    assert r1.plan is r2.plan  # same cached plan replayed
    assert r1.plan.executions == 2
    assert r1.prep_seconds > 0.0 and r1.exec_seconds > 0.0


def test_counter_kwarg_overrides_match_options():
    a = TriangleCounter(G_SKEWED, algorithm="matrix", block=32)
    b = TriangleCounter(G_SKEWED,
                        CountOptions(algorithm="matrix", block=32))
    assert a.options == b.options
    assert a.count() == b.count() == triangle_count_scipy(G_SKEWED)
    with pytest.raises(TypeError):
        TriangleCounter(G_SKEWED, options="intersection")


def test_count_result_int_semantics():
    res = TriangleCounter(G_SKEWED).count()
    truth = triangle_count_scipy(G_SKEWED)
    assert res == truth and truth == int(res)
    assert res == np.int64(truth)
    assert not (res == truth + 1)
    assert res != "not-a-count"


def test_count_many_matches_per_graph_loop():
    batch = [rmat_graph(7, 6, seed=s) for s in (1, 2, 3)] + [G_UNIFORM]
    tc = TriangleCounter(batch[0])  # auto: per-graph lane resolution
    results = tc.count_many(batch)
    assert len(results) == len(batch)
    for g, res in zip(batch, results):
        assert res == triangle_count_scipy(g), g.name
        assert res == TriangleCounter(g).count()
    # the session's own graph reused the session plan
    assert results[0].plan is tc.plan


def test_count_many_mesh_fallback_warns_and_stays_correct():
    """Pin the documented mesh behavior (the sharded-GraphBatch baseline):
    distributed lanes are NOT batchable, so ``count_many`` under a mesh
    falls back to per-graph sessions — one ``UserWarning`` per session,
    results still exact. A 1-device mesh keeps this in-process (promotion
    only kicks in on multi-device meshes, but an explicit distributed
    algorithm exercises the same fallback path)."""
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    batch = [rmat_graph(7, 6, seed=s) for s in (11, 12)]
    tc = TriangleCounter(
        batch[0], CountOptions(algorithm="intersection_distributed"),
        mesh=mesh)
    with pytest.warns(UserWarning, match="not\\s+batchable"):
        results = tc.count_many(batch)
    for g, res in zip(batch, results):
        assert res == triangle_count_scipy(g), g.name
        assert res.meta.get("batched") is None  # per-graph, not stacked
    # the warning fires once per session, not once per graph/chunk
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        again = tc.count_many(batch)
    assert [int(r) for r in again] == [int(r) for r in results]


# --- per-vertex analysis through the cached plan ----------------------------

@pytest.mark.parametrize("opts", [
    CountOptions(algorithm="intersection"),
    CountOptions(algorithm="subgraph"),
    CountOptions(algorithm="matrix", block=32),  # sidecar fallback
    CountOptions(algorithm="intersection", variant="full"),  # sidecar
], ids=lambda o: f"{o.algorithm}-{o.variant}")
def test_vertex_analysis_matches_listing(opts):
    g = G_SKEWED
    tc = TriangleCounter(g, opts)
    assert np.array_equal(tc.triangles_per_vertex(),
                          listing.triangles_per_vertex(g))
    assert np.allclose(tc.clustering_coefficients(),
                       listing.clustering_coefficients(g))
    assert tc.transitivity() == pytest.approx(listing.transitivity(g))


def test_vertex_analysis_subgraph_scatters_through_prune():
    """Pruned (2-core-peeled) vertices must report zero triangles at their
    ORIGINAL ids."""
    g = G_UNIFORM  # spur_fraction > 0 ⇒ the peel removes leaves
    tc = TriangleCounter(g, CountOptions(algorithm="subgraph"))
    t = tc.triangles_per_vertex()
    assert t.shape == (g.n,)
    assert np.array_equal(t, listing.triangles_per_vertex(g))
    assert tc.count().meta["vertices_pruned"] > 0


# --- deprecation shims ------------------------------------------------------

def test_legacy_shims_warn_and_agree():
    from repro.core import (
        triangle_count_intersection,
        triangle_count_matrix,
        triangle_count_subgraph,
    )

    g = G_SKEWED
    truth = triangle_count_scipy(g)
    with pytest.warns(DeprecationWarning):
        assert triangle_count_intersection(g) == truth
    with pytest.warns(DeprecationWarning):
        assert triangle_count_intersection(g, variant="full") == truth
    with pytest.warns(DeprecationWarning):
        assert triangle_count_matrix(g, block=32) == truth
    with pytest.warns(DeprecationWarning):
        count, stats = triangle_count_subgraph(g, return_stats=True)
    assert count == truth
    assert stats["num_embeddings"] == 6 * truth
    assert {"vertices_pruned", "prune_fraction", "edges_after",
            "edges_before"} <= set(stats)


def test_legacy_distributed_shims_warn_and_agree():
    from repro.core import (
        triangle_count_intersection_distributed,
        triangle_count_matrix_distributed,
    )

    g = rmat_graph(7, 6, seed=45)  # single host device: mesh defaults
    truth = triangle_count_scipy(g)
    with pytest.warns(DeprecationWarning):
        assert triangle_count_intersection_distributed(g) == truth
    with pytest.warns(DeprecationWarning):
        assert triangle_count_matrix_distributed(g, block=32) == truth


def test_facade_itself_does_not_warn():
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        assert TriangleCounter(G_SKEWED).count() == \
            triangle_count_scipy(G_SKEWED)


# --- registry ---------------------------------------------------------------

def test_registry_surface():
    from repro.core import register_algorithm

    assert set(available_algorithms()) >= {
        "intersection", "matrix", "subgraph", "edge",
        "intersection_distributed", "matrix_distributed",
    }
    with pytest.raises(ValueError):
        register_algorithm("intersection", lambda g, o, mesh=None: None)
    with pytest.raises(ValueError):
        CountOptions(algorithm="not-registered")


def test_custom_algorithm_registration_roundtrip():
    from repro.core import register_algorithm
    from repro.core.registry import OneShotPlan, _REGISTRY

    name = "test-constant-lane"

    def planner(g, options, *, mesh=None):
        return OneShotPlan(fn=lambda: 7, algorithm=name)

    register_algorithm(name, planner)
    try:
        res = TriangleCounter(G_SKEWED, CountOptions(algorithm=name)).count()
        assert res.count == 7 and res.algorithm == name
    finally:
        _REGISTRY.pop(name, None)


# --- datasets satellite -----------------------------------------------------

def test_load_dataset_unknown_name_lists_available():
    with pytest.raises(ValueError, match="tiny-rmat"):
        load_dataset("road-lik")  # typo
    names = available_datasets()
    assert names == sorted(names)
    assert "road-like" in names and "tiny-grid" in names


# --- interpret default satellite --------------------------------------------

def test_interpret_none_resolves_from_platform():
    from repro.core import KernelPlatformError, resolve_interpret
    # None: the interpreter on the CPU, the compiled kernel on a TPU
    assert resolve_interpret(None, "cpu") is True
    assert resolve_interpret(None, "tpu") is False
    # this suite runs on the CPU backend, so the default platform is "cpu"
    assert resolve_interpret(None) is True
    # explicit flags are kept, except interpreting a Pallas lane on a TPU
    assert resolve_interpret(False, "cpu") is False
    assert resolve_interpret(True, "tpu", backend="jnp") is True
    with pytest.raises(KernelPlatformError) as err:
        resolve_interpret(True, "tpu", lane="hash")
    assert err.value.lane == "hash"
    assert err.value.kernel == "hash_probe_counts_pallas"
    assert "hash_probe_counts_pallas" in str(err.value)
