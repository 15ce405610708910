"""The per-vertex pass (``TrianglePlan.triangles_per_vertex``) and the local
clustering coefficients built on it, against ``listing``'s host
enumeration, and the mask cores it runs.

* seeded Kronecker graphs of scales 8–10, with every bucket resident and
  with the buckets streamed in chunks (device and host prep), and a top
  bucket wider than the configured widths;
* a hub past ``VERTEX_DEVICE_MAX_DEGREE``, whose counts take the host's
  int64 accumulation;
* the credit grouped by source run, on streamed plans whose small chunks
  hold each case its source runs meet (a run across a chunk border, a run
  longer than a chunk, a chunk of padding only, a top bucket wider than
  the widths, the int64 host path), against ``listing`` and scipy; on
  buckets in any row order and with padding rows anywhere; and the row
  order and run ids it relies on;
* the mask rule on a TPU (broadcast at every width) and the row-chunked
  broadcast masks, equal to the unchunked compare;
* ``TriangleCounter.clustering_coefficients`` keeps its memo.
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax.numpy as jnp

from repro.core import (CountOptions, TriangleCounter, engine, listing, prep,
                        tracing)
from repro.graphs import rmat_graph
from repro.graphs.formats import edges_to_csr
from repro.kernels.intersect import ops

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


def _check(g, opts):
    tc = TriangleCounter(g, opts)
    t = tc.plan.triangles_per_vertex()
    want = listing.triangles_per_vertex(g)
    np.testing.assert_array_equal(t, want)
    lcc = tc.plan.clustering_coefficients(t)
    assert lcc.dtype == np.float64
    np.testing.assert_array_equal(lcc, listing.clustering_coefficients(g))
    np.testing.assert_array_equal(tc.plan.clustering_coefficients(), lcc)
    return tc


@pytest.mark.parametrize("scale", [8, 9, 10])
@pytest.mark.parametrize("budget,prep_backend", [
    (None, "device"), (1 << 13, "device"), (1 << 13, "host")],
    ids=["resident", "streamed", "streamed-host-prep"])
def test_vertex_counts_on_kronecker(scale, budget, prep_backend):
    g = rmat_graph(scale, 16, seed=100 + scale)
    tc = _check(g, CountOptions(algorithm="intersection",
                                prep_backend=prep_backend,
                                max_device_bytes=budget))
    meta = tc.count().meta
    assert (meta["num_chunks"] >= 2) == (budget is not None)
    assert int(tc.plan.degrees.max()) <= engine.VERTEX_DEVICE_MAX_DEGREE


def test_top_bucket_wider_than_the_widths():
    g = rmat_graph(10, 16, seed=7)
    tc = _check(g, CountOptions(algorithm="intersection", widths=(8, 16),
                                max_device_bytes=1 << 14))
    widths = [w for _, w in tc.plan.shape_keys]
    assert widths[-1] > 16 and widths[-1] & (widths[-1] - 1) == 0
    assert any(isinstance(st, engine._TiledStage) and st.shape_key[1] > 16
               for st in tc.plan.stages)


def test_subgraph_lane_scatters_through_prune():
    _check(rmat_graph(9, 8, seed=3), CountOptions(algorithm="subgraph",
                                                   prep_backend="host"))


def windmill(k: int):
    """A hub joined to k disjoint pairs, each pair an edge: the hub is in
    k triangles at degree 2k, every other vertex in one at degree 2."""
    hub = 2 * k
    a = np.arange(0, 2 * k, 2)
    src = np.concatenate([a, a, a + 1])
    dst = np.concatenate([a + 1, np.full(k, hub), np.full(k, hub)])
    return edges_to_csr(src, dst, n=2 * k + 1, name="windmill")


def test_hub_past_the_int32_bound_takes_the_host_path():
    k = engine.VERTEX_DEVICE_MAX_DEGREE // 2 + 1
    g = windmill(k)
    assert g.max_degree == 2 * k > engine.VERTEX_DEVICE_MAX_DEGREE
    tc = TriangleCounter(g, CountOptions(algorithm="intersection"))
    calls = len(tc.plan.stages)
    before = tracing.counters()
    t = tc.plan.triangles_per_vertex()
    after = tracing.counters()
    assert after["tc.host_syncs"] - before.get("tc.host_syncs", 0) == calls
    assert t[-1] == k and (t[:-1] == 1).all()
    lcc = tc.plan.clustering_coefficients(t)
    assert lcc[-1] == 2.0 * k / (2 * k * (2 * k - 1))
    assert (lcc[:-1] == 1.0).all()


def _scipy_counts(g):
    """t(v) from scipy sparse rows: each triangle u < v < w once, at its
    edge (u, v), whose forward rows share w."""
    a = sp.csr_matrix((np.ones(g.col_idx.shape[0], np.int64), g.col_idx,
                       g.row_ptr), shape=(g.n, g.n))
    up = sp.triu(a, k=1).tocsr()
    src, dst = up.nonzero()
    common = up[src].multiply(up[dst])
    per_edge = np.asarray(common.sum(axis=1)).ravel()
    t = (np.bincount(src, per_edge, g.n) + np.bincount(dst, per_edge, g.n)
         + np.asarray(common.sum(axis=0)).ravel())
    return t.astype(np.int64)


def _streamed_rows(tc):
    """(stage, its bucket's sorted sources) of each device-gathered stage."""
    out = []
    for st in tc.plan.stages:
        b = getattr(st, "source", None)
        if b is not None:
            out.append((st, np.asarray(b.sorted_src)[b.start:b.start
                                                     + b.edges]))
    return out


def _straddles(st, src):
    c = st.chunk_rows
    return any(src[k - 1] == src[k] for k in range(c, src.shape[0], c))


def _run_longer_than_a_chunk(st, src):
    cuts = np.flatnonzero(np.diff(src)) + 1
    runs = np.diff(np.concatenate([[0], cuts, [src.shape[0]]]))
    return int(runs.max()) > st.chunk_rows


def _padding_chunk(st, src):
    return any(s >= src.shape[0] for s in range(0, st.rows, st.chunk_rows))


def _wider_than_the_widths(st, src):
    return st.chunk_shape_key[1] > 16


def _past_the_int32_bound(st, src):
    return True  # the graph's hub is; the pass then syncs per call


_GROUPED_CASES = {
    "run-across-a-chunk-border": (
        lambda: rmat_graph(9, 16, seed=109), {"max_device_bytes": 1 << 15},
        _straddles),
    "run-longer-than-a-chunk": (
        lambda: rmat_graph(9, 16, seed=109), {"max_device_bytes": 1 << 13},
        _run_longer_than_a_chunk),
    "padding-only-chunk": (
        lambda: rmat_graph(9, 16, seed=109), {"max_device_bytes": 1 << 13},
        _padding_chunk),
    "top-bucket-wider-than-the-widths": (
        lambda: rmat_graph(10, 16, seed=7),
        {"widths": (8, 16), "max_device_bytes": 1 << 14},
        _wider_than_the_widths),
    "int64-host-path": (
        lambda: windmill(engine.VERTEX_DEVICE_MAX_DEGREE // 2 + 1),
        {"max_device_bytes": 1 << 20}, _past_the_int32_bound),
}


@pytest.mark.parametrize("case", list(_GROUPED_CASES))
def test_grouped_credit_on_streamed_plans(case):
    make, opts, holds = _GROUPED_CASES[case]
    g = make()
    tc = TriangleCounter(g, CountOptions(algorithm="intersection", **opts))
    streamed = _streamed_rows(tc)
    assert any(holds(st, src) for st, src in streamed), case
    on_device = int(g.max_degree) <= engine.VERTEX_DEVICE_MAX_DEGREE
    assert on_device == (case != "int64-host-path")
    t = tc.plan.triangles_per_vertex()
    np.testing.assert_array_equal(t, _scipy_counts(g))
    np.testing.assert_array_equal(t, listing.triangles_per_vertex(g))


def _shuffled(rng, b):
    order = rng.permutation(b["src"].shape[0])
    return {k: b[k][order] for k in ("u_lists", "v_lists", "src", "dst")}


def _padded(rng, b):
    """Padding rows (u = -1, v = -2, src = dst = 0) at row 0 and spread
    between the real rows."""
    e, w = b["u_lists"].shape
    real = np.ones(e + e // 3 + 1, bool)
    real[0] = False
    real[1 + rng.choice(real.shape[0] - 1, e // 3, replace=False)] = False
    out = {}
    for k, fill in (("u_lists", -1), ("v_lists", -2), ("src", 0),
                    ("dst", 0)):
        shape = (real.shape[0], w) if b[k].ndim == 2 else real.shape
        out[k] = np.full(shape, fill, b[k].dtype)
        out[k][real] = b[k]
    return out


_ROW_ORDERS = {
    "csr-order": lambda rng, b: b,
    "shuffled": _shuffled,
    "padding-anywhere": _padded,
    "shuffled-many-blocks": _shuffled,
}


@pytest.mark.parametrize("order", list(_ROW_ORDERS))
def test_vertex_executable_is_exact_for_any_row_order(order, monkeypatch):
    """The credit groups runs of equal ``src`` as they come: sources split
    over many runs, and padding rows between them, change only the number
    of runs, never a count."""
    if order.endswith("many-blocks"):  # blocks of 3 rows at W=32
        monkeypatch.setattr(engine, "_CREDIT_BLOCK_SLOTS", 3 * 32)
    g = rmat_graph(8, 8, seed=11)
    rng = np.random.default_rng(len(order))
    t = np.zeros(g.n, np.int64)
    for b in prep.prepare_intersection_buckets_host(g, widths=(8, 32)):
        rows = _ROW_ORDERS[order](rng, b)
        run = engine._build_vertex_executable(g.n)
        t += np.asarray(run(*(jnp.asarray(rows[k]) for k in (
            "u_lists", "v_lists", "src", "dst")), jnp.zeros(g.n, jnp.int32)))
    np.testing.assert_array_equal(t, listing.triangles_per_vertex(g))


@pytest.mark.parametrize("rows", [1000, 1024, 3072, 1 << 14])
def test_run_ids_cumsum_matches_numpy(rows):
    """The credit's run ids: a two-level cumulative sum over rows of 1,024
    (the last padded) equals numpy's."""
    first = np.random.default_rng(rows).random(rows) < 0.3
    np.testing.assert_array_equal(
        np.asarray(engine._cumsum_1d(jnp.asarray(first))), np.cumsum(first))


@pytest.mark.parametrize("widths", [(8, 32, 128, 512), (8, 16)])
@pytest.mark.parametrize("scale", [9, 10])
def test_streamed_bucket_rows_keep_source_order(scale, widths):
    """The grouped credit finds a chunk's source runs as rows of equal
    ``src``: a streamed bucket's rows keep CSR order."""
    buckets = prep.prepare_intersection_buckets_device(
        rmat_graph(scale, 16, seed=scale), widths=widths,
        max_bucket_bytes=0)
    assert len(buckets) >= 2
    for b in buckets:
        assert isinstance(b, prep.StreamedBucket)
        src = np.asarray(b.sorted_src)[b.start:b.start + b.edges]
        assert src.shape[0] == b.edges and (np.diff(src) >= 0).all()


def test_clustering_coefficients_keep_the_session_memo():
    g = rmat_graph(8, 8, seed=5)
    tc = TriangleCounter(g, CountOptions(algorithm="intersection"))
    before = tracing.counters().get("tc.vertex_passes", 0)
    cc = tc.clustering_coefficients()
    np.testing.assert_array_equal(cc, tc.clustering_coefficients())
    np.testing.assert_array_equal(cc, listing.clustering_coefficients(g))
    assert tracing.counters()["tc.vertex_passes"] == before + 1


@pytest.mark.parametrize("width", [64, 128, 512, 1024])
def test_mask_rule_takes_broadcast_on_a_tpu(width):
    assert ops.choose_mask_strategy(width, None, platform="tpu") \
        == "broadcast"
    assert ops.choose_mask_strategy(width, None, platform="cpu") == "probe"
    # a bitmap that fits still wins, as in the count rule
    assert ops.choose_mask_strategy(width, 2 * width, platform="tpu") \
        == "bitmap"


def _rows(rng, e, w, fill):
    """(e, w) sorted rows of distinct ids from [0, 4w), the last few
    columns of each row replaced by the in-row sentinel ``fill``."""
    ids = np.sort(rng.random((e, 4 * w)).argsort(axis=1)[:, :w], axis=1)
    ids[:, w - w // 8:] = fill
    return jnp.asarray(ids.astype(np.int32))


@pytest.mark.parametrize("e,w", [(200, 512), (64, 512), (37, 1024),
                                 (5, 32)])
def test_chunked_broadcast_masks_equal_the_whole_compare(e, w):
    rng = np.random.default_rng(e * w)
    n = 4 * w
    u, v = _rows(rng, e, w, n), _rows(rng, e, w, n + 1)
    eq = u[:, :, None] == v[:, None, :]
    got = ops.intersect_matches(u, v, strategy="broadcast")
    np.testing.assert_array_equal(got, eq.any(axis=2))
    np.testing.assert_array_equal(
        got, ops.intersect_matches(u, v, strategy="probe"))
    got_u, got_v = ops.intersect_matches_both(u, v, strategy="broadcast")
    np.testing.assert_array_equal(got_u, eq.any(axis=2))
    np.testing.assert_array_equal(got_v, eq.any(axis=1))
    np.testing.assert_array_equal(
        got.sum(axis=1), ops.intersect_counts(u, v, strategy="broadcast"))
