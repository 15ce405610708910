"""The per-vertex pass (``TrianglePlan.triangles_per_vertex``) and the local
clustering coefficients built on it, against ``listing``'s host
enumeration, and the mask cores it runs.

* seeded Kronecker graphs of scales 8–10, with every bucket resident and
  with the buckets streamed in chunks (device and host prep), and a top
  bucket wider than the configured widths;
* a hub past ``VERTEX_DEVICE_MAX_DEGREE``, whose counts take the host's
  int64 accumulation;
* the mask rule on a TPU (broadcast at every width) and the row-chunked
  broadcast masks, equal to the unchunked compare;
* ``TriangleCounter.clustering_coefficients`` keeps its memo.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import CountOptions, TriangleCounter, engine, listing, tracing
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
