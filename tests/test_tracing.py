"""Spans and counters of the count replay (``repro.core.tracing``).

* every count makes one dispatch and one host sync per resident stage and
  per streamed chunk, and counts the same as the untiled plan;
* a profile recorded around ``count()`` nests ``tc.count`` ⊃ ``tc.stage`` ⊃
  ``tc.dispatch`` / ``tc.sync`` with their attributes, and names the
  executables by lane, strategy and width;
* a per-vertex pass bumps ``tc.vertex_passes`` once, makes one dispatch per
  resident stage and streamed chunk and one host sync in all (one per call
  past the int32 bound), and its profile nests ``tc.vertex`` ⊃ ``tc.stage``
  ⊃ ``tc.dispatch`` over the ``tc_vertex_w<W>`` executables;
* the counters are also ``jax.monitoring`` events.
"""

import glob
import os

import jax
import pytest

from repro.core import CountOptions, TriangleCounter, triangle_count_scipy
from repro.core import engine, tracing
from repro.graphs import erdos_renyi_graph, rmat_graph

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(scope="module")
def g_rmat():
    return rmat_graph(8, edge_factor=8, seed=21)


def _delta(before, after, key):
    return after.get(key, 0) - before.get(key, 0)


@pytest.mark.parametrize("algorithm,prep_backend,graph", [
    ("intersection", "device", "rmat"),
    ("intersection", "host", "rmat"),
    ("matrix", "device", "er"),
])
def test_one_dispatch_and_sync_per_stage_and_chunk(g_rmat, algorithm,
                                                   prep_backend, graph):
    g = g_rmat if graph == "rmat" \
        else erdos_renyi_graph(400, avg_degree=10.0, seed=4)
    opts = CountOptions(algorithm=algorithm, prep_backend=prep_backend)
    whole = TriangleCounter(g, opts).count()
    tiled = TriangleCounter(g, opts.replace(max_device_bytes=1 << 13))
    first = tiled.count()
    chunks = first.meta["num_chunks"]
    assert chunks >= 2
    resident = len(tiled.plan.stages) - len(first.meta["tiled_buckets"])
    before = tracing.counters()
    for _ in range(2):
        assert int(tiled.plan.count()) == int(whole) \
            == int(triangle_count_scipy(g))
    after = tracing.counters()
    assert _delta(before, after, "tc.counts") == 2
    assert _delta(before, after, "tc.dispatches") == 2 * (resident + chunks)
    assert _delta(before, after, "tc.host_syncs") == 2 * (resident + chunks)


def test_resident_plan_counts_one_per_stage(g_rmat):
    tc = TriangleCounter(g_rmat, CountOptions(algorithm="intersection"))
    tc.count()
    before = tracing.counters()
    tc.plan.count()
    after = tracing.counters()
    assert _delta(before, after, "tc.dispatches") == len(tc.plan.stages)
    assert _delta(before, after, "tc.host_syncs") == len(tc.plan.stages)


def test_bumps_are_monitoring_events():
    seen = []

    def listen(event, **_):
        seen.append(event)

    jax.monitoring.register_event_listener(listen)
    try:
        before = tracing.counters().get("tc.test", 0)
        tracing.bump("tc.test", 2)
    finally:
        jax.monitoring.unregister_event_listener(listen)
    assert tracing.counters()["tc.test"] == before + 2
    assert seen.count("tc.test") == 2


def test_executables_are_named_by_lane_strategy_and_width():
    assert engine._executable_name("intersection", "broadcast",
                                   (1 << 18, 512)) \
        == "tc_intersect_broadcast_w512"
    assert engine._executable_name("matrix", None, (4, 64, 64)) \
        == "tc_matrix_b64"
    assert engine._executable_name("vertex", None, (8, 8, 100)) \
        == "tc_vertex_w8"
    assert engine._executable_name("edge", "probe", (8, 8, 5, 101)) is None
    vertex = engine.get_executable("vertex", "jnp", False, (16, 8, 100))
    assert vertex.__name__ == "tc_vertex_w8"
    fn = engine.get_executable("intersection", "jnp", False, (16, 8),
                               strategy="probe")
    assert fn.__name__ == "tc_intersect_probe_w8"


def _profile(tmp_path, fn):
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        fn()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans, modules = [], set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if e.name.startswith("tc."):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, stats))
                elif "hlo_op" in stats and "hlo_module" in stats:
                    modules.add(stats["hlo_module"])
    return spans, modules


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_profile_nests_count_stage_dispatch_sync(g_rmat, tmp_path):
    tc = TriangleCounter(g_rmat, CountOptions(algorithm="intersection",
                                              max_device_bytes=1 << 13))
    first = tc.count()
    spans, modules = _profile(tmp_path, tc.plan.count)
    by = {n: [s for s in spans if s[0] == n]
          for n in ("tc.count", "tc.stage", "tc.dispatch", "tc.sync")}
    count, = by["tc.count"]
    assert count[3] == {"algorithm": "intersection",
                        "stages": len(tc.plan.stages)}
    stages = by["tc.stage"]
    assert [s[3]["index"] for s in stages] == list(range(len(stages)))
    assert all(_inside(s, count) for s in stages)
    assert sum(s[3]["chunks"] for s in stages) == first.meta["num_chunks"]
    for s, st in zip(stages, tc.plan.stages):
        assert s[3]["width"] == st.chunk_shape_key[1]
        assert s[3]["strategy"] == st.strategy
        assert s[3]["rows"] == st.rows
    n = first.meta["num_chunks"]
    assert len(by["tc.dispatch"]) == len(by["tc.sync"]) == n
    for leaf in by["tc.dispatch"] + by["tc.sync"]:
        assert sum(_inside(leaf, s) for s in stages) == 1
    assert any(m.startswith("jit_tc_intersect_") and m.endswith("_gathered")
               for m in modules), modules


@pytest.mark.parametrize("prep_backend", ["device", "host"])
def test_vertex_pass_dispatches_per_chunk_and_syncs_once(g_rmat,
                                                         prep_backend):
    tc = TriangleCounter(g_rmat, CountOptions(
        algorithm="intersection", prep_backend=prep_backend,
        max_device_bytes=1 << 13))
    first = tc.count()
    chunks = first.meta["num_chunks"]
    assert chunks >= 2
    resident = len(tc.plan.stages) - len(first.meta["tiled_buckets"])
    want = tc.plan.triangles_per_vertex()
    before = tracing.counters()
    for _ in range(2):
        assert (tc.plan.triangles_per_vertex() == want).all()
    after = tracing.counters()
    assert _delta(before, after, "tc.vertex_passes") == 2
    assert _delta(before, after, "tc.dispatches") == 2 * (resident + chunks)
    assert _delta(before, after, "tc.host_syncs") == 2
    assert _delta(before, after, "tc.counts") == 0


def test_vertex_pass_past_the_int32_bound_syncs_per_call(g_rmat):
    tc = TriangleCounter(g_rmat, CountOptions(algorithm="intersection"))
    want = tc.plan.triangles_per_vertex()
    calls = len(tc.plan.stages)
    tc.plan.degrees = tc.plan.degrees.copy()
    tc.plan.degrees[0] = engine.VERTEX_DEVICE_MAX_DEGREE + 1
    before = tracing.counters()
    assert (tc.plan.triangles_per_vertex() == want).all()
    after = tracing.counters()
    assert _delta(before, after, "tc.dispatches") == calls
    assert _delta(before, after, "tc.host_syncs") == calls


def test_profile_nests_vertex_stage_dispatch(g_rmat, tmp_path):
    tc = TriangleCounter(g_rmat, CountOptions(algorithm="intersection",
                                              max_device_bytes=1 << 13))
    first = tc.count()
    tc.plan.triangles_per_vertex()
    spans, modules = _profile(tmp_path, tc.plan.triangles_per_vertex)
    by = {n: [s for s in spans if s[0] == n]
          for n in ("tc.vertex", "tc.stage", "tc.dispatch", "tc.sync",
                    "tc.count")}
    assert not by["tc.count"]
    vertex, = by["tc.vertex"]
    assert vertex[3] == {"algorithm": "intersection",
                         "stages": len(tc.plan.stages)}
    stages = by["tc.stage"]
    assert all(_inside(s, vertex) for s in stages)
    assert sum(s[3]["chunks"] for s in stages) == first.meta["num_chunks"]
    assert [s[3]["strategy"] for s in stages] == [
        "broadcast" if w < 64 else "probe" for w in
        (s[3]["width"] for s in stages)]
    assert len(by["tc.dispatch"]) == first.meta["num_chunks"]
    sync, = by["tc.sync"]
    assert _inside(sync, vertex) and not any(_inside(sync, s)
                                             for s in stages)
    assert any(m.startswith("jit_tc_vertex_w") and m.endswith("_gathered")
               for m in modules), modules
