"""The per-vertex cell ``lcc-g500-s20-replay``: its plain reference on graphs
whose counts are known in closed form or by hand, and against the program
on seeded Kronecker graphs; its configuration, system, cell and readers,
found by name from their own files; whole runs at a size a test can hold,
where a planted fault and the float32 control make ``correct`` false; and a
traced run whose result line carries every per-layer metric of the cell."""

import ast
import dataclasses
import json
import os
import subprocess
import sys
from math import comb

import jax
import numpy as np
import pytest

from bench import control, run as bench_run
from bench.harness import manifest as manifests, vertex_reference
from bench.harness import trace
from bench.harness.record import Events, Run

ROOT = manifests.ROOT
CELL = "lcc-g500-s20-replay"
NEW_READERS = ("lcc.host_syncs", "lcc.dispatches", "vertex_roofline")


def csr(n, edges):
    """Symmetric sorted CSR of an undirected edge list."""
    pairs = sorted({(u, v) for a, b in edges for u, v in ((a, b), (b, a))})
    row_ptr = np.zeros(n + 1, np.int32)
    np.cumsum(np.bincount([u for u, _ in pairs], minlength=n),
              out=row_ptr[1:])
    return n, row_ptr, np.array([v for _, v in pairs], np.int32)


def lcc_of(g):
    t = vertex_reference.triangles(*g)
    return t, vertex_reference.lcc(t, np.diff(g[1]))


@pytest.mark.parametrize("n", [3, 4, 7, 20])
def test_complete_graph(n):
    t, lcc = lcc_of(csr(n, [(u, v) for u in range(n)
                            for v in range(u + 1, n)]))
    assert (t == comb(n - 1, 2)).all()
    assert (lcc == 1.0).all() and lcc.dtype == np.float64


@pytest.mark.parametrize("n", [2, 10, 300])
def test_star_has_no_triangles(n):
    t, lcc = lcc_of(csr(n + 1, [(0, v) for v in range(1, n + 1)]))
    assert (t == 0).all() and (lcc == 0.0).all()


@pytest.mark.parametrize("rim", [4, 5, 9])
def test_wheel_by_hand(rim):
    # hub 0 on a cycle 1..rim: the hub closes one triangle per rim edge
    # (rim of them, at degree rim); each rim vertex is in the two of its
    # two rim edges, at degree 3: LCC 2 * 2 / (3 * 2) = 2/3
    edges = [(0, v) for v in range(1, rim + 1)] + [
        (v, v % rim + 1) for v in range(1, rim + 1)]
    t, lcc = lcc_of(csr(rim + 1, edges))
    assert t[0] == rim and (t[1:] == 2).all()
    assert lcc[0] == 2.0 * rim / (rim * (rim - 1))
    assert (lcc[1:] == 4.0 / 6.0).all()


def test_isolated_and_pendant_vertices_read_zero():
    t, lcc = lcc_of(csr(6, [(0, 1), (1, 2), (0, 2), (2, 3)]))
    assert list(t) == [1, 1, 1, 0, 0, 0]
    assert list(lcc) == [1.0, 1.0, 1.0 / 3.0, 0.0, 0.0, 0.0]


def test_vertex_bytes_by_hand():
    # triangle 0-1-2 plus a pendant 2-3: merge bytes 4 * 7 (the forward
    # lists read, test_harness_reference), plus 4 bytes of counts per id
    g = csr(4, [(0, 1), (1, 2), (0, 2), (2, 3)])
    assert vertex_reference.vertex_bytes(*g) == 4 * 7 + 4 * 4


@pytest.mark.parametrize("scale", [7, 8])
def test_reference_agrees_with_brute_force(scale):
    g = manifests.load().graph({"generator": "kronecker", "scale": scale,
                                "edge_factor": 8}, 3)
    n, row_ptr, col_idx = g
    a = np.zeros((n, n), np.int64)
    for u in range(n):
        a[u, col_idx[row_ptr[u]:row_ptr[u + 1]]] = 1
    np.testing.assert_array_equal(vertex_reference.triangles(*g, workers=3),
                                  np.diag(a @ a @ a) // 2)


@pytest.mark.parametrize("scale", [8, 9, 10])
def test_program_agrees_with_the_reference(scale):
    from repro.core import CountOptions, TriangleCounter
    from bench.systems.counter import as_graph

    g = manifests.load().graph({"generator": "kronecker", "scale": scale,
                                "edge_factor": 16}, 40 + scale)
    t, lcc = lcc_of(g)
    plan = TriangleCounter(as_graph(g, "k"), CountOptions(
        max_device_bytes=1 << 14)).plan
    got = plan.triangles_per_vertex()
    np.testing.assert_array_equal(got, t)
    np.testing.assert_array_equal(plan.clustering_coefficients(got), lcc)


def test_config_system_cell_and_readers_are_found_by_name():
    man = manifests.load()
    entry, = [c for c in man.data["configs"] if c["name"] == "lcc-g500-s20"]
    assert entry["file"] == "bench/configs/lcc-g500-s20.json"
    assert entry["reduced"] == ["scale"]
    cell = man.cell(CELL)
    assert cell.chips == 1 and cell.traffic["loop"] == "closed"
    assert cell.config["system"] == "lcc"
    assert cell.config["graph"] == {"generator": "kronecker", "scale": 20,
                                    "edge_factor": 16, "a": 0.57,
                                    "b": 0.19, "c": 0.19}
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "edges_per_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "setup.plan_s", "setup.compile_s", "idle_share.count", *NEW_READERS}
    for name in NEW_READERS:
        assert callable(man.reader(name))
        assert (man.bench / "metrics" / f"{name}.py").is_file()
    system = man.system("lcc")
    assert system.__module__ != man.system("counter").__module__
    assert issubclass(man.system("lcc", control=True), system)


def tiny(scale=9, **options):
    man = manifests.load()
    cell = man.cell(CELL)
    config = dict(cell.config, graph=dict(cell.config["graph"], scale=scale),
                  options=dict(cell.config["options"], **options))
    return man, dataclasses.replace(cell, config=config)


def run(seconds=0.5, **kw):
    man, cell = tiny(max_device_bytes=1 << 14)
    return bench_run.run_cell(man, cell, 2 ** 31 + 17, seconds, False,
                              jax.devices(), Events(), {}, **kw)


def test_sound_run_is_correct():
    res = run()
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "edges_per_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"] == {"t_err_max": {"value": 0, "limit": 0},
                             "lcc_err_max": {"value": 0.0, "limit": 0.0}}


def test_a_count_planted_wrong_is_not_correct(monkeypatch):
    from repro.core.engine import TrianglePlan

    real = TrianglePlan.triangles_per_vertex

    def planted(self):
        t = real(self)
        t[int(np.argmax(t))] += 1
        return t

    monkeypatch.setattr(TrianglePlan, "triangles_per_vertex", planted)
    res = run()
    assert not res["correct"]
    assert res["checks"]["t_err_max"]["value"] == 1
    assert res["checks"]["lcc_err_max"]["value"] > 0


def test_lcc_in_float32_is_not_correct(monkeypatch):
    from repro.core import engine

    monkeypatch.setattr(engine, "local_clustering_coefficients",
                        lambda t, d: vertex_reference.lcc(t, d, np.float32))
    res = run()
    assert not res["correct"]
    assert res["checks"]["t_err_max"]["value"] == 0
    assert res["checks"]["lcc_err_max"]["value"] > 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_is_not_correct(seed):
    man, cell = tiny()
    res = control.run_control(man, cell, seed, 0.0, jax.devices())
    assert not res["correct"]
    assert res["checks"]["t_err_max"]["value"] == 0
    assert res["checks"]["lcc_err_max"]["value"] > 0


def _run(**kw) -> Run:
    base = dict(loop="closed", setup_s=3.0, spans={"plan_s": 1.5},
                setup_events={"seconds": {}, "counts": {}},
                window_s=30.0, records=[(0, 1, None)] * 2,
                work={"edges": 100, "merge_bytes": 8e9,
                      "vertex_bytes": 8e9 + 4e6},
                peaks={"hbm_bytes_per_s": 8e11},
                trace=trace.TraceSummary(busy_s=24.0, window_s=30.0,
                                         device_ops=[], idle_gaps=[],
                                         longest_gaps=[], devices=1))
    base.update(kw)
    return Run(**base)


@pytest.mark.parametrize("metric,counter", [
    ("lcc.dispatches", "tc.dispatches"), ("lcc.host_syncs", "tc.host_syncs")])
def test_pass_counter_readers(monkeypatch, metric, counter):
    from repro.core import tracing

    read = manifests.load().reader(metric)
    setup = {"tc.vertex_passes": 1, counter: 60}
    monkeypatch.setattr(tracing, "counters",
                        lambda: {"tc.vertex_passes": 3, counter: 180})
    assert read(_run(setup_events={"counts": setup})) == 60.0
    # a program that counts no passes (the parent's) reads nothing
    monkeypatch.setattr(tracing, "counters", lambda: {counter: 180})
    assert read(_run(setup_events={"counts": {counter: 60}})) is None
    assert read(_run(records=[])) is None
    assert read(_run(loop="open")) is None


def test_vertex_roofline_by_hand():
    read = manifests.load().reader("vertex_roofline")
    # least 8.004e9 B / 8e11 B/s = 10.005 ms over 12 s busy per pass
    assert read(_run()) == pytest.approx(100 * 0.010005 / 12.0)
    assert read(_run(trace=None)) is None
    assert read(_run(work={"edges": 100, "merge_bytes": 8e9})) is None


def test_traced_run_reports_every_per_layer_metric(tmp_path):
    """A traced run of the cell on this backend, in a fresh process as the
    command runs it: one dispatch per resident stage and streamed chunk,
    one host sync per pass, and a roofline share under 100%."""
    code = f"""
import dataclasses, json, sys
sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / "src")!r}]
import jax
from bench import run as bench_run
from bench.harness import manifest as manifests
from bench.harness.record import Events
man = manifests.load()
cell = man.cell({CELL!r})
config = dict(cell.config, graph=dict(cell.config["graph"], scale=9),
              options=dict(cell.config["options"], max_device_bytes=1 << 14))
res = bench_run.run_cell(man, dataclasses.replace(cell, config=config), 5,
                         0.5, True, jax.devices(), Events().register(),
                         {{"hbm_bytes_per_s": 8e11}},
                         trace_dir={str(tmp_path)!r})
print(json.dumps(res))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(metrics) == {"setup.plan_s", "setup.compile_s",
                            "idle_share.count", *NEW_READERS}
    setup = next(line for line in p.stdout.splitlines()
                 if line.startswith("setup: "))
    info = ast.literal_eval(setup.split("; ", 1)[1])
    chunks = info["num_chunks"]
    resident = len(info["bucket_shapes"]) - len(info["tiled_buckets"])
    assert chunks >= 2
    assert metrics["lcc.dispatches"] == resident + chunks
    assert metrics["lcc.host_syncs"] == 1.0
    assert 0 < metrics["vertex_roofline"] <= 100


def test_a_program_without_lcc_fails_before_the_graph_is_made(monkeypatch):
    """The cell over a program whose plans give no clustering coefficients
    stops at once, before a graph is generated or planned."""
    from repro.core.engine import TrianglePlan

    made = []
    man, cell = tiny()
    monkeypatch.setattr(manifests.Manifest, "graph",
                        lambda self, spec, seed: made.append(seed))
    monkeypatch.delattr(TrianglePlan, "clustering_coefficients")
    with pytest.raises(NotImplementedError):
        bench_run.run_cell(man, cell, 3, 0.1, False, jax.devices(), Events(),
                           {})
    assert not made
