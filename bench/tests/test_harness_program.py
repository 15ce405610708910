"""The program's spans and counters as the harness reads them: the exact
split of idle gaps by ``tc.*`` span, the per-count counter readers, the
accepted readers left as they read, and a traced run on this backend whose
profile the split reads back."""

import ast
import dataclasses
import json
import os
import subprocess
import sys

import jax
import pytest

from bench import program_gaps, run as bench_run
from bench.harness import manifest as manifests
from bench.harness import program, trace
from bench.harness.record import Events, Run

ROOT = manifests.ROOT
ACCEPTED = ("setup.plan_s", "setup.compile_s", "intersect_roofline",
            "idle_share.count")


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_gap_split_across_two_spans_and_outside_every_span():
    device = {"/device:TPU:0": [("a", 10, 20), ("b", 60, 70)]}
    spans = [("tc.count", 0, 50), ("tc.stage", 5, 50),
             ("tc.dispatch", 5, 15), ("tc.sync", 25, 40)]
    got = program.gaps(device, spans, (0, 100))
    # idle: [0,10) [20,60) [70,100); [0,5) count, [5,10) dispatch,
    # [20,25) stage, [25,40) sync, [40,50) stage, [50,60) + [70,100) none
    assert got == pytest.approx({"tc.count": 5e-9, "tc.dispatch": 5e-9,
                                 "tc.stage": 15e-9, "tc.sync": 15e-9,
                                 program.NO_SPAN: 40e-9})
    assert sum(got.values()) == pytest.approx(80e-9)


def test_gaps_average_over_devices_and_sum_to_idle():
    device = {"/device:TPU:0": [("a", 0, 40)],
              "/device:TPU:1": [("a", 0, 80)]}
    spans = [("tc.sync", 30, 90)]
    got = program.gaps(device, spans, (0, 100))
    # TPU:0 idle [40,100): sync 50, none 10; TPU:1 idle [80,100): 10, 10
    assert got == pytest.approx({"tc.sync": 30e-9, program.NO_SPAN: 10e-9})
    summary = trace.summarize(trace.RawTrace(
        device_ops=device, host=[("bench.window", 0, 100)]))
    assert sum(got.values()) == pytest.approx(
        summary.window_s - summary.busy_s)


def test_executables_inside_the_window():
    modules = {"/device:TPU:0": [("jit_a", -10, 10), ("jit_b", 20, 30),
                                 ("jit_a", 90, 120)]}
    assert program.executables(modules, (0, 100)) == pytest.approx(
        {"jit_a": 20e-9, "jit_b": 10e-9})


def _run(**kw) -> Run:
    base = dict(loop="closed", setup_s=3.0, spans={"plan_s": 1.5},
                setup_events={"seconds": {
                    "/jax/core/compile/backend_compile_duration": 2.0},
                    "counts": {}},
                window_s=30.0, records=[(0, 1, 5)] * 3,
                work={"edges": 100, "merge_bytes": 8e9},
                peaks={"hbm_bytes_per_s": 8e11},
                trace=trace.TraceSummary(busy_s=3.0, window_s=36.0,
                                         device_ops=[], idle_gaps=[],
                                         longest_gaps=[], devices=1))
    base.update(kw)
    return Run(**base)


@pytest.mark.parametrize("metric,counter", [
    ("count.dispatches", "tc.dispatches"),
    ("count.host_syncs", "tc.host_syncs")])
def test_counter_readers(monkeypatch, metric, counter):
    from repro.core import tracing

    read = manifests.load().reader(metric)
    setup = {"tc.counts": 1, counter: 20}
    monkeypatch.setattr(tracing, "counters",
                        lambda: {"tc.counts": 4, counter: 80})
    assert read(_run(setup_events={"counts": setup})) == 20.0
    # the window's counts do not add up: something else counted
    assert read(_run(setup_events={"counts": {}})) is None
    assert read(_run(records=[])) is None
    assert read(_run(loop="open")) is None
    import repro.core

    monkeypatch.delattr(repro.core, "tracing")
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    assert read(_run(setup_events={"counts": setup})) is None


def test_accepted_readers_read_the_same_run_alike():
    """What the program's counters add to a run (their set-up events) moves
    none of the accepted per-layer readers."""
    man = manifests.load()
    plain = _run()
    counted = _run(setup_events={
        "seconds": dict(plain.setup_events["seconds"]),
        "counts": {"tc.counts": 1, "tc.dispatches": 20,
                   "tc.host_syncs": 20}})
    for name in ACCEPTED:
        read = man.reader(name)
        assert read(plain) is not None
        assert read(counted) == read(plain), name


def test_traced_run_splits_its_idle_time(tmp_path):
    """A traced run of the counting cell on this backend, in a fresh process
    as the command runs it: the counter readers give one dispatch and one
    sync per stage and chunk, and the split of the kept profile adds up to
    the run's idle share."""
    code = f"""
import dataclasses, json, sys
sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / "src")!r}]
import jax
from bench import run as bench_run
from bench.harness import manifest as manifests
from bench.harness.record import Events
man = manifests.load()
cell = man.cell("g500-s18-count")
config = dict(cell.config, graph=dict(cell.config["graph"], scale=9),
              options=dict(cell.config["options"], max_device_bytes=1 << 15))
res = bench_run.run_cell(man, dataclasses.replace(cell, config=config), 5,
                         0.5, True, jax.devices(), Events().register(),
                         {{"hbm_bytes_per_s": 8e11}},
                         trace_dir={str(tmp_path)!r})
print(json.dumps(res))
"""
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=_env())
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    setup = next(line for line in p.stdout.splitlines()
                 if line.startswith("setup: "))
    info = ast.literal_eval(setup.split("; ", 1)[1])
    chunks = info["num_chunks"]
    resident = len(info["bucket_shapes"]) - len(info["tiled_buckets"])
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    assert res["correct"]
    assert chunks >= 2
    assert metrics["count.dispatches"] == resident + chunks
    assert metrics["count.host_syncs"] == resident + chunks
    split = program_gaps.split(str(tmp_path))
    assert split["idle_share"]["total"] == pytest.approx(
        metrics["idle_share.count"], abs=0.1)
    assert {"tc.dispatch", "tc.sync"} <= set(split["program_gaps"])
    assert split["spans"]["tc.count"]["n"] >= 1
    assert any(k.endswith("_gathered") for k in split["device_executables"])


def test_cli_prints_one_json_line(tmp_path):
    f = jax.jit(lambda x: (x * 2).sum())
    x = jax.numpy.ones(1024)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("tc.sync"):
                f(x).block_until_ready()
    p = subprocess.run([sys.executable, str(ROOT / "bench" /
                                            "program_gaps.py"),
                        str(tmp_path)], capture_output=True, text=True,
                       timeout=120, env=_env())
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout)
    assert set(out) == {"window_s", "busy_s", "program_gaps", "idle_share",
                        "device_executables", "modules_busy_s", "dropped_s",
                        "spans", "stages"}
    assert out["spans"]["tc.sync"]["n"] == 1


def test_split_reads_what_run_cell_keeps(tmp_path):
    """In-process: a kept profile of a traced run is read back whole."""
    man = manifests.load()
    cell = man.cell("g500-s18-count")
    config = dict(cell.config, graph=dict(cell.config["graph"], scale=8))
    res = bench_run.run_cell(man, dataclasses.replace(cell, config=config),
                             3, 0.3, True, jax.devices(), Events(),
                             {"hbm_bytes_per_s": 8e11},
                             trace_dir=str(tmp_path))
    split = program_gaps.split(str(tmp_path))
    assert split["window_s"] == pytest.approx(res["device"]["window_s"])
    assert split["busy_s"] == pytest.approx(res["device"]["busy_s"])
