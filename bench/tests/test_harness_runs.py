"""Whole runs of the harness on this backend, below the look for a chip:
with the timed path broken underneath, ``correct`` comes out false; with the
control in the program's place, too. And the command itself refuses a
machine without a TPU, or a checkout without the program."""

import dataclasses
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

from bench import control, run as bench_run
from bench.harness import manifest as manifests
from bench.harness.record import Events

ROOT = manifests.ROOT

# A service cell for the service system's own tests; BENCHMARK.json holds
# no service cell yet. Scale-8 pool graphs at a low rate.
SERVICE = manifests.Cell(
    name="svc-test",
    config={"name": "svc-test", "system": "service", "options": {},
            "serve": {"max_queue_depth": 64, "batch_window_ms": 2.0,
                      "max_batch": 8},
            "control": {"accumulate": "bfloat16"}},
    traffic={"loop": "open", "rate_per_s": 150.0,
             "pool": [{"graph": {"generator": "kronecker", "scale": 8,
                                 "edge_factor": 8}, "graphs": 16}],
             "popularity": {"zipf_s": 1.0},
             "tenants": {"count": 16, "zipf_s": 1.0}},
    chips=1,
    end_to_end=[{"name": "setup_s", "unit": "s"},
                {"name": "serve_p95_ms", "unit": "ms"}],
    per_layer=[{"name": n, "unit": u} for n, u in (
        ("serve.coalesce", "req/dispatch"), ("serve.queue_wait_ms", "ms"),
        ("idle_share.serve", "%"))])


def tiny(cell_name: str):
    """The cell as committed, at a size a test can hold: scale 9 for the
    counting cell."""
    man = manifests.load()
    if cell_name == SERVICE.name:
        return man, SERVICE
    cell = man.cell(cell_name)
    config = dict(cell.config, graph=dict(cell.config["graph"], scale=9))
    return man, dataclasses.replace(cell, config=config)


def run(cell_name: str, seconds: float = 0.5, **kw) -> dict:
    man, cell = tiny(cell_name)
    return bench_run.run_cell(man, cell, 2 ** 31 + 11, seconds, False,
                              jax.devices(), Events(), {}, **kw)


@pytest.mark.parametrize("cell", ["g500-s18-count", "svc-test"])
def test_sound_run_is_correct(cell):
    res = run(cell)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "edges_per_s" if cell ==
                                   "g500-s18-count" else "serve_p95_ms"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert all(v["value"] == 0 for v in res["checks"].values())


def test_count_altered_where_produced(monkeypatch):
    from repro.core.engine import TrianglePlan

    real = TrianglePlan.count
    monkeypatch.setattr(TrianglePlan, "count", lambda self: real(self) + 1)
    res = run("g500-s18-count")
    assert not res["correct"]
    assert res["checks"]["count_err_max"]["value"] == 1


def test_count_with_a_stage_left_out(monkeypatch):
    from repro.core.engine import _Stage

    monkeypatch.setattr(_Stage, "run", lambda self: 0)
    res = run("g500-s18-count")
    assert not res["correct"]


def test_service_answer_altered_in_batch(monkeypatch):
    from repro.serve.coalescer import Coalescer

    real = Coalescer._count_batch

    def altered(self, chunk, *a, **k):
        out = real(self, chunk, *a, **k)
        return [out[0] + 2] + out[1:]

    monkeypatch.setattr(Coalescer, "_count_batch", altered)
    monkeypatch.setattr(Coalescer, "_count_single",
                        lambda self, pg, options: 0)
    res = run("svc-test", seconds=1.0)
    assert not res["correct"]
    assert res["checks"]["answer_err_max"]["value"] > 0


def test_service_half_the_batch_left_out(monkeypatch):
    from repro.serve.coalescer import Coalescer

    real = Coalescer.count_group

    def half(self, key, prepped, options):
        counts, sizes = real(self, key, prepped, options)
        keep = max(len(counts) // 2, 1) if len(counts) > 1 else 1
        return counts[:keep], sizes[:keep]

    monkeypatch.setattr(Coalescer, "count_group", half)
    monkeypatch.setattr(manifests.load().system("service"), "grace_s", 1.0)
    res = run("svc-test", seconds=1.0)
    assert not res["correct"]
    assert res["checks"]["unanswered"]["value"] > 0


def test_control_service_is_not_correct():
    """The bfloat16 control over the test pool is off on some request of
    every seed."""
    man = manifests.load()
    for seed in (1, 2, 3):
        res = control.run_control(man, SERVICE, seed, 1.0, jax.devices())
        assert not res["correct"], res["checks"]
        assert res["checks"]["answer_err_max"]["value"] > 0


def test_control_counter_is_not_correct():
    """The float32 control at the smallest Kronecker scale whose total
    passes 2**24 (the cell runs scale 18, a size no test holds)."""
    man = manifests.load()
    cell = man.cell("g500-s18-count")
    config = dict(cell.config, graph=dict(cell.config["graph"], scale=17))
    res = control.run_control(man, dataclasses.replace(cell, config=config),
                              7, 0.0, jax.devices())
    assert not res["correct"]
    assert res["checks"]["count_err_max"]["value"] > 0


def cli(cwd: pathlib.Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "g500-s18-count",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def result_lines(stdout: str):
    out = []
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "correct" in obj:
            out.append(obj)
    return out


def test_cli_refuses_a_cpu():
    p = cli(ROOT)
    assert p.returncode != 0
    assert not result_lines(p.stdout)
    assert "TPU" in p.stderr


def test_cli_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = cli(tmp_path)
    assert p.returncode != 0
    assert not result_lines(p.stdout)
