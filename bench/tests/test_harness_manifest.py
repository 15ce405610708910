"""BENCHMARK.json and the files it names: the rules, and that a new
configuration, traffic mix and metric are found by name from new files
alone."""

import copy
import dataclasses
import hashlib
import json
import pathlib
import shutil

import pytest

from bench.harness import manifest as manifests

ROOT = manifests.ROOT


def test_manifest_is_valid():
    man = manifests.load()
    man.validate()
    assert man.data["command"] == ["python3", "bench/run.py"]
    assert man.data["paths"] == ["bench"]


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  manifests.load().data["workloads"]])
def test_every_cell_resolves(cell):
    man = manifests.load()
    c = man.cell(cell)
    assert callable(man.system(c.config["system"]))
    assert c.traffic["loop"] in ("closed", "open")
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    for m in c.per_layer:
        assert m["moves"] in names
        assert callable(man.reader(m["name"]))


def broken(edit):
    man = manifests.load()
    data = copy.deepcopy(man.data)
    edit(data)
    return manifests.Manifest(data, man.root)


@pytest.mark.parametrize("edit", [
    lambda d: d["workloads"][0].update(name="has space"),
    lambda d: d["end_to_end"][1].update(unit="edges per s"),
    lambda d: d["per_layer"][0].update(name="a/b"),
    lambda d: d["workloads"][0].update(traffic="no-such-mix"),
    lambda d: d["workloads"][0].update(config="no-such-config"),
    lambda d: d["per_layer"].append(dict(d["per_layer"][0],
                                         name="no.reader")),
    lambda d: d["per_layer"][0].update(moves="no_such_metric"),
    lambda d: d["end_to_end"][1].update(workloads=[]),
    lambda d: d["end_to_end"][1].update(source="program_span"),
    lambda d: d["end_to_end"][1].update(bound=0.5),
    lambda d: d["end_to_end"].pop(0),
    lambda d: d["workloads"].append(dict(d["workloads"][0], name="twin")),
], ids=["cell-name", "unit", "metric-name", "traffic-file", "config-file",
        "reader-file", "moves-unknown", "moves-not-reported",
        "e2e-source", "bound", "no-setup", "pair-twice"])
def test_validation_refuses(edit):
    with pytest.raises(manifests.ManifestError):
        broken(edit).validate()


def digest(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_cell_config_mix_generator_and_metric_are_found_by_name(
        tmp_path):
    """A later PR adds a graph generator, a configuration, a traffic mix, a
    metric and a cell as new files plus manifest entries; no file the
    benchmark had changes, and the harness finds and runs all of them."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(tmp_path / "bench")
    (tmp_path / "bench" / "graphs" / "complete.py").write_text(
        "import numpy as np\n\n\n"
        "def generate(seed, n):\n"
        "    col = [v for u in range(n) for v in range(n) if v != u]\n"
        "    row_ptr = (np.arange(n + 1) * (n - 1)).astype(np.int32)\n"
        "    return n, row_ptr, np.array(col, np.int32)\n")
    (tmp_path / "bench" / "configs" / "k20.json").write_text(
        json.dumps({"name": "k20", "system": "counter",
                    "graph": {"generator": "complete", "n": 20},
                    "options": {"algorithm": "intersection"},
                    "control": {"accumulate": "bfloat16"}}))
    (tmp_path / "bench" / "traffic" / "one-caller.json").write_text(
        json.dumps({"loop": "closed"}))
    (tmp_path / "bench" / "metrics" / "counts_in_window.py").write_text(
        "def read(run):\n    return float(len(run.records))\n")
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    data["configs"].append({"name": "k20", "source": "test",
                            "file": "bench/configs/k20.json",
                            "reduced": [], "why": "test"})
    data["workloads"].append({"name": "k20-count", "config": "k20",
                              "traffic": "one-caller", "chips": 1,
                              "why": "test"})
    for m in data["end_to_end"]:
        if m["name"] == "edges_per_s":
            m["workloads"].append("k20-count")
    data["per_layer"].append({"name": "counts_in_window", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "plan", "moves": "edges_per_s",
                              "workloads": ["k20-count"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))

    after = digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before
    man = manifests.load(tmp_path)
    man.validate()
    cell = man.cell("k20-count")
    assert {m["name"] for m in cell.per_layer} >= {"counts_in_window"}

    import jax
    from bench import run as bench_run
    from bench.harness.record import Events

    res = bench_run.run_cell(man, cell, 5, 0.2, False, jax.devices(),
                             Events(), {})
    assert res["correct"] and res["attempted"] >= 1
    assert res["checks"] == {"count_err_max": {"value": 0, "limit": 0}}
    assert set(res["metrics"]) == {"setup_s", "edges_per_s"}
    assert res["metrics"]["edges_per_s"]["value"] > 0
    assert man.reader("counts_in_window")(
        type("R", (), {"records": [1, 2]})()) == 2.0


def test_config_naming_a_missing_system_is_refused(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    conf = tmp_path / "bench" / "configs" / "g500-s18.json"
    conf.write_text(json.dumps(dict(json.loads(conf.read_text()),
                                    system="no-such-system")))
    with pytest.raises(manifests.ManifestError, match="no-such-system"):
        manifests.load(tmp_path).validate()
