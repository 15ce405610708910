"""``BENCHMARK.json`` and the files it names, found by name and checked.

A cell names a configuration and a traffic mix; each lives in a file of its
own (``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``). A
configuration names the kind of system that drives it
(``bench/systems/<system>.py``: a ``System`` class and the ``Control`` that
puts the reference in its place) and the generator of its graphs
(``bench/graphs/<generator>.py``: ``generate(seed, **params)``), and each
metric has a reader of its own (``bench/metrics/<metric>.py``: ``read``).
Adding a cell, a configuration, a mix, a system, a generator or a metric is
adding files and manifest entries: nothing here names one.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
import re
from typing import Callable, List, Optional

__all__ = ["Cell", "Manifest", "ManifestError", "load"]

ROOT = pathlib.Path(__file__).resolve().parents[2]

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
_E2E_SOURCES = {"device_trace", "host_clock"}


class ManifestError(ValueError):
    """The manifest or a file it names breaks a rule of the benchmark."""


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload: a configuration under a traffic mix."""

    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def _check_name(kind: str, name) -> None:
    if not isinstance(name, str) or not _NAME.match(name):
        raise ManifestError(f"{kind} name {name!r}: 1-64 of A-Z a-z 0-9 _ . -,"
                            f" starting with a letter, digit or _")


def _read_json(path: pathlib.Path, what: str) -> dict:
    if not path.is_file():
        raise ManifestError(f"{what}: no file {path}")
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def _module(path: pathlib.Path):
    """The module in ``path``, executed once per process."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", str(path)), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Manifest:
    """The parsed manifest, rooted at ``root``."""

    data: dict
    root: pathlib.Path

    @property
    def bench(self) -> pathlib.Path:
        return self.root / "bench"

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return _read_json(self.root / c["file"], f"config {name}")
        raise ManifestError(f"no configuration {name!r}")

    def traffic(self, name: str) -> dict:
        return _read_json(self.bench / "traffic" / f"{name}.json",
                          f"traffic {name}")

    def _load(self, kind: str, name: str, attr: str):
        """``attr`` of ``bench/<kind>/<name>.py``, loaded from its file."""
        _check_name(kind, name)
        path = self.bench / kind / f"{name}.py"
        if not path.is_file():
            raise ManifestError(f"{kind} {name!r}: no file "
                                f"{path.relative_to(self.root)}")
        mod = _module(path)
        if not hasattr(mod, attr):
            raise ManifestError(f"{kind} {name!r}: {path.name} has no {attr}")
        return getattr(mod, attr)

    def reader(self, metric: str) -> Callable:
        """The metric's ``read(run)`` function, from its own file."""
        return self._load("metrics", metric, "read")

    def system(self, name: str, control: bool = False):
        """The class that drives a configuration's ``system`` (or, with
        ``control``, the reference in its place)."""
        return self._load("systems", name, "Control" if control else "System")

    def graph(self, spec: dict, seed: int):
        """(n, row_ptr, col_idx) from ``spec["generator"]``'s file, called
        with the seed and the rest of ``spec``."""
        if "generator" not in spec:
            raise ManifestError(f"graph {spec}: names no generator")
        params = {k: v for k, v in spec.items() if k != "generator"}
        return self._load("graphs", spec["generator"], "generate")(seed,
                                                                   **params)

    def cell(self, name: str) -> Cell:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return Cell(
                    name=name,
                    config=self.config(w["config"]),
                    traffic=self.traffic(w["traffic"]),
                    chips=int(w["chips"]),
                    end_to_end=[m for m in self.data["end_to_end"]
                                if _applies(m, name)],
                    per_layer=[m for m in self.data["per_layer"]
                               if _applies(m, name)],
                )
        raise ManifestError(f"no workload {name!r}; known: "
                            f"{[w['name'] for w in self.data['workloads']]}")

    def validate(self) -> None:
        """Raise ``ManifestError`` at the first rule broken."""
        d = self.data
        for key in ("command", "paths", "run_seconds", "configs", "workloads",
                    "end_to_end", "per_layer"):
            if key not in d:
                raise ManifestError(f"manifest lacks {key!r}")
        for entry in d["configs"] + d["workloads"] + d["per_layer"]:
            for key in ("why", "source", "layer"):
                text = entry.get(key, "x")
                if not isinstance(text, str) or not 1 <= len(text) <= 200 \
                        or "\n" in text or "\t" in text:
                    raise ManifestError(f"{entry['name']}: {key} must be one "
                                        f"line of 1-200 characters")
        for group in (d["configs"], d["workloads"],
                      d["end_to_end"] + d["per_layer"]):
            names = [entry["name"] for entry in group]
            for name in names:
                _check_name("entry", name)
            if len(set(names)) != len(names):
                raise ManifestError(f"a name appears twice in {names}")
        cells = {w["name"] for w in d["workloads"]}
        for c in d["configs"]:
            if not c["file"].startswith("bench/"):
                raise ManifestError(f"config {c['name']}: file outside bench/")
            conf = self.config(c["name"])
            self.system(conf.get("system", ""))
            self.system(conf.get("system", ""), control=True)
            if "graph" in conf:
                self._load("graphs", conf["graph"].get("generator", ""),
                           "generate")
            if len(c["reduced"]) > 16:
                raise ManifestError(f"config {c['name']}: over 16 reduced")
            for k in c["reduced"]:
                _check_name("reduced key", k)
            if not any(w["config"] == c["name"] for w in d["workloads"]):
                raise ManifestError(f"config {c['name']} used by no cell")
        pairs = set()
        for w in d["workloads"]:
            _check_name("traffic", w["traffic"])
            if (w["config"], w["traffic"]) in pairs:
                raise ManifestError(f"cell {w['name']}: pair twice")
            pairs.add((w["config"], w["traffic"]))
            if w["chips"] not in (1, 4):
                raise ManifestError(f"cell {w['name']}: chips {w['chips']}")
            self.config(w["config"])
            self.traffic(w["traffic"])
        e2e = {m["name"]: m for m in d["end_to_end"]}
        if "setup_s" not in e2e:
            raise ManifestError("end_to_end lacks setup_s")
        for m in d["end_to_end"] + d["per_layer"]:
            if not _UNIT.match(m["unit"]):
                raise ManifestError(f"metric {m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                raise ManifestError(f"metric {m['name']}: better "
                                    f"{m['better']!r}")
            allowed = _E2E_SOURCES if m["name"] in e2e else _SOURCES
            if m["source"] not in allowed:
                raise ManifestError(f"metric {m['name']}: source "
                                    f"{m['source']!r}")
            for w in m.get("workloads", []):
                if w not in cells:
                    raise ManifestError(f"metric {m['name']}: no cell {w!r}")
            self.reader(m["name"])
        for m in d["end_to_end"]:
            if not 0.01 <= m["bound"] <= 0.25:
                raise ManifestError(f"metric {m['name']}: bound {m['bound']}")
        for m in d["per_layer"]:
            moved = e2e.get(m["moves"])
            if moved is None:
                raise ManifestError(f"metric {m['name']}: moves unknown "
                                    f"{m['moves']!r}")
            for w in cells:
                if _applies(m, w) and not _applies(moved, w):
                    raise ManifestError(
                        f"metric {m['name']}: cell {w} does not report "
                        f"{m['moves']}")
        for w in cells:
            reported = [m for m in d["end_to_end"] if _applies(m, w)]
            if len(reported) < 2:
                raise ManifestError(f"cell {w}: needs setup_s and another "
                                    f"end-to-end metric")
            if not any(_applies(m, w) for m in d["per_layer"]):
                raise ManifestError(f"cell {w}: no per-layer metric")


def load(root: Optional[pathlib.Path] = None) -> Manifest:
    """The manifest at ``root`` (default: this checkout)."""
    root = pathlib.Path(root) if root is not None else ROOT
    return Manifest(_read_json(root / "BENCHMARK.json", "manifest"), root)
