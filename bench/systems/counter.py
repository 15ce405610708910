"""``"system": "counter"`` — a closed loop over ``TriangleCounter.count()``.

One ``repro.core.TriangleCounter`` session over the configuration's
``graph`` (made from the run's seed by its generator), with ``options`` as
its ``CountOptions``. Set-up plans the count and counts once, which
compiles or loads every executable the window replays; the window replays
``count()`` back to back. ``check`` compares every count of the window with
the plain reference.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Tuple

from bench.harness import reference, traffic as mixes
from bench.harness.traffic import span

__all__ = ["Control", "System"]

Checks = Dict[str, Tuple[float, float]]


def as_graph(csr, name: str):
    """The program's ``Graph`` over the harness's (n, row_ptr, col_idx)."""
    from repro.graphs import Graph

    n, row_ptr, col_idx = csr
    return Graph(n=int(n), row_ptr=row_ptr, col_idx=col_idx, name=name)


class System:
    """A closed loop over ``TriangleCounter.count()``."""

    def __init__(self, config: dict, traffic: dict, manifest):
        self.config = config
        self.traffic = traffic
        self.manifest = manifest
        self.spans: Dict[str, float] = {}
        self.session = None

    def make_inputs(self, seed: int, seconds: float) -> dict:
        return {"csr": self.manifest.graph(self.config["graph"], seed)}

    def work(self, inputs: dict) -> dict:
        """Edges counted per call and the merge bytes a count must read."""
        n, row_ptr, col_idx = inputs["csr"]
        return {"edges": int(col_idx.shape[0]) // 2,
                "merge_bytes": reference.merge_bytes(n, row_ptr, col_idx)}

    def setup(self, inputs: dict) -> Dict[str, Any]:
        from repro.core import CountOptions, TriangleCounter

        t0 = time.perf_counter()
        with span("bench.plan"):
            self.session = TriangleCounter(
                as_graph(inputs["csr"], self.config["name"]),
                CountOptions(**self.config["options"]))
            self.session.plan.block_until_ready()
        t1 = time.perf_counter()
        with span("bench.warmup"):
            first = self.session.count()
        t2 = time.perf_counter()
        self.spans = {"plan_s": t1 - t0, "warmup_s": t2 - t1}
        meta = first.meta
        return {"algorithm": first.algorithm,
                "bucket_shapes": meta.get("bucket_shapes"),
                "bucket_strategies": meta.get("bucket_strategies"),
                "tiled_buckets": meta.get("tiled_buckets"),
                "num_chunks": meta.get("num_chunks"),
                "cache": self.session.cache_stats()}

    def window(self, inputs: dict, seconds: float):
        session = self.session
        return mixes.closed_loop(lambda: session.count().count, seconds)

    def close(self) -> None:
        self.session = None

    def check(self, inputs: dict, records) -> Tuple[Checks, int, int]:
        want = reference.count(*inputs["csr"])
        err = max((abs(int(a) - want) for _, _, a in records), default=0)
        return {"count_err_max": (err, 0)}, len(records), 0


class Control(System):
    """The reference's total, accumulated at the precision the
    configuration's ``control.accumulate`` names, as every count's answer."""

    def setup(self, inputs: dict) -> dict:
        self.answer = reference.control_count(
            reference.row_counts(*inputs["csr"]),
            self.config["control"]["accumulate"])
        return {"control": self.config["control"]["accumulate"]}

    def window(self, inputs: dict, seconds: float):
        # every count of a window answers alike: one stands for them all
        return mixes.closed_loop(lambda: self.answer, 0.0)
