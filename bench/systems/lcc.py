"""``"system": "lcc"`` — a closed loop over per-vertex triangle counts and
LDBC Graphalytics' local clustering coefficients.

One ``repro.core.TriangleCounter`` session over the configuration's
``graph`` (made from the run's seed by its generator), with ``options`` as
its ``CountOptions``. Set-up plans the session and runs one per-vertex pass,
which compiles or loads every executable the window replays. Each call of
the window is one whole pass on the session's plan (``one_pass``):
``plan.triangles_per_vertex()`` (on the device) and
``plan.clustering_coefficients(t)`` (float64, on the host), so no memo of
the session stands in for a pass. ``check`` compares every call's t(v) and
LCC with the plain per-vertex reference
(``bench.harness.vertex_reference``).
"""

from __future__ import annotations

import time
from typing import Any, Dict, Tuple

import numpy as np

from bench.harness import reference, traffic as mixes, vertex_reference
from bench.harness.traffic import span
from bench.systems import counter
from bench.systems.counter import as_graph

__all__ = ["Control", "System"]

Checks = Dict[str, Tuple[float, float]]


def one_pass(plan):
    """One whole pass: (t, LCC) from the device counts of ``plan``."""
    t = plan.triangles_per_vertex()
    return t, plan.clustering_coefficients(t)


class System(counter.System):
    """A closed loop of whole per-vertex passes and their LCC."""

    def make_inputs(self, seed: int, seconds: float) -> dict:
        # a program whose plans give no clustering coefficients cannot run
        # the cell: fail at once, before the graph is made and planned
        from repro.core.engine import TrianglePlan

        if not hasattr(TrianglePlan, "clustering_coefficients"):
            raise NotImplementedError(
                "the program's TrianglePlan has no clustering_coefficients")
        return super().make_inputs(seed, seconds)

    def work(self, inputs: dict) -> dict:
        """Edges per pass, and the bytes a merge intersection (and a pass,
        which also writes the (n,) counts) must move."""
        n, row_ptr, col_idx = inputs["csr"]
        return {"edges": int(col_idx.shape[0]) // 2,
                "merge_bytes": reference.merge_bytes(n, row_ptr, col_idx),
                "vertex_bytes": vertex_reference.vertex_bytes(
                    n, row_ptr, col_idx)}

    def setup(self, inputs: dict) -> Dict[str, Any]:
        from repro.core import CountOptions, TriangleCounter

        t0 = time.perf_counter()
        with span("bench.plan"):
            self.session = TriangleCounter(
                as_graph(inputs["csr"], self.config["name"]),
                CountOptions(**self.config["options"]))
            plan = self.session.plan
            plan.block_until_ready()
        t1 = time.perf_counter()
        with span("bench.warmup"):
            one_pass(plan)
        t2 = time.perf_counter()
        self.spans = {"plan_s": t1 - t0, "warmup_s": t2 - t1}
        meta = plan.meta
        return {"algorithm": plan.algorithm,
                "bucket_shapes": meta.get("bucket_shapes"),
                "tiled_buckets": meta.get("tiled_buckets"),
                "num_chunks": meta.get("num_chunks"),
                "max_degree": int(np.max(plan.degrees, initial=0)),
                "cache": self.session.cache_stats()}

    def window(self, inputs: dict, seconds: float):
        plan = self.session.plan
        return mixes.closed_loop(lambda: one_pass(plan), seconds)

    def check(self, inputs: dict, records) -> Tuple[Checks, int, int]:
        n, row_ptr, col_idx = inputs["csr"]
        want_t = vertex_reference.triangles(n, row_ptr, col_idx)
        want_lcc = vertex_reference.lcc(want_t, np.diff(row_ptr))
        t_err, lcc_err = 0, 0.0
        for _, _, (t, lcc) in records:
            t_err = max(t_err, int(np.max(np.abs(
                np.asarray(t, np.int64) - want_t), initial=0)))
            lcc_err = max(lcc_err, float(np.max(np.abs(
                np.asarray(lcc, np.float64) - want_lcc), initial=0.0)))
        return ({"t_err_max": (t_err, 0), "lcc_err_max": (lcc_err, 0.0)},
                len(records), 0)


class Control(System):
    """The reference's exact counts, with LCC computed at the precision the
    configuration's ``control.accumulate`` names, as every pass's answer."""

    def setup(self, inputs: dict) -> dict:
        n, row_ptr, col_idx = inputs["csr"]
        t = vertex_reference.triangles(n, row_ptr, col_idx)
        dtype = np.dtype(self.config["control"]["accumulate"]).type
        self.answer = (t, vertex_reference.lcc(t, np.diff(row_ptr), dtype))
        return {"control": self.config["control"]["accumulate"]}

    def window(self, inputs: dict, seconds: float):
        # every pass of a window answers alike: one stands for them all
        return mixes.closed_loop(lambda: self.answer, 0.0)
