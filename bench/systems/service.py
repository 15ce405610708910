"""``"system": "service"`` — an open loop of ``count`` requests.

One ``repro.serve.TriangleService`` with ``options`` (its ``CountOptions``)
and ``serve`` (its ``ServeConfig``) over the traffic mix's pool of graphs.
Set-up warms the service over the pool; the window submits each request at
its due time. ``check`` compares every answer due in the window with the
plain reference.
"""

from __future__ import annotations

import time
from concurrent.futures import Future
from types import SimpleNamespace
from typing import Any, Dict, List, Tuple

from bench.harness import reference, traffic as mixes
from bench.harness.traffic import span
from bench.systems.counter import as_graph

__all__ = ["Control", "System"]

Checks = Dict[str, Tuple[float, float]]


class System:
    """An open loop of ``count`` requests into ``TriangleService``."""

    grace_s = 60.0  # how long past the window a request may still come

    def __init__(self, config: dict, traffic: dict, manifest):
        self.config = config
        self.traffic = traffic
        self.manifest = manifest
        self.spans: Dict[str, float] = {}
        self.service = None
        self.graphs: List[Any] = []

    def make_inputs(self, seed: int, seconds: float) -> dict:
        pool = mixes.make_pool(self.traffic, seed, self.manifest.graph)
        return {"pool": pool, "schedule": mixes.make_schedule(
            self.traffic, seed, seconds, len(pool))}

    def work(self, inputs: dict) -> dict:
        return {"graphs": len(inputs["pool"]),
                "requests": len(inputs["schedule"])}

    def setup(self, inputs: dict) -> Dict[str, Any]:
        from repro.core import CountOptions
        from repro.serve import ServeConfig, TriangleService

        self.graphs = [as_graph(csr, f"pool{i}")
                       for i, csr in enumerate(inputs["pool"])]
        self.service = TriangleService(
            CountOptions(**self.config["options"]),
            config=ServeConfig(**self.config["serve"]))
        t0 = time.perf_counter()
        with span("bench.warmup"):
            info = self.service.warmup(self.graphs)
        self.spans = {"warmup_s": time.perf_counter() - t0}
        self.service.start()
        return {"warmup": info,
                "plan_cache": self.service.snapshot()["plan_cache"]}

    def submit(self, graph: int, tenant: int):
        return self.service.submit("count", self.graphs[graph],
                                   tenant=f"tenant{tenant:02d}")

    def window(self, inputs: dict, seconds: float):
        return mixes.open_loop(self.submit, inputs["schedule"], seconds,
                               grace=self.grace_s)

    def close(self) -> None:
        if self.service is not None:
            self.service.stop()
        self.service = None
        self.graphs = []

    def check(self, inputs: dict, records) -> Tuple[Checks, int, int]:
        want = {}
        err, unanswered, failed = 0, 0, 0
        for req in records:
            if req.error is not None:
                failed += 1
            elif req.result is None:
                unanswered += 1
            else:
                if req.graph not in want:
                    want[req.graph] = reference.count(*inputs["pool"][req.graph])
                err = max(err, abs(int(req.result.count) - want[req.graph]))
        return ({"answer_err_max": (err, 0), "unanswered": (unanswered, 0)},
                len(records), failed)


class Control(System):
    """Each request answered at once with its graph's reference total,
    accumulated at the precision ``control.accumulate`` names."""

    def setup(self, inputs: dict) -> dict:
        dtype = self.config["control"]["accumulate"]
        self.answers = [reference.control_count(reference.row_counts(*csr),
                                                dtype)
                        for csr in inputs["pool"]]
        return {"control": dtype}

    def submit(self, graph: int, tenant: int):
        fut = Future()
        fut.set_result(SimpleNamespace(count=self.answers[graph],
                                       batch_size=1, queue_wait_s=0.0))
        return fut
