"""The one traffic generator: every mix is a data file that this reads.

A mix (``bench/traffic/<name>.json``) says how load reaches the system:

* ``"loop": "closed"`` — one caller replays the system's call back to back.
  The window closes when the first call that ends at or after ``seconds``
  returns, so it holds whole calls only.
* ``"loop": "open"`` — requests are due at Poisson arrivals of
  ``rate_per_s``, whatever the system does. Each names a graph of the mix's
  ``pool`` (groups ``{"graph": <generator spec>, "graphs": <how many>}``),
  drawn by Zipf popularity (``popularity.zipf_s``) over a seeded rank
  order, and a tenant drawn by Zipf over ``tenants.count``.
  Every request is timed from its due time to its future's completion.

Everything random comes from the run's seed: the same seed gives the same
pool, the same order and the same arrival times.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["Request", "closed_loop", "make_pool", "make_schedule",
           "open_loop", "rng", "span"]


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any seed >= 0."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.default_rng([seed, stream])


def make_pool(traffic: dict, seed: int,
              make_graph: Callable[[dict, int], Any]
              ) -> List[Tuple[int, np.ndarray, np.ndarray]]:
    """The mix's graphs, as (n, row_ptr, col_idx), in group order;
    ``make_graph(spec, seed)`` makes each from its group's spec."""
    r = rng(seed, 0)
    pool = []
    for group in traffic["pool"]:
        for s in r.integers(0, 2 ** 63 - 1, size=int(group["graphs"])):
            pool.append(make_graph(group["graph"], int(s)))
    return pool


def _zipf(r: np.random.Generator, n: int, s: float, size: int) -> np.ndarray:
    """``size`` draws from [0, n) with P(k-th of a seeded order) ~ k**-s."""
    p = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    order = r.permutation(n)
    return order[r.choice(n, size=size, p=p / p.sum())]


def make_schedule(traffic: dict, seed: int, seconds: float,
                  n_graphs: int) -> List[Tuple[float, int, int]]:
    """(due seconds after the window opens, graph index, tenant index) for
    every request due inside ``seconds``."""
    r = rng(seed, 1)
    rate = float(traffic["rate_per_s"])
    gaps = r.exponential(1.0 / rate, size=int(rate * seconds * 1.5) + 64)
    due = np.cumsum(gaps)
    while due[-1] < seconds:  # a schedule short by chance: draw on
        due = np.concatenate([due, due[-1] + np.cumsum(
            r.exponential(1.0 / rate, size=len(due)))])
    due = due[due < seconds]
    graphs = _zipf(r, n_graphs, float(traffic["popularity"]["zipf_s"]),
                   len(due))
    tenants = _zipf(r, int(traffic["tenants"]["count"]),
                    float(traffic["tenants"]["zipf_s"]), len(due))
    return list(zip(due.tolist(), graphs.tolist(), tenants.tolist()))


def span(name: str):
    """A host span of the harness in the profiler's trace (``bench.*``)."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


def closed_loop(call: Callable[[], Any], seconds: float
                ) -> Tuple[List[Tuple[float, float, Any]], float]:
    """Replay ``call`` back to back; returns ([(start, end, answer)] in
    seconds from the window's opening, window seconds)."""
    records = []
    with span("bench.window"):
        t0 = time.perf_counter()
        while True:
            s = time.perf_counter()
            with span("bench.count"):
                answer = call()
            e = time.perf_counter()
            records.append((s - t0, e - t0, answer))
            if e - t0 >= seconds:
                break
    return records, e - t0


@dataclasses.dataclass
class Request:
    """One open-loop request: when it was due and what became of it."""

    due: float  # seconds after the window opened
    graph: int
    tenant: int
    sent: float = 0.0  # seconds after the window opened
    done: Optional[float] = None  # completion, seconds after the opening
    result: Any = None
    error: Optional[BaseException] = None

    @property
    def latency(self) -> Optional[float]:
        return None if self.done is None else self.done - self.due


def open_loop(submit: Callable[[int, int], Any],
              schedule: Sequence[Tuple[float, int, int]], seconds: float,
              grace: float = 60.0) -> Tuple[List[Request], float]:
    """Send each request at its due time through ``submit(graph, tenant)``
    (which returns a future), then wait up to ``grace`` seconds past the
    window for the stragglers. Returns (requests, window seconds)."""
    reqs = [Request(due=d, graph=g, tenant=t) for d, g, t in schedule]
    futures = []
    with span("bench.window"):
        t0 = time.perf_counter()

        def finished(req: Request, fut) -> None:
            req.done = time.perf_counter() - t0

        for req in reqs:
            delay = req.due - (time.perf_counter() - t0)
            if delay > 0:
                with span("bench.wait"):
                    time.sleep(delay)
            with span("bench.submit"):
                req.sent = time.perf_counter() - t0
                fut = submit(req.graph, req.tenant)
                fut.add_done_callback(lambda f, req=req: finished(req, f))
            futures.append(fut)
        rest = seconds - (time.perf_counter() - t0)
        if rest > 0:
            with span("bench.wait"):
                time.sleep(rest)
        window = time.perf_counter() - t0
    deadline = time.perf_counter() + max(grace, 0.0)
    for req, fut in zip(reqs, futures):
        try:
            req.result = fut.result(timeout=max(deadline - time.perf_counter(),
                                                0.0))
        except TimeoutError:
            req.done = None  # never came
        except Exception as e:  # shed or failed: the request's own outcome
            req.error = e
    return reqs, window
