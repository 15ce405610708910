"""What one run leaves for the metric readers, and JAX's compile events.

Every reader in ``bench/metrics/`` is ``read(run: Run) -> float | None``:
it takes its number from this record and returns None where the run has
nothing for it to read.
"""

from __future__ import annotations

import dataclasses
import math
import threading
from typing import Any, Dict, List, Optional, Sequence

from bench.harness.trace import TraceSummary

__all__ = ["Events", "Run", "idle_share", "percentile"]


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The nearest-rank ``q``-th percentile (0 < q <= 100); None if empty."""
    xs = sorted(values)
    if not xs:
        return None
    return xs[max(math.ceil(q / 100.0 * len(xs)) - 1, 0)]


class Events:
    """Seconds per JAX monitoring duration event, and counts of plain
    events, since the recorder was registered (thread-safe)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.seconds: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}

    def on_duration(self, event: str, duration: float, **_) -> None:
        with self._lock:
            self.seconds[event] = self.seconds.get(event, 0.0) + duration

    def on_event(self, event: str, **_) -> None:
        with self._lock:
            self.counts[event] = self.counts.get(event, 0) + 1

    def register(self) -> "Events":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def snapshot(self) -> dict:
        with self._lock:
            return {"seconds": dict(self.seconds), "counts": dict(self.counts)}

    @staticmethod
    def delta(before: dict, after: dict) -> dict:
        return {kind: {k: v - before[kind].get(k, 0)
                       for k, v in after[kind].items()
                       if v != before[kind].get(k, 0)}
                for kind in ("seconds", "counts")}


@dataclasses.dataclass
class Run:
    """One run of one cell, as the metric readers see it."""

    loop: str  # "closed" or "open"
    setup_s: float
    spans: Dict[str, float]  # host-clock seconds of the set-up spans
    setup_events: dict  # Events.delta over set-up
    window_s: float  # host clock, opening to close
    records: List[Any]  # closed: (start, end, answer); open: Request
    work: Dict[str, Any]  # what one call must do, from the inputs alone
    peaks: Dict[str, Any]  # the device's row of bench/peaks.json
    trace: Optional[TraceSummary] = None


def idle_share(run: Run) -> Optional[float]:
    """Percent of the traced window in which no operation ran on the
    device; None without a trace."""
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
