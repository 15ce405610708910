"""From a JAX profiler trace to device busy time, idle gaps and top ops.

The trace of a ``--trace 1`` run holds the device planes (``/device:TPU:<i>``,
whose ``XLA Ops`` line has one event per operation run) and the host plane
(``/host:CPU``), where the harness's own spans (``bench.*``, written with
``jax.profiler.TraceAnnotation``) lie on the same clock. A backend without device
planes (the CPU, in the tests) has its operations on host threads, as events
that carry an ``hlo_op`` stat; those stand for the device there.

* busy: the union of the operations' intervals inside the window, averaged
  over the devices that ran any;
* idle gaps: the complement of that union inside the window, each named by
  the innermost harness span open at its midpoint (``(no host span)`` where
  none is);
* top ops: device seconds by operation name.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["Interval", "TraceSummary", "attribute", "load", "summarize",
           "union"]

WINDOW_SPAN = "bench.window"
NO_SPAN = "(no host span)"

Interval = Tuple[float, float]  # (start_ns, end_ns)


@dataclasses.dataclass
class TraceSummary:
    """What the metrics and the result line read from one trace."""

    busy_s: float  # mean over devices of the busy union inside the window
    window_s: float
    device_ops: List[Tuple[str, float]]  # (op name, seconds), longest first
    idle_gaps: List[Tuple[str, float]]  # (host span, idle seconds), longest
    longest_gaps: List[Tuple[str, float]]  # single gaps, longest first
    devices: int


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint intervals covering the same points."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(merged: Sequence[Interval], w0: float, w1: float) -> List[Interval]:
    return [(max(s, w0), min(e, w1)) for s, e in merged if e > w0 and s < w1]


def _gaps(merged: Sequence[Interval], w0: float, w1: float) -> List[Interval]:
    gaps, t = [], w0
    for s, e in merged:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if w1 > t:
        gaps.append((t, w1))
    return gaps


def attribute(points: Sequence[float],
              host: Sequence[Tuple[str, float, float]]) -> List[str]:
    """For each point, the name of the innermost host event (latest start)
    whose interval holds it; ``NO_SPAN`` where none does."""
    events = sorted(host, key=lambda h: h[1])
    starts = [h[1] for h in events]
    order = sorted(range(len(points)), key=lambda i: points[i])
    names = [NO_SPAN] * len(points)
    open_events: List[Tuple[str, float, float]] = []  # sorted by start
    k = 0
    for i in order:
        p = points[i]
        hi = bisect.bisect_right(starts, p)
        open_events.extend(events[k:hi])
        k = hi
        open_events = [h for h in open_events if h[2] > p]
        if open_events:
            names[i] = open_events[-1][0]
    return names


def _top(totals: Dict[str, float], n: int) -> List[Tuple[str, float]]:
    return sorted(totals.items(), key=lambda kv: -kv[1])[:n]


@dataclasses.dataclass
class RawTrace:
    """Events of one trace, in nanoseconds on the profiler's clock."""

    device_ops: Dict[str, List[Tuple[str, float, float]]]  # plane -> ops
    host: List[Tuple[str, float, float]]  # (name, start, end)


def load(log_dir: str) -> RawTrace:
    """Read the newest ``.xplane.pb`` under ``log_dir``; host events are the
    harness spans."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    planes = list(ProfileData.from_file(paths[-1]).planes)
    device: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
    host: List[Tuple[str, float, float]] = []
    cpu_ops: List[Tuple[str, float, float]] = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name.startswith("bench."):
                    host.append((name, e.start_ns, e.start_ns + e.duration_ns))
                elif not device and any(k == "hlo_op" for k, _ in e.stats):
                    cpu_ops.append((name, e.start_ns,
                                    e.start_ns + e.duration_ns))
    if cpu_ops:
        device["/host:CPU"] = cpu_ops
    return RawTrace(device_ops=device, host=host)


def summarize(raw: RawTrace, window: Optional[Interval] = None,
              top: int = 10) -> TraceSummary:
    """Reduce ``raw`` over ``window`` (default: the ``bench.window`` span)."""
    if window is None:
        spans = [(s, e) for n, s, e in raw.host if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"trace has no {WINDOW_SPAN} span")
        window = spans[0]
    w0, w1 = window
    busy, ops = [], {}
    gap_totals: Dict[str, float] = {}
    gaps_named: List[Tuple[str, float]] = []
    for plane, events in sorted(raw.device_ops.items()):
        inside = _clip(union([(s, e) for _, s, e in events]), w0, w1)
        busy.append(sum(e - s for s, e in inside))
        for name, s, e in events:
            d = min(e, w1) - max(s, w0)
            if d > 0:
                ops[name] = ops.get(name, 0.0) + d
        gaps = _gaps(inside, w0, w1)
        names = attribute([(s + e) / 2 for s, e in gaps],
                          [h for h in raw.host if h[0] != WINDOW_SPAN])
        for (s, e), name in zip(gaps, names):
            gap_totals[name] = gap_totals.get(name, 0.0) + (e - s)
            gaps_named.append((name, e - s))
    n = max(len(busy), 1)
    return TraceSummary(
        busy_s=sum(busy) / n / 1e9,
        window_s=(w1 - w0) / 1e9,
        device_ops=[(k, v / 1e9) for k, v in _top(ops, top)],
        idle_gaps=[(k, v / n / 1e9) for k, v in _top(gap_totals, top)],
        longest_gaps=[(k, v / 1e9) for k, v in
                      sorted(gaps_named, key=lambda kv: -kv[1])[:top]],
        devices=len(busy),
    )
