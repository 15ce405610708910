"""The program's own spans and counters, as a run leaves them.

The count replay marks its work with ``tc.*`` spans on the profiler's host
timeline (``repro.core.tracing``: ``tc.count`` ⊃ ``tc.stage`` ⊃
``tc.dispatch`` / ``tc.sync``) and keeps process-wide counters
(``tc.counts``, ``tc.dispatches``, ``tc.host_syncs``), each bump also a
``jax.monitoring`` event. Its executables are named by lane, strategy and
width (``jit_tc_intersect_broadcast_w512`` and its ``_gathered`` twin).

* ``load``: the ``tc.*`` spans of a profile, each device's executables
  (the ``XLA Modules`` line of a device plane; on the CPU, the
  ``hlo_module`` stat of each operation), and the stretches in which the
  profiler dropped a device's events (``Trace Buffers Dropped`` on its
  ``XLA TraceMe`` line): there the device looks idle whatever it ran.
* ``gaps``: the window's device-idle time split exactly by the innermost
  ``tc.*`` span open at each instant (``(no program span)`` where none is),
  averaged over devices. Spans are taken as one nest whatever thread opened
  them.
* ``executables``: device seconds per executable inside the window.
* ``per_count``: a counter's increase over a run's window per count.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from bench.harness import trace

__all__ = ["NO_SPAN", "Program", "executables", "gaps", "load", "per_count"]

PREFIX = "tc."
NO_SPAN = "(no program span)"
DROPPED = "Trace Buffers Dropped"

Span = Tuple[str, float, float]  # (name, start_ns, end_ns)


@dataclasses.dataclass
class Program:
    """The program's events in one profile, in ns on the profiler's clock."""

    spans: List[Span]
    attrs: List[dict]  # the stats of each span, in the order of ``spans``
    modules: Dict[str, List[Span]]  # device plane -> executables run
    dropped: Dict[str, List[Span]]  # device plane -> events not recorded


def load(log_dir: str) -> Program:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    planes = list(ProfileData.from_file(paths[-1]).planes)
    prog = Program(spans=[], attrs=[], modules={}, dropped={})
    for plane in planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    prog.modules.setdefault(plane.name, []).extend(
                        (re.sub(r"\(\d+\)$", "", e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events)
                elif line.name == "XLA TraceMe":
                    prog.dropped[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name == DROPPED]
    cpu_modules: List[Span] = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                end = e.start_ns + e.duration_ns
                if e.name.startswith(PREFIX):
                    prog.spans.append((e.name, e.start_ns, end))
                    prog.attrs.append(dict(e.stats))
                elif not prog.modules:
                    stats = dict(e.stats)
                    if "hlo_op" in stats and "hlo_module" in stats:
                        cpu_modules.append((stats["hlo_module"], e.start_ns,
                                            end))
    if cpu_modules:
        prog.modules["/host:CPU"] = cpu_modules
    return prog


def _label(spans: Sequence[Span], w0: float, w1: float
           ) -> List[Tuple[float, float, str]]:
    """``[w0, w1)`` cut at every span boundary, each piece named by the
    innermost span open over it."""
    cuts = sorted({w0, w1} | {t for _, s, e in spans for t in (s, e)
                              if w0 < t < w1})
    pieces = list(zip(cuts, cuts[1:]))
    names = trace.attribute([(a + b) / 2 for a, b in pieces], spans)
    return [(a, b, NO_SPAN if n == trace.NO_SPAN else n)
            for (a, b), n in zip(pieces, names)]


def gaps(device_ops: Dict[str, List[Span]], spans: Sequence[Span],
         window: trace.Interval) -> Dict[str, float]:
    """Device-idle seconds of ``window`` by innermost program span, averaged
    over the planes in ``device_ops``; they sum to the window less the
    mean busy time."""
    w0, w1 = window
    pieces = _label(spans, w0, w1)
    totals: Dict[str, float] = {}
    for _, events in sorted(device_ops.items()):
        idle = trace._gaps(trace._clip(trace.union(
            [(s, e) for _, s, e in events]), w0, w1), w0, w1)
        i = 0
        for a, b, name in pieces:  # both sorted and disjoint: one sweep
            while i < len(idle) and idle[i][1] <= a:
                i += 1
            j = i
            while j < len(idle) and idle[j][0] < b:
                d = min(b, idle[j][1]) - max(a, idle[j][0])
                totals[name] = totals.get(name, 0.0) + d
                j += 1
    n = max(len(device_ops), 1)
    return {k: v / n / 1e9 for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])}


def executables(modules: Dict[str, List[Span]], window: trace.Interval
                ) -> Dict[str, float]:
    """Device seconds per executable inside ``window``, averaged over the
    planes, longest first."""
    w0, w1 = window
    totals: Dict[str, float] = {}
    for events in modules.values():
        for name, s, e in events:
            d = min(e, w1) - max(s, w0)
            if d > 0:
                totals[name] = totals.get(name, 0.0) + d
    n = max(len(modules), 1)
    return {k: v / n / 1e9 for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])}


def per_count(run, name: str) -> Optional[float]:
    """The program counter ``name``'s increase over the window of a closed
    loop, per count completed in it.

    The counters are read now, after the window; their set-up increase is
    among the run's set-up events. The rest is the window's where the
    program counted nothing before set-up, which holds where the rest of
    ``tc.counts`` equals the window's calls. None otherwise, and where the
    program keeps no counters."""
    if run.loop != "closed" or not run.records:
        return None
    try:
        from repro.core import tracing
    except ImportError:
        return None
    now = tracing.counters()
    setup = run.setup_events.get("counts", {})

    def window(key: str) -> int:
        return now.get(key, 0) - setup.get(key, 0)

    if window(PREFIX + "counts") != len(run.records):
        return None
    return window(name) / len(run.records)
