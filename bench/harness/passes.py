"""Program counters per per-vertex pass, over a closed loop's window.

The per-vertex replay (``TrianglePlan.triangles_per_vertex``) bumps
``tc.vertex_passes`` once per pass, beside the ``tc.dispatches`` and
``tc.host_syncs`` of its calls (``repro.core.tracing``).
"""

from __future__ import annotations

from typing import Optional

__all__ = ["PASSES", "per_pass"]

PASSES = "tc.vertex_passes"


def per_pass(run, name: str) -> Optional[float]:
    """The program counter ``name``'s increase over the window of a closed
    loop, per per-vertex pass completed in it.

    The counters are read now, after the window; their set-up increase is
    among the run's set-up events. The rest is the window's where the
    program counted nothing before set-up, which holds where the rest of
    ``tc.vertex_passes`` equals the window's calls. None otherwise, and
    where the program keeps no such counter."""
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

    if window(PASSES) != len(run.records):
        return None
    return window(name) / len(run.records)
