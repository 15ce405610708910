"""Spans and counters on the count and per-vertex paths, on the JAX
profiler's clock.

``span(name, **attrs)`` is a ``jax.profiler.TraceAnnotation``: free of any
record unless a profiler is running (about a microsecond per span), and
recorded then on the host plane of the profile, on the clock of the device
planes. ``bump(name)`` adds to a process-wide integer counter, read with
``counters()``; each bump is also a ``jax.monitoring`` event of the same
name, so a listener registered there counts the program's events beside
JAX's own. Counters always count.

The count replay (``TrianglePlan.count``) marks:

* ``tc.count`` — one count (``algorithm``, ``stages``); bumps ``tc.counts``;
* ``tc.stage`` — one stage of it (``index``, ``width``, ``strategy``,
  ``rows``, ``chunks``);
* ``tc.dispatch`` — the host enqueuing one device call (a resident stage, or
  one streamed chunk with its upload or gather arguments); bumps
  ``tc.dispatches``;
* ``tc.sync`` — the host blocked reading one result; bumps
  ``tc.host_syncs``.

The per-vertex replay (``TrianglePlan.triangles_per_vertex``) marks:

* ``tc.vertex`` — one pass (``algorithm``, ``stages``); bumps
  ``tc.vertex_passes``;
* inside it the same ``tc.stage`` (``strategy`` is the mask core's),
  ``tc.dispatch`` per resident stage or streamed chunk, and ``tc.sync``:
  once per pass while the counts add up on the device, once per call past
  the int32 bound.

``jit(name, fn)`` compiles ``fn`` as the module ``jit_<name>``: the name a
device profile gives the executable's operations.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict

import jax

__all__ = ["bump", "counters", "jit", "span"]

_lock = threading.Lock()
_counts: Dict[str, int] = {}


def span(name: str, **attrs) -> jax.profiler.TraceAnnotation:
    """A context manager marking ``name`` (with ``attrs``: ints and short
    strings already at hand) on the profiler's host timeline."""
    return jax.profiler.TraceAnnotation(name, **attrs)


def bump(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    with _lock:
        _counts[name] = _counts.get(name, 0) + n
    for _ in range(n):
        jax.monitoring.record_event(name)


def counters() -> Dict[str, int]:
    """A snapshot of every counter since the process started."""
    with _lock:
        return dict(_counts)


def jit(name: str, fn: Callable, **jit_kwargs) -> Callable:
    """``jax.jit(fn, **jit_kwargs)`` under ``name``."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kwargs)
