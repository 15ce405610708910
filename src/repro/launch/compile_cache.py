"""JAX's persistent compilation cache for the repo's entry points.

``enable_compile_cache`` is called by the scripts a user runs
(``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``), never on library
import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
nothing is set here. Otherwise the cache goes to ``.jax_cache/`` at the root
of the checkout: a fixed path, because the path is part of what makes a
later run's compile hit.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
