"""``enable_compile_cache``: a fixed in-repo path, or the user's own."""

from __future__ import annotations

import os
import pathlib
import subprocess
import sys

import jax

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parent.parent


def test_cache_dir_is_fixed_under_the_checkout():
    assert compile_cache.CACHE_DIR == ROOT / ".jax_cache"


def test_env_dir_wins_and_nothing_is_set(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_sets_the_in_repo_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(compile_cache.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


_HIT_SCRIPT = r"""
import pathlib, sys
import jax, jax.numpy as jnp
from repro.launch import compile_cache
compile_cache.CACHE_DIR = pathlib.Path(sys.argv[1])
compile_cache.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
hits = []
jax.monitoring.register_event_listener(
    lambda e, **_: hits.append(e)
    if e == "/jax/compilation_cache/cache_hits" else None)
jax.jit(lambda x: jnp.cumsum(x * 3 + 1))(jnp.arange(64)).block_until_ready()
print("HITS", len(hits))
"""


def test_second_process_hits_the_cache(tmp_path):
    """Two runs in one checkout with the variable unset: the second reads
    what the first wrote at the helper's path."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src")] + [p for p in [env.get("PYTHONPATH")] if p])
    cache = tmp_path / "cache"

    def run() -> int:
        out = subprocess.run([sys.executable, "-c", _HIT_SCRIPT, str(cache)],
                             env=env, capture_output=True, text=True,
                             timeout=300, check=True).stdout
        return int(out.split("HITS")[1])

    assert run() == 0
    assert any(cache.iterdir())
    assert run() >= 1
