"""Dispatch layer for the TRUST-style per-vertex hash-table counting core.

The hashing lane's count stage is a membership problem: for forward edge
(u, v), how many of v's oriented neighbors appear in u's oriented neighbor
list? The intersect package answers it by merging two *sorted arrays*; this
package answers it TRUST-style (arXiv:2103.08053) by probing a *per-vertex
hash table* — O(D) slot compares per probe instead of O(W) or O(log W):

    backend      core                                  notes
    --------     ----------------------------------   -------------------------
    "jnp"        ``hash_probe_counts_jnp``             chunked gather, CPU path
    "pallas"     ``hash_probe_counts_pallas``          table-in-HBM TPU kernel
    "ref"        ``hash_probe_counts_ref``             structure-blind oracle

Sentinel rules (shared with the rest of the repo): candidate rows are the
bucket machinery's ``v_lists`` — in-row padding n + 1, whole padding rows -2,
with ``src`` carrying 0 on padding rows; table padding is -1. Only values in
[0, n) probe, so no sentinel combination can ever match.

Table sizing: ``hash_num_buckets`` picks B = next-pow2(width) (≥ 8), i.e. a
load factor ≤ 1 for a full row; the planner measures the real maximum chain
length with ``hash_table_depth`` and rounds it to a pow2 D, so the table
shape (n, B, D) is a deterministic function of the graph's shape class and
plans with equal classes share compiled executables.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.hash_tc.build import build_hash_table, hash_table_depth
from repro.kernels.hash_tc.probe import (
    hash_probe_counts_jnp,
    hash_probe_counts_pallas,
)
from repro.kernels.hash_tc.ref import hash_probe_counts_ref

__all__ = [
    "build_hash_table",
    "hash_num_buckets",
    "hash_probe_counts",
    "hash_table_depth",
]


def hash_num_buckets(width: int) -> int:
    """Bucket count for a table serving rows of ``width``: next pow2, ≥ 8."""
    return max(8, 1 << max(0, int(width) - 1).bit_length())


def hash_probe_counts(
    w_lists: jnp.ndarray,
    src: jnp.ndarray,
    table: jnp.ndarray,
    *,
    backend: str = "jnp",
    interpret: bool = True,
    tile_edges: int = 128,
) -> jnp.ndarray:
    """Dispatch per-edge hash-probe counts. (E, W) probes × (n, B, D) → (E,).

    Args:
      w_lists: (E, W) int32 candidate rows (sorted N⁺(dst) lists; in-row
        sentinel n + 1, whole padding rows -2).
      src: (E,) int32 anchor vertex per row (padding rows carry 0).
      table: (n, B, D) int32 per-vertex hash table from
        ``build_hash_table``; B must be a power of two.
      backend: "pallas" (table-in-HBM TPU kernel), "jnp" (chunked-gather
        production path), or "ref" (the structure-blind oracle).
      tile_edges: pallas grid tile height; E is sentinel-row-padded to a
        multiple of it and the padding stripped from the result.
      interpret: pallas interpret mode (True = run kernel bodies on CPU).

    Returns:
      (E,) int32 — per-edge count of candidates present in ``table[src]``
      (= |N⁺(dst) ∩ N⁺(src)| when fed the planner's oriented rows).
    """
    if backend not in ("pallas", "jnp", "ref"):
        raise ValueError(f"unknown backend {backend!r}")
    if backend == "ref":
        return hash_probe_counts_ref(w_lists, src, table)
    if backend == "jnp":
        return hash_probe_counts_jnp(w_lists, src, table)

    # backend == "pallas": tile the edge axis, strip padding on the way out
    e = int(w_lists.shape[0])
    if e == 0:
        return jnp.zeros((0,), jnp.int32)
    pad = (-e) % tile_edges
    if pad:
        w_lists = jnp.pad(w_lists, ((0, pad), (0, 0)), constant_values=-2)
        src = jnp.pad(src, ((0, pad),), constant_values=0)
    out = hash_probe_counts_pallas(
        w_lists, src, table, tile_edges=tile_edges, interpret=interpret
    )
    return out[:e] if pad else out
