"""Hash-probe cores for the TRUST-style hashing lane.

Per forward edge (u, v) the lane counts ``|N⁺(v) ∩ N⁺(u)|`` by probing each
element of the candidate row (``N⁺(v)``, the bucket machinery's ``v_lists``)
against the anchor's hash table row ``table[u]`` — TRUST's warp-level
hash-intersection, vectorized: a probe ``w`` reads bucket ``w & (B - 1)`` and
compares against its D chain slots, so per-edge work is O(W·D) instead of the
broadcast core's O(W²).

Two implementations of the same semantics:

* ``hash_probe_counts_jnp``    — gathers each edge's table row and resolves
                                 all probes with one ``take_along_axis``;
                                 ``lax.map``-chunked so the (C, B, D) gather
                                 stays inside a fixed element budget.
* ``hash_probe_counts_pallas`` — a Pallas kernel: the table stays in HBM;
                                 each grid step loads a (TE, B) probe tile
                                 and the tile's anchors into SMEM, then per
                                 edge copies the anchor's (D, B) table row
                                 into VMEM and resolves its probes with one
                                 128-lane-slab bucket gather.

Probe-validity rule: only values in [0, n) probe; the candidate rows' in-row
sentinel (n + 1) and whole-row padding (-2) are masked out, and empty table
slots hold -1 which no valid probe can equal.

VMEM budget (pallas): 2 · 2 · TE·B·4B double-buffered probe tiles + one
(max(D, 2), max(B, 128)) table row + a few (D, B) intermediates; TE=128,
B=512 is ~1.1 MB. The table itself is only bounded by HBM. The per-edge row
copy is serial (start, then wait before the gather).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lanes import LANE, lane_gather

__all__ = ["hash_probe_counts_jnp", "hash_probe_counts_pallas"]

# element budget for one chunk's (C, B, D) table gather + (C, W, D) candidate
# compare — mirrors the broadcast core's chunking constant
_PROBE_CHUNK_ELEMS = 1 << 22


def _probe_block(w_lists: jnp.ndarray, src: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    n, num_buckets, _ = table.shape
    tbl = table[src]  # (C, B, D)
    valid = (w_lists >= 0) & (w_lists < n)
    bkt = jnp.where(valid, w_lists & (num_buckets - 1), 0)
    cand = jnp.take_along_axis(tbl, bkt[:, :, None], axis=1)  # (C, W, D)
    hit = jnp.any(cand == w_lists[:, :, None], axis=-1) & valid
    return hit.sum(axis=1).astype(jnp.int32)


@jax.jit
def hash_probe_counts_jnp(
    w_lists: jnp.ndarray, src: jnp.ndarray, table: jnp.ndarray
) -> jnp.ndarray:
    """Chunked jnp hash probe (the production CPU path).

    Args:
      w_lists: (E, W) int32 candidate rows (sorted N⁺(dst) lists, in-row
        sentinel n + 1, whole padding rows -2).
      src: (E,) int32 anchor vertex per row (padding rows carry 0 — harmless,
        their probes are all invalid).
      table: (n, B, D) int32 hash table from ``build_hash_table``; B must be
        a power of two.

    Returns:
      (E,) int32 — per-edge count of candidates present in ``table[src]``.
    """
    e, w = w_lists.shape
    if e == 0:
        return jnp.zeros((0,), jnp.int32)
    _, num_buckets, depth = table.shape
    per_row = (num_buckets + w) * max(1, depth)
    chunk = int(min(e, max(1, _PROBE_CHUNK_ELEMS // max(1, per_row))))
    if chunk >= e:
        return _probe_block(w_lists, src, table)
    pad = (-e) % chunk
    wp = jnp.pad(w_lists, ((0, pad), (0, 0)), constant_values=-2)
    sp = jnp.pad(src, ((0, pad),), constant_values=0)
    out = jax.lax.map(
        lambda t: _probe_block(t[0], t[1], table),
        (wp.reshape(-1, chunk, w), sp.reshape(-1, chunk)),
    )
    return out.reshape(-1)[:e]


def _hash_probe_kernel(src_ref, w_ref, tbl_hbm, out_ref, row_ref, sem, *,
                       num_buckets: int, n: int):
    te = w_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, te), 1)

    def body(i, acc):
        copy = pltpu.make_async_copy(tbl_hbm.at[src_ref[0, i]], row_ref, sem)
        copy.start()
        row = w_ref[pl.ds(i, 1), :]  # (1, B) candidates of edge i
        valid = (row >= 0) & (row < n)
        bkt = jnp.where(valid, row & (num_buckets - 1), 0)
        copy.wait()
        tbl = row_ref[...]  # (D, B): chain slot d of every bucket
        cand = lane_gather(tbl, jnp.concatenate([bkt] * tbl.shape[0], axis=0))
        hit = jnp.max((cand == row).astype(jnp.int32), axis=0, keepdims=True)
        cnt = jnp.sum(hit * valid.astype(jnp.int32), axis=1, keepdims=True)
        return jnp.where(lane == i, cnt, acc)

    out_ref[...] = jax.lax.fori_loop(0, te, body, jnp.zeros((1, te), jnp.int32))


@functools.partial(jax.jit, static_argnames=("tile_edges", "interpret"))
def hash_probe_counts_pallas(
    w_lists: jnp.ndarray,
    src: jnp.ndarray,
    table: jnp.ndarray,
    *,
    tile_edges: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    """Pallas hash-probe kernel; semantics of ``hash_probe_counts_jnp``.

    Args:
      w_lists: (E, W) int32 candidate rows; E must be a multiple of
        ``tile_edges`` (ops.py pads with sentinel rows).
      src: (E,) int32 anchor vertices (padding rows carry 0).
      table: (n, B, D) int32 hash table, B a power of two and ≥ W; it stays
        in HBM and each edge copies its anchor's (D, B) row into VMEM.
      tile_edges: probe rows per grid step; a multiple of 128 (the lane
        width of the (1, TE) output block).
      interpret: run the kernel body on CPU for validation; pass False on a
        real TPU.

    Returns:
      (E,) int32 per-edge hit counts.
    """
    e, w = w_lists.shape
    n, num_buckets, depth = table.shape
    assert e % tile_edges == 0 and w <= num_buckets, (e, tile_edges, w)
    # the kernel's layout: (n, D, B) rows of at least 2 chain slots × 128
    # lanes (Mosaic's DMA and lane-gather minimums), padded with empty
    # slots; candidate rows widened to the same lanes with never-probing ids
    lanes, slots = max(num_buckets, LANE), max(depth, 2)
    tbl = jnp.pad(table.transpose(0, 2, 1),
                  ((0, 0), (0, slots - depth), (0, lanes - num_buckets)),
                  constant_values=-1)
    w_lists = jnp.pad(w_lists, ((0, 0), (0, lanes - w)), constant_values=-2)
    groups = e // tile_edges
    out = pl.pallas_call(
        functools.partial(_hash_probe_kernel, num_buckets=num_buckets, n=n),
        grid=(groups,),
        in_specs=[
            pl.BlockSpec((None, 1, tile_edges), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((tile_edges, lanes), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, tile_edges), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, e), jnp.int32),
        scratch_shapes=[pltpu.VMEM((slots, lanes), jnp.int32),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(src.reshape(groups, 1, tile_edges), w_lists, tbl)
    return out.reshape(e)
