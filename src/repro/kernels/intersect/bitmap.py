"""Bitmap set-intersection core (the ``bitmap`` strategy).

TRUST-style dense core (arXiv:2103.08053): pack each v-list into
``num_bits/32`` 32-bit words — bit ``i`` set iff vertex ``i`` is a neighbor —
then test each u element by one word gather plus shift/AND. Packing is
O(W·num_bits/32) adds per row, membership testing O(W); when the id range
fits in ``num_bits ≈ W`` bits this is a ~32× reduction over the broadcast
core's O(W²) compares, which is why the engine's ``strategy="auto"`` cost
model picks it exactly when a bucket's id range fits the packed width.

Contract (shared by the jnp, Pallas, and numpy-ref implementations):

* rows sorted ascending; real values strictly increasing, then a run of one
  repeated padding sentinel (the layout ``csr_to_padded_neighbors`` emits).
  Strictness is what lets the packer turn bit-wise OR into a masked SUM
  (each kept value owns a distinct bit).
* values outside ``[0, num_bits)`` never match: negative row-padding
  sentinels and any overflow ids are masked out on both sides. Callers that
  need exact agreement with the broadcast/probe cores must therefore choose
  ``num_bits`` ≥ id range (the engine uses ``n + 2`` to cover both in-row
  sentinels ``n`` and ``n + 1``).

The words are int32 (TPU vector units reduce only signed integers); a word
holding bit 31 is negative, and bits are read back with a logical shift.
The Pallas kernel never materializes the packed row: it walks the words,
reducing word k of every v row to a (TE, 1) column and selecting it into the
u positions whose id falls in word k, so it needs no lane gather and costs
O(W·num_bits/32) per row like the jnp path.

VMEM budget (pallas): 2 · 2 · TE·W·4B double-buffered inputs + about
5 · TE·W·4B intermediates (the word/bit indices of u and v, the per-v-bit
contributions and the gathered u words) + 2 · TE·4B out; TE=256, W=512 is
~4.5 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lanes import row_counts

__all__ = [
    "intersect_counts_bitmap",
    "intersect_counts_bitmap_pallas",
    "intersect_counts_bitmap_ref",
    "intersect_matches_bitmap",
]


def _first_mask(v: jnp.ndarray, shifted: jnp.ndarray) -> jnp.ndarray:
    """First occurrence of each value in sorted rows: ``shifted`` is v moved
    right by one position (column 0 is ignored).

    Keeping only first occurrences makes the per-word SUM below equal the
    bit-wise OR (rows are sorted ⇒ duplicates are adjacent, and only the
    trailing padding run repeats)."""
    col = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    return (col == 0) | (v != shifted)


def _bit_split(x: jnp.ndarray, num_bits: int):
    """(valid, word, bit) of every id: ids outside [0, num_bits) are masked."""
    valid = (x >= 0) & (x < num_bits)
    return valid, jnp.where(valid, x >> 5, 0), jnp.where(valid, x & 31, 0)


def _contrib(first, v_valid, v_bit) -> jnp.ndarray:
    """Each kept v value's bit as an int32 word contribution."""
    return jnp.where(first & v_valid, jnp.left_shift(jnp.int32(1), v_bit), 0)


def _test_bits(words: jnp.ndarray, u_valid, u_bit) -> jnp.ndarray:
    """(E, W) bool: bit ``u_bit`` of each gathered word, valid ids only."""
    hits = jax.lax.shift_right_logical(words, u_bit) & 1
    return (hits != 0) & u_valid


def _pack_rows(v: jnp.ndarray, num_bits: int) -> jnp.ndarray:
    """Pack each sorted row of v into ``num_bits/32`` int32 bitmap words."""
    assert num_bits % 32 == 0 and num_bits > 0, num_bits
    shifted = jnp.concatenate([v[:, :1], v[:, :-1]], axis=1)
    v_valid, v_word, v_bit = _bit_split(v, num_bits)
    contrib = _contrib(_first_mask(v, shifted), v_valid, v_bit)
    words = []
    for k in range(num_bits // 32):  # static unroll; (E, W) memory per word
        sel = jnp.where(v_word == k, contrib, 0)
        words.append(sel.sum(axis=1, dtype=jnp.int32))
    return jnp.stack(words, axis=1)  # (E, nwords) int32


def _probe_bits(packed: jnp.ndarray, u: jnp.ndarray,
                num_bits: int) -> jnp.ndarray:
    """(E, W) bool: gather each u element's word and test its bit."""
    u_valid, u_word, u_bit = _bit_split(u, num_bits)
    hit_words = jnp.take_along_axis(packed, u_word, axis=1)  # (E, W)
    return _test_bits(hit_words, u_valid, u_bit)


def _pack_and_probe(u: jnp.ndarray, v: jnp.ndarray, num_bits: int) -> jnp.ndarray:
    """Shared jnp body: pack v rows into int32 words, probe u. (E,) int32."""
    return _probe_bits(_pack_rows(v, num_bits), u, num_bits) \
        .sum(axis=1, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_bits",))
def intersect_matches_bitmap(
    u_lists: jnp.ndarray, v_lists: jnp.ndarray, *, num_bits: int
) -> jnp.ndarray:
    """Bitmap membership MASK (jnp path): which u positions occur in v.

    The mask form of ``intersect_counts_bitmap`` — same packing, but the
    per-position hits are returned instead of row-summed, for the engine's
    vertex/edge analysis executables (which scatter each match to its
    triangle's vertices/edges).

    Args:
      u_lists: (E, W) int32 sorted rows (see module contract).
      v_lists: (E, W) int32 sorted rows, disjoint padding sentinel.
      num_bits: static packed-bitmap capacity, a positive multiple of 32.
        Values outside [0, num_bits) on either side never match.

    Returns:
      (E, W) bool — ``out[e, j]`` iff ``u_lists[e, j]`` is in
      ``v_lists[e]`` and within [0, num_bits).
    """
    return _probe_bits(_pack_rows(v_lists, num_bits), u_lists, num_bits)


@functools.partial(jax.jit, static_argnames=("num_bits",))
def intersect_counts_bitmap(
    u_lists: jnp.ndarray, v_lists: jnp.ndarray, *, num_bits: int
) -> jnp.ndarray:
    """Bitmap membership counts (jnp path).

    Args:
      u_lists: (E, W) int32 sorted rows (see module contract).
      v_lists: (E, W) int32 sorted rows, disjoint padding sentinel.
      num_bits: static packed-bitmap capacity, a positive multiple of 32.
        Values outside [0, num_bits) on either side are masked out.

    Returns:
      (E,) int32 — per-edge |N(u) ∩ N(v)| restricted to ids < num_bits.
    """
    return _pack_and_probe(u_lists, v_lists, num_bits)


def _bitmap_kernel(u_ref, v_ref, out_ref, *, num_bits: int):
    u = u_ref[...]  # (TE, W) int32, rows sorted
    v = v_ref[...]
    v_valid, v_word, v_bit = _bit_split(v, num_bits)
    contrib = _contrib(_first_mask(v, pltpu.roll(v, 1, 1)), v_valid, v_bit)
    u_valid, u_word, u_bit = _bit_split(u, num_bits)

    def word(k, hit_words):
        col = jnp.sum(jnp.where(v_word == k, contrib, 0), axis=1,
                      keepdims=True)  # (TE, 1): word k of every v row
        return jnp.where(u_word == k, col, hit_words)

    hit_words = jax.lax.fori_loop(0, num_bits // 32, word,
                                  jnp.zeros(u.shape, jnp.int32))
    hits = _test_bits(hit_words, u_valid, u_bit)
    out_ref[...] = row_counts(hits.astype(jnp.int32))


@functools.partial(
    jax.jit, static_argnames=("num_bits", "tile_edges", "interpret")
)
def intersect_counts_bitmap_pallas(
    u_lists: jnp.ndarray,
    v_lists: jnp.ndarray,
    *,
    num_bits: int,
    tile_edges: int = 256,
    interpret: bool = True,
) -> jnp.ndarray:
    """Pallas bitmap kernel: per-edge |N(u) ∩ N(v)| for (E, W) lists.

    Args:
      u_lists: (E, W) int32 sorted rows; E must be a multiple of
        ``tile_edges`` (callers pad with sentinel rows — see ops.py).
      v_lists: (E, W) int32 sorted rows, disjoint padding sentinel.
      num_bits: static packed-bitmap capacity, a positive multiple of 32.
      tile_edges: rows per grid step; a multiple of 128 (the lane width of
        the (1, TE) output block).
      interpret: run the kernel body on CPU for validation; pass False on a
        real TPU.

    Returns:
      (E,) int32 per-edge intersection sizes restricted to ids < num_bits.
    """
    e, w = u_lists.shape
    assert e % tile_edges == 0, (e, tile_edges)
    out = pl.pallas_call(
        functools.partial(_bitmap_kernel, num_bits=num_bits),
        grid=(e // tile_edges,),
        in_specs=[
            pl.BlockSpec((tile_edges, w), lambda i: (i, 0)),
            pl.BlockSpec((tile_edges, w), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_edges), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, e), jnp.int32),
        interpret=interpret,
    )(u_lists, v_lists)
    return out.reshape(e)


def intersect_counts_bitmap_ref(
    u_lists, v_lists, *, num_bits: int
) -> np.ndarray:
    """Numpy reference for the bitmap masking contract (tests only).

    Implements the same semantics as the jnp/Pallas bitmap cores — ids
    outside [0, num_bits) are ignored, v treated as a set — via Python sets,
    sharing no code with them.

    Args:
      u_lists / v_lists: (E, W) integer arrays, any layout.
      num_bits: bitmap capacity; out-of-range ids never match.

    Returns:
      (E,) int32 numpy array of per-edge counts.
    """
    u = np.asarray(u_lists)
    v = np.asarray(v_lists)
    out = np.zeros(u.shape[0], dtype=np.int32)
    for e in range(u.shape[0]):
        members = {x for x in v[e].tolist() if 0 <= x < num_bits}
        out[e] = sum(1 for x in u[e].tolist() if 0 <= x < num_bits and x in members)
    return out
