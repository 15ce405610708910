"""Strategy × backend dispatch for the batched set-intersection core.

The TC hot loop is one function — per-edge |N(u) ∩ N(v)| over padded (E, W)
sorted neighbor lists — with three interchangeable *strategies* (how the
intersection is computed) times three *backends* (where it runs):

  strategy    work/row      wins when
  ---------   -----------   ------------------------------------------------
  broadcast   O(W²)         narrow buckets: pure VPU compare, no gathers
  probe       O(W·log W)    wide skewed buckets: log W gather/select rounds
  bitmap      O(W·B/32)     the bucket's id range fits B ≈ W packed bits
                            (TRUST-style dense neighborhoods)

  backend
  -------
  pallas      the TPU kernels (interpret=True runs them on CPU)
  jnp         pure-jnp paths — the production CPU paths, GSPMD-shardable
  ref         the O(E·W²) broadcast-compare oracle (strategy-independent
              semantics; every strategy must agree with it on in-range ids)

``choose_strategy`` is the documented cost model behind ``strategy="auto"``:
bitmap when the id range fits the packed width (a ~32× compare reduction),
probe for wide buckets (W ≥ 64, past the O(W²)/O(W log W) crossover measured
on the CPU), broadcast for narrow ones — and broadcast for the ``jnp`` core
on a TPU, where the vmapped ``searchsorted`` probe is gather-bound. ``resolve_strategy`` additionally
picks the bitmap capacity; the engine applies it per degree bucket and bakes
the result into the executable-cache key. ``choose_mask_strategy`` is the
same rule for the mask entry points (``intersect_matches``, the per-vertex
and per-edge executables), with bitmap allowed to a wider id range and the
same TPU exception. The ``jnp`` broadcast count and masks share one row
chunking (``_row_chunked``), which bounds the (E, W, W) compare tensor.

Sentinel-padding rules (repo-wide): within a row, u pads with one value and
v with a different one (the engine uses ``n`` and ``n + 1``); whole padding
*rows* added to reach a tile multiple use ``-1`` (u) and ``-2`` (v). Disjoint
sentinels mean padding contributes zero matches without masks — except in the
bitmap core, which masks ids outside [0, num_bits) explicitly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.intersect.bitmap import (
    intersect_counts_bitmap,
    intersect_counts_bitmap_pallas,
    intersect_matches_bitmap,
)
from repro.kernels.intersect.intersect import intersect_counts_pallas
from repro.kernels.intersect.probe import (
    intersect_counts_probe,
    intersect_counts_probe_pallas,
)
from repro.kernels.intersect.ref import intersect_counts_ref

__all__ = [
    "BITMAP_MAX_BITS",
    "STRATEGIES",
    "available_strategies",
    "intersect_counts",
    "intersect_counts_probe",
    "intersect_matches",
    "intersect_matches_both",
    "choose_strategy",
    "choose_mask_strategy",
    "resolve_mask_strategy",
    "resolve_strategy",
    "packed_bits",
]

STRATEGIES = ("broadcast", "probe", "bitmap")


def available_strategies() -> tuple:
    """The valid set-intersection strategy names, sorted (the discovery
    helper mirroring ``repro.graphs.available_datasets`` /
    ``repro.core.available_algorithms``). Every ``strategy=`` kwarg accepts
    these plus ``"auto"``, which resolves per bucket via the
    ``choose_strategy`` / ``choose_mask_strategy`` cost models."""
    return tuple(sorted(STRATEGIES))

# O(W²) broadcast vs O(W log W) probe crossover: below this width the
# gather-free broadcast compare wins on the VPU
_PROBE_MIN_WIDTH = 64

# hard cap on any bitmap's capacity: the packer statically unrolls
# num_bits/32 iterations (each touching an (E, W) temporary), so an
# unbounded forced bitmap on a large id range would blow up trace time
# long before producing a result — refuse instead
BITMAP_MAX_BITS = 1 << 16


def _ceil32(x: int) -> int:
    return max(32, ((int(x) + 31) // 32) * 32)


def packed_bits(width: int) -> int:
    """Bitmap capacity paired with a width-W bucket: W bits (min one word).

    The bitmap core packs v-lists into ``packed_bits(W)/32`` uint32 words, so
    a bucket qualifies for the auto cost model only when every vertex id the
    bucket can contain fits below this many bits.
    """
    return _ceil32(width)


def choose_strategy(width: int, id_range=None, backend: str = "jnp",
                    platform=None) -> str:
    """The ``strategy="auto"`` cost model. Pure function, documented contract.

    Args:
      width: the bucket's padded list width W (static).
      id_range: number of distinct ids the lists may contain (the engine
        passes ``n + 2`` to cover the in-row sentinels ``n`` and ``n + 1``);
        None when unknown (e.g. under tracing), which disqualifies bitmap.
      backend: the backend that will run the core ("jnp" | "pallas").
      platform: where it runs; None means JAX's default backend.

    Returns:
      "bitmap" when ``id_range`` fits the packed width (membership tests
      collapse to shift/AND over W/32 words; the packed width must also stay
      under ``BITMAP_MAX_BITS``), else "probe" for wide buckets (W ≥ 64),
      else "broadcast" for narrow ones. The one exception is the ``jnp``
      probe core on a TPU: its vmapped ``searchsorted`` is gather-bound
      there (on a v5e, the ``strategy`` phase of ``chip_smoke.py``, which
      measures this on every run, read it 38–40× slower than the ``jnp``
      broadcast core at W = 128 and 116–117× at W = 512 over 4,096 rows,
      two runs), so wide buckets take broadcast.
    """
    pw = packed_bits(width)
    if id_range is not None and int(id_range) <= pw and pw <= BITMAP_MAX_BITS:
        return "bitmap"
    if backend == "jnp" and (platform or jax.default_backend()) == "tpu":
        return "broadcast"
    if width >= _PROBE_MIN_WIDTH:
        return "probe"
    return "broadcast"


def resolve_strategy(width: int, id_range=None, strategy: str = "auto",
                     backend: str = "jnp"):
    """Resolve ("auto" or explicit) strategy to (strategy, bitmap_bits).

    ``bitmap_bits`` is None except for the bitmap strategy, where it is
    ``packed_bits(width)`` when the id range fits (so same-shaped buckets from
    different graphs share one executable-cache entry) and the id range
    rounded up to a word multiple when bitmap is forced beyond it.
    ``backend`` is the backend that will run the core (``choose_strategy``).

    Raises:
      ValueError: strategy="bitmap" forced with no ``id_range`` to size the
        bitmap, forced over an id range needing more than ``BITMAP_MAX_BITS``
        packed bits, or an unknown strategy name.
    """
    if strategy == "auto":
        strategy = choose_strategy(width, id_range, backend)
    if strategy not in STRATEGIES:
        raise ValueError(
            f"unknown strategy {strategy!r}; expected 'auto' or one of {STRATEGIES}"
        )
    bits = None
    if strategy == "bitmap":
        if id_range is None:
            raise ValueError("strategy='bitmap' needs id_range to size the bitmap")
        pw = packed_bits(width)
        bits = pw if int(id_range) <= pw else _ceil32(id_range)
        if bits > BITMAP_MAX_BITS:
            raise ValueError(
                f"strategy='bitmap' would need a {bits}-bit bitmap for id "
                f"range {int(id_range)} (cap: BITMAP_MAX_BITS={BITMAP_MAX_BITS}); "
                f"use strategy='probe' (or 'auto') for this bucket"
            )
    return strategy, bits


def _probe_mask(u_lists, v_lists):
    """Probe-core membership mask: binary-search each u element in v."""

    def one(u, v):
        pos = jnp.clip(jnp.searchsorted(v, u), 0, v.shape[0] - 1)
        return v[pos] == u

    return jax.vmap(one)(u_lists, v_lists)


def _resolve_mask_args(u_lists, v_lists, strategy, bitmap_bits):
    """Shared strategy resolution for the mask entry points: "auto" uses
    the concrete id range when available (``choose_mask_strategy``), the
    width-only rule under tracing; forced bitmap sizes its capacity."""
    if strategy == "auto":
        strategy, bits = resolve_mask_strategy(
            u_lists.shape[1], _auto_id_range(u_lists, v_lists)
        )
        if strategy == "bitmap":
            bitmap_bits = bits
    elif strategy == "bitmap" and bitmap_bits is None:
        _, bitmap_bits = resolve_mask_strategy(
            u_lists.shape[1], _auto_id_range(u_lists, v_lists),
            strategy="bitmap",
        )
    elif strategy not in STRATEGIES:
        raise ValueError(
            f"unknown strategy {strategy!r}; expected 'auto' or one of {STRATEGIES}"
        )
    return strategy, bitmap_bits


def intersect_matches(
    u_lists: jnp.ndarray,
    v_lists: jnp.ndarray,
    *,
    strategy: str = "auto",
    bitmap_bits=None,
) -> jnp.ndarray:
    """Per-position membership mask: which u-list entries appear in v.

    The mask form of ``intersect_counts`` — summing the result along the
    last axis gives exactly the per-edge intersection sizes — consumed by
    the engine's "vertex" and "edge" analysis executables, which need to
    know WHICH common neighbor matched so they can scatter the triangle to
    its three vertices / three edges. All three strategies apply: broadcast
    (eq-any over the (E, W, W) compare tensor, row-chunked as the count
    core's), probe (searchsorted), and
    bitmap (pack v, gather-test each u element — the TRUST-style core,
    picked by "auto" exactly when the id range fits the packed width).

    Args:
      u_lists: (E, W) int32, each row a sorted neighbor list padded with a
        sentinel disjoint from v's.
      v_lists: (E, W) int32, same layout, disjoint padding sentinel.
      strategy: "auto" | "broadcast" | "probe" | "bitmap" — "auto" applies
        ``choose_mask_strategy``.
      bitmap_bits: static bitmap capacity for strategy="bitmap"; must cover
        the id range for exact agreement with the other strategies.

    Returns:
      (E, W) bool — ``out[e, j]`` iff ``u_lists[e, j]`` occurs in
      ``v_lists[e]``. Padding positions are never True (disjoint sentinels).
    """
    strategy, bitmap_bits = _resolve_mask_args(u_lists, v_lists,
                                               strategy, bitmap_bits)
    if strategy == "broadcast":
        return _row_chunked(_matches_broadcast, u_lists, v_lists)
    if strategy == "bitmap":
        return intersect_matches_bitmap(u_lists, v_lists,
                                        num_bits=int(bitmap_bits))
    return _probe_mask(u_lists, v_lists)


def intersect_matches_both(
    u_lists: jnp.ndarray,
    v_lists: jnp.ndarray,
    *,
    strategy: str = "auto",
    bitmap_bits=None,
) -> tuple:
    """Both directions of ``intersect_matches`` in one call.

    Returns ``(matched_u, matched_v)`` — (E, W) bool masks of which u-list
    positions occur in v and which v-list positions occur in u. For every
    common element there is exactly one True in each mask (rows are
    deduplicated neighbor lists), so both masks row-sum to the same
    per-edge intersection sizes. The broadcast core shares each row
    block's eq tensor between the two reductions; probe and bitmap each run two
    passes with the roles swapped. The engine's "edge" executables consume
    both masks to group triangle contributions by u-row and v-row
    respectively.
    """
    strategy, bitmap_bits = _resolve_mask_args(u_lists, v_lists,
                                               strategy, bitmap_bits)
    if strategy == "broadcast":
        return _row_chunked(_matches_both_broadcast, u_lists, v_lists)
    if strategy == "bitmap":
        bits = int(bitmap_bits)
        return (intersect_matches_bitmap(u_lists, v_lists, num_bits=bits),
                intersect_matches_bitmap(v_lists, u_lists, num_bits=bits))
    return _probe_mask(u_lists, v_lists), _probe_mask(v_lists, u_lists)


def choose_mask_strategy(width: int, id_range=None, platform=None) -> str:
    """The ``strategy="auto"`` cost model for MASK consumers
    (``intersect_matches`` / ``intersect_matches_both``).

    The mask entry points pay differently than the counting ones: probe
    masks run TWO vmapped searchsorted passes (one per direction) while the
    bitmap mask packs each side once and then does O(W) word gathers — so
    bitmap stays the winner well past the counting lane's
    ``id_range ≤ packed_bits(width)`` rule. Measured on the CPU jnp paths
    the crossover sits near B ≈ 4·W packed bits, which is the bound used
    here (capped by ``BITMAP_MAX_BITS`` as everywhere).

    The masks run on the ``jnp`` cores only, so the count rule's TPU
    exception holds for every width: the vmapped ``searchsorted`` is
    gather-bound there, and the ``strategy`` phase of ``chip_smoke.py``
    times the probe and broadcast masks beside the counts.

    Args:
      width: the bucket's padded list width W (static).
      id_range: number of distinct ids the lists may contain (the engine
        passes ``n + 2``); None (e.g. under tracing) disqualifies bitmap.
      platform: where it runs; None means JAX's default backend.

    Returns:
      "bitmap" | "probe" | "broadcast".
    """
    if id_range is not None:
        bits = _ceil32(id_range)
        if bits <= BITMAP_MAX_BITS and bits <= 4 * packed_bits(width):
            return "bitmap"
    if (platform or jax.default_backend()) == "tpu":
        return "broadcast"
    if width >= _PROBE_MIN_WIDTH:
        return "probe"
    return "broadcast"


def resolve_mask_strategy(width: int, id_range=None, strategy: str = "auto"):
    """Resolve an ("auto" or explicit) MASK strategy to (strategy, bitmap_bits).

    The mask analogue of ``resolve_strategy``: "auto" applies
    ``choose_mask_strategy``; an explicit "bitmap" sizes its capacity from
    the id range (word-rounded), with the same ``BITMAP_MAX_BITS`` refusal.

    Raises:
      ValueError: bitmap forced with no ``id_range``, an id range past the
        packed-bits cap, or an unknown strategy name.
    """
    if strategy == "auto":
        strategy = choose_mask_strategy(width, id_range)
    if strategy not in STRATEGIES:
        raise ValueError(
            f"unknown strategy {strategy!r}; expected 'auto' or one of {STRATEGIES}"
        )
    bits = None
    if strategy == "bitmap":
        if id_range is None:
            raise ValueError("strategy='bitmap' needs id_range to size the bitmap")
        bits = _ceil32(id_range)
        if bits > BITMAP_MAX_BITS:
            raise ValueError(
                f"strategy='bitmap' would need a {bits}-bit bitmap for id "
                f"range {int(id_range)} (cap: BITMAP_MAX_BITS={BITMAP_MAX_BITS}); "
                f"use strategy='probe' (or 'auto') for this bucket"
            )
    return strategy, bits


def _pad_rows(u_lists, v_lists, tile_edges: int):
    """Sentinel-pad (E, W) pairs to an E that is a multiple of tile_edges.

    Padding rows use u=-1, v=-2: disjoint (and negative, so also masked by
    the bitmap core) ⇒ they contribute zero matches.
    """
    e = u_lists.shape[0]
    pad = (-e) % tile_edges
    if pad:
        u_lists = jnp.concatenate(
            [u_lists, jnp.full((pad, u_lists.shape[1]), -1, u_lists.dtype)]
        )
        v_lists = jnp.concatenate(
            [v_lists, jnp.full((pad, v_lists.shape[1]), -2, v_lists.dtype)]
        )
    return u_lists, v_lists, e, pad


# compare-matrix elements materialized per lax.map step of the jnp broadcast
# path — bounds memory at ~16M bools however large the bucket is
_BROADCAST_CHUNK_ELEMS = 1 << 24


def _row_chunked(core, u_lists, v_lists):
    """``core(u, v)`` over blocks of rows, under ``lax.map``, so that at most
    ``_BROADCAST_CHUNK_ELEMS`` compares live at once. ``core`` returns
    arrays (or a tuple of them) whose leading axis is the block's rows;
    rows padded to fill the last block are cut off again."""
    e, w = u_lists.shape
    chunk = int(max(1, min(max(e, 1), _BROADCAST_CHUNK_ELEMS // max(w * w, 1))))
    u_lists, v_lists, e, pad = _pad_rows(u_lists, v_lists, chunk)
    uc = u_lists.reshape(-1, chunk, w)
    vc = v_lists.reshape(-1, chunk, w)
    out = jax.lax.map(lambda ab: core(ab[0], ab[1]), (uc, vc))

    def rows(x):
        x = x.reshape((-1,) + x.shape[2:])
        return x[:e] if pad else x

    return jax.tree.map(rows, out)


@jax.jit
def _broadcast_jnp(u_lists, v_lists):
    """jnp broadcast-compare counts, chunked over rows to bound the (E, W, W)
    compare tensor (same algorithm as the pallas broadcast kernel)."""
    return _row_chunked(intersect_counts_ref, u_lists, v_lists)


def _matches_broadcast(u_lists, v_lists):
    return (u_lists[:, :, None] == v_lists[:, None, :]).any(axis=2)


def _matches_both_broadcast(u_lists, v_lists):
    eq = u_lists[:, :, None] == v_lists[:, None, :]
    return eq.any(axis=2), eq.any(axis=1)


def _auto_id_range(u_lists, v_lists):
    """Best-effort id range from concrete inputs; None under tracing.

    Rows are sorted ascending (the repo-wide contract every core relies on),
    so each row's max is its last column — an O(E) reduction, not O(E·W).
    """
    if isinstance(u_lists, jax.core.Tracer) or isinstance(v_lists, jax.core.Tracer):
        return None
    if u_lists.shape[0] == 0 or u_lists.shape[1] == 0:
        return 0
    hi = max(int(jnp.max(u_lists[:, -1])), int(jnp.max(v_lists[:, -1])), -1)
    return hi + 1


def intersect_counts(
    u_lists: jnp.ndarray,
    v_lists: jnp.ndarray,
    *,
    strategy: str = "auto",
    backend: str = "jnp",
    tile_edges: int = 256,
    interpret: bool = True,
    bitmap_bits=None,
) -> jnp.ndarray:
    """Dispatch per-edge intersection counts. Shapes (E, W) ×2 → (E,) int32.

    Args:
      u_lists: (E, W) int32; each row a sorted neighbor list, padded with a
        sentinel value disjoint from v's.
      v_lists: (E, W) int32, same layout, disjoint padding sentinel.
      strategy: "broadcast" | "probe" | "bitmap" | "auto". "auto" applies
        ``choose_strategy`` using the concrete id range when available
        (falling back to the width-only probe/broadcast rule under tracing).
      backend: "pallas" (TPU kernels), "jnp" (pure-jnp production path), or
        "ref" (the broadcast-compare oracle, strategy-independent).
      tile_edges: pallas grid tile height; E is sentinel-row-padded to a
        multiple of it and the padding stripped from the result.
      interpret: pallas interpret mode (True = run kernel bodies on CPU).
      bitmap_bits: static bitmap capacity for strategy="bitmap" only
        (multiple of 32); never consulted by the "auto" selector. Defaults
        to the concrete id range rounded up; required when tracing. Ids ≥
        bitmap_bits never match — callers wanting exact agreement with the
        other strategies must cover the full id range.

    Returns:
      (E,) int32 per-edge |N(u) ∩ N(v)|.
    """
    if backend not in ("pallas", "jnp", "ref"):
        raise ValueError(f"unknown backend {backend!r}")
    if backend == "ref":
        return intersect_counts_ref(u_lists, v_lists)

    if strategy == "auto":
        # derive the id range from the data (the engine pre-resolves with the
        # graph's true id range instead); under tracing this is None and the
        # width-only probe/broadcast rule applies, so auto never selects a
        # bitmap whose capacity the data wasn't checked against
        strategy, bits = resolve_strategy(
            u_lists.shape[1], _auto_id_range(u_lists, v_lists),
            backend=backend,
        )
        if strategy == "bitmap":
            bitmap_bits = bits
    elif strategy == "bitmap" and bitmap_bits is None:
        _, bitmap_bits = resolve_strategy(
            u_lists.shape[1], _auto_id_range(u_lists, v_lists),
            strategy="bitmap",
        )
    elif strategy not in STRATEGIES:
        raise ValueError(
            f"unknown strategy {strategy!r}; expected 'auto' or one of {STRATEGIES}"
        )

    if backend == "jnp":
        if strategy == "broadcast":
            return _broadcast_jnp(u_lists, v_lists)
        if strategy == "probe":
            return intersect_counts_probe(u_lists, v_lists)
        return intersect_counts_bitmap(u_lists, v_lists, num_bits=int(bitmap_bits))

    # backend == "pallas": tile the edge axis, strip padding on the way out
    u_lists, v_lists, e, pad = _pad_rows(u_lists, v_lists, tile_edges)
    if strategy == "broadcast":
        out = intersect_counts_pallas(
            u_lists, v_lists, tile_edges=tile_edges, interpret=interpret
        )
    elif strategy == "probe":
        out = intersect_counts_probe_pallas(
            u_lists, v_lists, tile_edges=tile_edges, interpret=interpret
        )
    else:
        out = intersect_counts_bitmap_pallas(
            u_lists, v_lists, num_bits=int(bitmap_bits),
            tile_edges=tile_edges, interpret=interpret,
        )
    return out[:e] if pad else out
