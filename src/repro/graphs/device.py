"""Device-resident graph containers and jitted prep primitives.

Everything the triangle-counting *prep* stage used to do in per-graph host
numpy — CSR construction, degree-rank forward orientation, padded neighbor
gathers, degree-class bucket layout — reformulated as statically-shaped JAX
computations so batch workloads are kernel-bound, not prep-bound (the
TRUST-style decoupling of GPU-resident preprocessing from counting, and the
Wang & Owens formulation of orientation/filtering as device primitives).

Static shapes are the whole game: every jitted stage here is keyed on shapes
only, so the retrace/recompile cost is paid once per *shape class*, not once
per graph. ``ShapePolicy`` defines the shape classes — it rounds every
data-dependent extent (edge-array lengths, per-bucket edge counts) up to the
next power of two, padding with the repo-wide whole-row sentinels (``-1`` for
u rows, ``-2`` for v rows, which every intersection core treats as zero
matches). Two graphs prepped under the same policy whose rounded extents
collide share every traced prep stage AND every counting executable — which
is what lets ``GraphBatch`` (see ``repro.core.engine``) stack a whole batch
of generated graphs into one vmapped device dispatch.

Containers:

* ``DeviceCSR``   — the raw device-resident CSR arrays (``row_ptr``,
                    ``col_idx`` padded to a policy-rounded length), plus a
                    jitted sort-based builder ``from_edges``.
* ``DeviceGraph`` — a ``DeviceCSR`` + ``ShapePolicy`` with cached derived
                    structure: the forward-oriented edge set, padded
                    neighbor matrices, and the bucket sort the prep lanes
                    in ``repro.core.prep`` consume.

Sentinel conventions (repo-wide, see ``repro.kernels.intersect.ops``): in-row
padding uses ``n`` (u side) / ``n + 1`` (v side); whole padding rows use
``-1`` / ``-2``; padded ``col_idx`` slots use ``n``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.graphs.formats import Graph

__all__ = [
    "DEFAULT_SHAPE_POLICY",
    "EDGE_KEY_SENTINEL",
    "WIDE_EDGE_KEY_SENTINEL",
    "DeviceCSR",
    "DeviceGraph",
    "GraphTooLargeError",
    "ShapePolicy",
    "ShardedBucket",
    "ShardedDeviceCSR",
    "bfs_levels",
    "deal_across_shards",
    "dynamic_update_step",
    "edge_key_context",
    "edge_key_dtype",
    "edge_key_sentinel",
    "fits_int32_pair_keys",
    "next_pow2",
    "resolve_edge_key_mode",
    "shard_valid_counts",
]

# Dead slots in a sorted packed-edge-key array (the dynamic lane's edge-set
# container) carry this value, so they sort past every real lo*(n+1)+hi key
# (real keys are < (n+1)^2 <= int32 max by fits_int32_pair_keys). The wide
# (int64) key mode uses WIDE_EDGE_KEY_SENTINEL the same way; prefer
# ``edge_key_sentinel(mode)`` over the raw constants.
EDGE_KEY_SENTINEL: int = int(np.iinfo(np.int32).max)
WIDE_EDGE_KEY_SENTINEL: int = int(np.iinfo(np.int64).max)

#: Valid values for every ``key_mode`` parameter in the repo.
EDGE_KEY_MODES: Tuple[str, ...] = ("auto", "int32", "wide")


class GraphTooLargeError(ValueError):
    """The graph exceeds a lane's packed-edge-key capacity.

    Raised from the single checkpoint :func:`resolve_edge_key_mode` when a
    graph cannot be represented in the requested key mode: either
    ``key_mode="int32"`` was forced past ``fits_int32_pair_keys`` (n ≲ 46k),
    or n is so large that even int64 keys would overflow (n ≳ 3e9). The
    message names the lanes/modes that *do* support the graph."""


def next_pow2(x: int) -> int:
    """Smallest power of two ≥ ``x`` (and ≥ 1)."""
    x = int(x)
    return 1 if x <= 1 else 1 << (x - 1).bit_length()


def fits_int32_pair_keys(n: int) -> bool:
    """Whether ``(n + 1)²`` fits the int32 range — the bound behind the fast
    path of every packed ``a * (n + 1) + b`` vertex-pair key in the repo
    (``DeviceCSR.from_edges`` sort keys, the edge lane's undirected-edge
    keys). x64 is off by default, so int32 keys are the fast path; past
    n ≲ 46k the key layer promotes to the wide (x64 int64) mode — see
    :func:`resolve_edge_key_mode`."""
    return (n + 1) ** 2 <= np.iinfo(np.int32).max


def fits_int64_pair_keys(n: int) -> bool:
    """Whether ``(n + 1)²`` fits the int64 range (n ≲ 3e9) — the hard bound
    of the wide key mode, i.e. the only n bound the hardware imposes."""
    return (n + 1) ** 2 <= np.iinfo(np.int64).max


def resolve_edge_key_mode(n: int, key_mode: str = "auto", *,
                          lane: str = "edge") -> str:
    """THE capacity checkpoint: resolve a requested key mode for a graph.

    Every packed-pair-key construction site in the repo routes its capacity
    decision through here (grep-audited in ``tests/test_capacity.py``), so
    there is exactly one place that can raise :class:`GraphTooLargeError`
    and no site can silently overflow.

    Args:
      n: vertex count.
      key_mode: "auto" (int32 when it fits, else wide), "int32" (force the
        fast path; raises past the bound), or "wide" (force x64 int64 keys).
      lane: name used in error messages ("edge", "dynamic", ...).

    Returns:
      The resolved concrete mode: "int32" or "wide".

    Raises:
      GraphTooLargeError: ``key_mode="int32"`` past ``fits_int32_pair_keys``,
        or n past ``fits_int64_pair_keys`` in any mode.
    """
    if key_mode not in EDGE_KEY_MODES:
        raise ValueError(
            f"key_mode must be one of {EDGE_KEY_MODES}, got {key_mode!r}"
        )
    if not fits_int64_pair_keys(n):
        raise GraphTooLargeError(
            f"the {lane} lane packs vertex pairs into (n+1)-radix keys and "
            f"(n+1)^2 overflows even int64 for n={n}; no key mode supports "
            f"this graph (the matrix / hash / bfs lanes use no packed keys "
            f"and remain available)"
        )
    if fits_int32_pair_keys(n):
        return "wide" if key_mode == "wide" else "int32"
    if key_mode == "int32":
        raise GraphTooLargeError(
            f"the {lane} lane was forced to key_mode='int32' but "
            f"(n+1)^2 > int32 max for n={n} (the int32 fast path needs "
            f"n <= 46339); use key_mode='auto' or 'wide' for this graph, "
            f"or the matrix / hash / bfs lanes, which use no packed keys"
        )
    return "wide"


def edge_key_dtype(mode: str) -> np.dtype:
    """Host/device dtype of packed edge keys in a resolved key mode."""
    return np.dtype(np.int64) if mode == "wide" else np.dtype(np.int32)


def edge_key_sentinel(mode: str) -> int:
    """Dead-slot sentinel (dtype max) of a resolved key mode."""
    return WIDE_EDGE_KEY_SENTINEL if mode == "wide" else EDGE_KEY_SENTINEL


def edge_key_context(mode: str):
    """Context manager every wide-mode device computation runs under.

    jax demotes int64 results to int32 whenever an op runs outside an
    ``enable_x64`` scope — even on arrays created inside one — so BOTH the
    trace and every call of a wide-key executable must be wrapped. The
    int32 fast path gets a no-op context, keeping call sites uniform."""
    return jax.enable_x64(True) if mode == "wide" else contextlib.nullcontext()


@dataclasses.dataclass(frozen=True)
class ShapePolicy:
    """How data-dependent extents are rounded into static shape classes.

    Attributes:
      edge_rounding: "pow2" (default) rounds every edge extent — uploaded
        ``col_idx`` length, per-bucket edge counts — up to the next power of
        two, so same-policy graphs of similar size land in identical shape
        classes and share traced prep stages and counting executables.
        "exact" keeps true extents (minimal padding, maximal retracing) —
        the parity-testing configuration.
      min_edges: floor on any rounded extent; keeps tiny buckets from
        fragmenting the executable cache into near-duplicate shapes.

    Frozen ⇒ hashable: a policy participates in ``CountOptions`` equality
    and therefore in the engine's executable-cache keys (``key()`` is the
    normalized tuple used there).
    """

    edge_rounding: str = "pow2"
    min_edges: int = 8

    def __post_init__(self):
        if self.edge_rounding not in ("pow2", "exact"):
            raise ValueError(
                f"edge_rounding must be 'pow2' or 'exact', "
                f"got {self.edge_rounding!r}"
            )
        if not isinstance(self.min_edges, int) or isinstance(self.min_edges, bool) \
                or self.min_edges < 1:
            raise ValueError(
                f"min_edges must be a positive int, got {self.min_edges!r}"
            )

    def round_edges(self, count: int) -> int:
        """The static extent an array of ``count`` edge rows is padded to."""
        count = int(count)
        if self.edge_rounding == "exact":
            return max(count, 1)
        return max(self.min_edges, next_pow2(count))

    def key(self) -> tuple:
        """Hashable identity used in options/cache keys."""
        return (self.edge_rounding, self.min_edges)


DEFAULT_SHAPE_POLICY = ShapePolicy()


# ---------------------------------------------------------------------------
# Jitted primitives — every static_argnames set is a shape class
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n", "m_pad"))
def _edge_sources(row_ptr: jnp.ndarray, *, n: int, m_pad: int) -> jnp.ndarray:
    """src[i] = CSR row owning slot i (the device analogue of np.repeat)."""
    slots = jnp.arange(m_pad, dtype=jnp.int32)
    src = jnp.searchsorted(row_ptr, slots, side="right") - 1
    return jnp.clip(src, 0, max(n - 1, 0)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n", "m_pad", "wide"))
def _csr_from_edges(src: jnp.ndarray, dst: jnp.ndarray, valid: jnp.ndarray,
                    *, n: int, m_pad: int, wide: bool = False):
    """Sort-based CSR build from a (possibly unsorted, masked) edge list.

    Assumes the valid (src, dst) pairs are deduplicated directed edges.
    Invalid slots sort to the end. Returns (row_ptr, col_idx, m) where
    ``col_idx`` is padded with the sentinel ``n`` and ``m`` is the valid
    edge count (a device scalar). Sort keys (and the ``row_starts`` probe
    vector) are int32 on the fast path and int64 when ``wide`` — the caller
    resolves the mode through ``resolve_edge_key_mode`` and wraps wide
    calls in ``edge_key_context``.
    """
    kdt = jnp.int64 if wide else jnp.int32
    big = jnp.asarray(np.iinfo(np.int64 if wide else np.int32).max, kdt)
    key = jnp.where(
        valid,
        src.astype(kdt) * jnp.asarray(n + 1, kdt) + dst.astype(kdt),
        big,
    )
    order = jnp.argsort(key)
    skey = key[order]
    m = valid.sum()
    col = jnp.where(jnp.arange(m_pad) < m, dst[order], n).astype(jnp.int32)
    row_starts = jnp.arange(n + 1, dtype=kdt) * jnp.asarray(n + 1, kdt)
    row_ptr = jnp.searchsorted(skey, row_starts, side="left").astype(jnp.int32)
    return row_ptr, col, m.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n", "m_pad", "mf_pad"))
def _orient_forward_dev(row_ptr: jnp.ndarray, col_idx: jnp.ndarray,
                        m, *, n: int, m_pad: int, mf_pad: int):
    """Degree-rank forward orientation, compacted to static shape.

    Keeps u→v iff rank(u) < rank(v) with rank = (degree, id) — the paper's
    'filter out half the edges by degree order'. The kept edges (exactly
    m // 2 of them) occupy the leading slots of the returned arrays in CSR
    order; ``kvalid`` marks them. Returns
    (fwd_src, fwd_dst, kvalid, fwd_row_ptr, fwd_deg).
    """
    src = _edge_sources(row_ptr, n=n, m_pad=m_pad)
    dst = col_idx
    valid = jnp.arange(m_pad) < m
    deg = jnp.diff(row_ptr)
    du = deg[src]
    dv = deg[jnp.clip(dst, 0, max(n - 1, 0))]
    keep = valid & ((du < dv) | ((du == dv) & (src < dst)))
    order = jnp.argsort(~keep)  # stable: kept edges first, CSR order intact
    take = order[:mf_pad]
    kvalid = keep[take]
    fsrc = jnp.where(kvalid, src[take], 0).astype(jnp.int32)
    fdst = jnp.where(kvalid, dst[take], 0).astype(jnp.int32)
    fdeg = jax.ops.segment_sum(
        kvalid.astype(jnp.int32), fsrc, num_segments=max(n, 1)
    )[:n]
    frow_ptr = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(fdeg).astype(jnp.int32)]
    )
    return fsrc, fdst, kvalid, frow_ptr, fdeg


@functools.partial(jax.jit, static_argnames=("n", "width"))
def _padded_neighbors_dev(src: jnp.ndarray, dst: jnp.ndarray,
                          valid: jnp.ndarray, row_ptr: jnp.ndarray,
                          *, n: int, width: int) -> jnp.ndarray:
    """(n, width) neighbor matrix padded with the in-row sentinel ``n``.

    Edge slot i lands at column ``i - row_ptr[src[i]]`` (edges are in CSR
    order, so each row's slots are contiguous); invalid slots scatter out of
    bounds and are dropped.
    """
    pos = jnp.arange(src.shape[0], dtype=jnp.int32) - row_ptr[src]
    pos = jnp.where(valid, pos, width)  # out of bounds ⇒ dropped
    out = jnp.full((n, width), n, dtype=jnp.int32)
    return out.at[src, pos].set(dst.astype(jnp.int32), mode="drop")


@functools.partial(jax.jit, static_argnames=("n", "num_bounds"))
def _bucket_sort_dev(src: jnp.ndarray, dst: jnp.ndarray, valid: jnp.ndarray,
                     deg: jnp.ndarray, bounds: jnp.ndarray,
                     *, n: int, num_bounds: int):
    """Stable-sort edges into degree-class buckets.

    Bucket of an edge = first bound ≥ max(deg[src], deg[dst]) (the paper's
    TwoSmall/TwoLarge grouping, statically shaped); invalid slots sort into
    a trailing overflow class. Returns (sorted_src, sorted_dst, counts,
    starts) with counts/starts per real bucket.
    """
    lim = max(n - 1, 0)
    w = jnp.maximum(deg[jnp.clip(src, 0, lim)], deg[jnp.clip(dst, 0, lim)])
    b = jnp.searchsorted(bounds, w, side="left")
    b = jnp.where(valid, b, num_bounds).astype(jnp.int32)
    order = jnp.argsort(b)  # stable: CSR order preserved within a bucket
    counts = jnp.bincount(b, length=num_bounds + 1)[:num_bounds]
    starts = jnp.concatenate(
        [jnp.zeros(1, counts.dtype), jnp.cumsum(counts)]
    )[:num_bounds]
    return src[order], dst[order], counts, starts


@functools.partial(jax.jit, static_argnames=("n", "e_pad", "width"))
def _gather_bucket_dev(sorted_src: jnp.ndarray, sorted_dst: jnp.ndarray,
                       start, count, nbrs: jnp.ndarray,
                       *, n: int, e_pad: int, width: int):
    """Materialize one bucket's padded (e_pad, width) neighbor-list pair.

    Rows past ``count`` are whole-row padding: u = -1, v = -2 (disjoint ⇒
    zero matches in every intersection core). Within real rows, u keeps the
    in-row sentinel ``n`` and v's is rewritten to ``n + 1``. Returns
    (u_lists, v_lists, src, dst); padded rows carry src = dst = 0, which is
    safe for the per-vertex scatters because their match counts are zero.
    """
    rows = jnp.arange(e_pad)
    bvalid = rows < count
    lim = max(sorted_src.shape[0] - 1, 0)
    idx = jnp.clip(start + rows, 0, lim)
    sb = jnp.where(bvalid, sorted_src[idx], 0).astype(jnp.int32)
    db = jnp.where(bvalid, sorted_dst[idx], 0).astype(jnp.int32)
    u = jnp.where(bvalid[:, None], nbrs[sb, :width], -1).astype(jnp.int32)
    vfull = nbrs[db, :width]
    v = jnp.where(
        bvalid[:, None], jnp.where(vfull == n, n + 1, vfull), -2
    ).astype(jnp.int32)
    return u, v, sb, db


@functools.partial(jax.jit, static_argnames=("n1", "wide"))
def _sorted_edge_keys_dev(src: jnp.ndarray, dst: jnp.ndarray,
                          valid: jnp.ndarray, *, n1: int,
                          wide: bool = False):
    """Sorted packed keys of a masked undirected edge list, plus the sort
    permutation.

    Each live slot's key is ``min(src, dst) * n1 + max(src, dst)`` (``n1`` =
    n + 1, so keys of distinct edges are distinct and ascending keys are
    ascending (lo, hi) pairs — the same order as a host
    ``edge_list_unique``). Dead slots take the key-dtype max sentinel and
    sort to the end, so the leading ``valid.sum()`` entries are the real
    edges. Returns ``(sorted_keys, perm)`` with ``sorted_keys = keys[perm]``
    — ``perm`` maps sorted-key positions back to edge slots, which is how
    the engine reorders its slot-indexed support vectors into key order.
    Keys are int32 on the fast path, int64 when ``wide`` (the caller
    resolves the mode through ``resolve_edge_key_mode`` and wraps wide
    calls in ``edge_key_context``).
    """
    kdt = jnp.int64 if wide else jnp.int32
    lo = jnp.minimum(src, dst).astype(kdt)
    hi = jnp.maximum(src, dst).astype(kdt)
    key = jnp.where(valid, lo * jnp.asarray(n1, kdt) + hi,
                    jnp.asarray(np.iinfo(np.int64 if wide else np.int32).max,
                                kdt))
    perm = jnp.argsort(key)
    return key[perm], perm.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n",))
def _two_core_peel_dev(src: jnp.ndarray, dst: jnp.ndarray,
                       valid: jnp.ndarray, init_alive: jnp.ndarray, *, n: int):
    """Fixed-point 2-core peel over a masked static edge list."""
    lim = max(n - 1, 0)
    dst_c = jnp.clip(dst, 0, lim)

    def cond(state):
        _, changed = state
        return changed

    def body(state):
        alive, _ = state
        contrib = (valid & alive[src] & alive[dst_c]).astype(jnp.int32)
        deg = jax.ops.segment_sum(contrib, src, num_segments=n)
        new_alive = alive & (deg >= 2)
        return new_alive, jnp.any(new_alive != alive)

    alive, _ = jax.lax.while_loop(cond, body, (init_alive, jnp.array(True)))
    return alive


@functools.partial(jax.jit, static_argnames=("n",))
def _bfs_levels_dev(src: jnp.ndarray, dst: jnp.ndarray,
                    valid: jnp.ndarray, *, n: int) -> jnp.ndarray:
    """Multi-source BFS levels over a masked static directed edge list.

    Sources are the id-local-minima — vertices with no smaller-id neighbor —
    so every connected component contains at least one (its minimum-id
    vertex) and isolated vertices are their own sources; every vertex
    therefore ends at a finite level. Levels relax as a frontier fixpoint:
    ``lvl[v] = min(lvl[v], 1 + min over in-edges of lvl[u])``, one
    ``scatter-min`` per round, while_loop until no level changes. No packed
    pair keys ⇒ no n ≲ 46k bound.
    """
    lim = max(n - 1, 0)
    src_c = jnp.clip(src, 0, lim)
    dst_c = jnp.clip(dst, 0, lim)
    inf = jnp.int32(n)  # BFS levels are hop counts < n

    has_smaller = jnp.zeros((n,), bool).at[dst_c].max(
        valid & (src < dst), mode="drop"
    )
    lvl0 = jnp.where(has_smaller, inf, 0).astype(jnp.int32)

    def cond(state):
        _, changed = state
        return changed

    def body(state):
        lvl, _ = state
        through = jnp.where(valid, lvl[src_c] + 1, inf)
        cand = jnp.full((n,), inf, jnp.int32).at[dst_c].min(through, mode="drop")
        new = jnp.minimum(lvl, cand)
        return new, jnp.any(new != lvl)

    lvl, _ = jax.lax.while_loop(cond, body, (lvl0, jnp.array(n > 0)))
    return lvl


def bfs_levels(dg: "DeviceGraph") -> jnp.ndarray:
    """(n,) int32 BFS levels of a ``DeviceGraph`` (see ``_bfs_levels_dev``).

    The BFS counting lane orders vertices by ``(level, id)`` — a total order,
    so orienting every edge toward its larger-rank endpoint yields a DAG in
    which each triangle has exactly one wedge vertex (its rank-minimum) and
    is closed exactly once.
    """
    return _bfs_levels_dev(dg.edge_sources(), dg.csr.col_idx,
                           dg.edge_valid(), n=dg.n)


@functools.partial(jax.jit, static_argnames=("n", "m_pad"))
def _induced_compact_dev(row_ptr: jnp.ndarray, col_idx: jnp.ndarray,
                         alive: jnp.ndarray, m, *, n: int, m_pad: int):
    """Compact the directed edges with both endpoints alive (CSR order kept).

    Vertex ids are NOT renumbered — dead vertices simply end up with empty
    rows, so downstream per-vertex scatters stay in original-id space.
    Returns (row_ptr_sub, col_sub, kept) with ``col_sub`` padded with ``n``.
    """
    src = _edge_sources(row_ptr, n=n, m_pad=m_pad)
    valid = jnp.arange(m_pad) < m
    lim = max(n - 1, 0)
    keep = valid & alive[src] & alive[jnp.clip(col_idx, 0, lim)]
    order = jnp.argsort(~keep)  # stable compaction
    ksrc = src[order]
    kval = keep[order]
    col = jnp.where(kval, col_idx[order], n).astype(jnp.int32)
    deg = jax.ops.segment_sum(
        kval.astype(jnp.int32), jnp.where(kval, ksrc, 0),
        num_segments=max(n, 1),
    )[:n]
    row_ptr_sub = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(deg).astype(jnp.int32)]
    )
    return row_ptr_sub, col, keep.sum()


def _anchor_rows(keys: jnp.ndarray, rkeys: jnp.ndarray, verts: jnp.ndarray,
                 valid: jnp.ndarray, *, n: int, width: int):
    """Gather padded adjacency rows for a batch of anchor vertices straight
    from the two sorted key orderings — no materialized (n, W) matrix.

    For vertex v, the forward run of ``keys`` (sorted ``lo*(n+1)+hi``)
    holds its neighbors > v and the run of ``rkeys`` (sorted
    ``hi*(n+1)+lo``) its neighbors < v; both runs are located with two
    searchsorted probes and are ascending, so emitting the reverse run
    first yields a globally ascending row (the probe/bitmap cores require
    sorted rows) padded with the in-row sentinel ``n``. Invalid anchors get
    all-padding rows and degree 0. Returns ``(rows (B, width), deg (B,))``.
    Key dtype (int32 fast path / int64 wide mode) follows ``keys``.
    """
    cap = int(keys.shape[0])
    kdt = keys.dtype
    n1 = jnp.asarray(n + 1, kdt)
    v = jnp.clip(verts, 0, max(n - 1, 0)).astype(kdt)
    base = v * n1
    # run boundaries: all of v's keys lie in [v*n1, v*n1 + n) and the
    # resolve_edge_key_mode checkpoint keeps v*n1 + n in the key range
    sf = jnp.searchsorted(keys, base)
    ef = jnp.searchsorted(keys, base + jnp.asarray(n, kdt))
    sr = jnp.searchsorted(rkeys, base)
    er = jnp.searchsorted(rkeys, base + jnp.asarray(n, kdt))
    df = jnp.where(valid, ef - sf, 0)
    dr = jnp.where(valid, er - sr, 0)
    lanes = jnp.arange(width, dtype=jnp.int32)[None, :]
    rev = rkeys[jnp.clip(sr[:, None] + lanes, 0, cap - 1)] % n1
    fwd = keys[jnp.clip(sf[:, None] + lanes - dr[:, None], 0, cap - 1)] % n1
    rows = jnp.where(
        lanes < dr[:, None], rev,
        jnp.where(lanes < (dr + df)[:, None], fwd, jnp.int32(n)))
    return rows.astype(jnp.int32), (df + dr).astype(jnp.int32)


def dynamic_update_step(keys: jnp.ndarray, rkeys: jnp.ndarray,
                        upd_keys: jnp.ndarray, upd_rkeys: jnp.ndarray,
                        upd_ins: jnp.ndarray, upd_valid: jnp.ndarray,
                        *, n: int, width: int):
    """One traced step of the dynamic lane: apply a batched edge update to
    the device-resident edge set in place.

    The edge set is kept in TWO sorted orderings of packed keys (int32 fast
    path / int64 wide mode, dtype follows ``keys``) — ``keys`` by
    ``lo*(n+1)+hi`` and ``rkeys`` by ``hi*(n+1)+lo`` — each with capacity
    ``keys.shape[0]`` (a ``ShapePolicy`` pow2 class) and the key-dtype max
    sentinel in dead slots. Together the two orderings ARE the
    adjacency structure: any vertex's neighbor row is two contiguous runs,
    so per-batch work stays O(batch) gathers plus two capacity-length
    sorts — no O(n·width) CSR / neighbor-matrix rebuild per step. The step:

    1. *resolve* — membership-test the batch against the current key set:
       effective deletes are requested deletes that are present, effective
       inserts are requested inserts that are absent (set semantics; the
       sorted side arrays feed the engine's delta executables).
    2. *apply* — tombstone each deleted slot to the sentinel in place in
       both orderings, then merge the insert candidates in and compact each
       with one sort (tombstones and overflow slots sort past every live
       key). The caller guarantees live-after <= capacity (it grows the key
       arrays BEFORE the step when a batch could overflow, so this compiles
       once per capacity class, not once per batch).
    3. *gather* — anchor-vertex adjacency rows for the delta pass, at the
       session's ``width`` class: rows/degrees of every update edge's
       endpoints against BOTH the pre-update state (for Δ⁻) and the
       post-update state (for Δ⁺), via :func:`_anchor_rows`.
    4. *degrees* — the full (n,) degree vector of the new state from two
       n-query searchsorted boundary scans (for the max-degree stat that
       drives the rare monotone width-class growth).

    Everything is statically shaped by ``(cap, ub, n, width)``; the engine
    caches one jitted wrapper per such class (``"dynamic_step"`` in the
    process-wide executable cache), so steady-state updates are a single
    cached device dispatch.

    Returns:
      (new_keys, new_rkeys, eff_ins, eff_del, ins_skeys, del_skeys,
      old_lo_rows, old_hi_rows, old_lo_deg, old_hi_deg,
      new_lo_rows, new_hi_rows, new_lo_deg, new_hi_deg, stats) —
      ``ins_skeys``/``del_skeys`` are the sorted effective-update forward
      key arrays (sentinel padded); the ``*_rows``/``*_deg`` blocks are the
      (ub, width)/(ub,) anchor adjacency of each update edge's lo/hi
      endpoint; ``stats`` is ``[live_edges, max_degree, num_inserted,
      num_deleted]`` int32, the step's single host-sync payload.
    """
    cap = int(keys.shape[0])
    kdt = keys.dtype
    sent = jnp.asarray(
        WIDE_EDGE_KEY_SENTINEL if kdt == jnp.int64 else EDGE_KEY_SENTINEL,
        kdt)
    n1 = jnp.asarray(n + 1, kdt)
    # -- resolve: which requests take effect against the current set
    idx = jnp.clip(jnp.searchsorted(keys, upd_keys), 0, cap - 1)
    present = (keys[idx] == upd_keys) & upd_valid
    eff_del = present & ~upd_ins
    eff_ins = upd_valid & upd_ins & ~present
    del_skeys = jnp.sort(jnp.where(eff_del, upd_keys, sent))
    ins_skeys = jnp.sort(jnp.where(eff_ins, upd_keys, sent))
    # -- apply: tombstone deletes in place, merge-sort-compact inserts
    # (both orderings; the reverse positions get their own searchsorted)
    tomb = keys.at[jnp.where(eff_del, idx, cap)].set(sent, mode="drop")
    new_keys = jnp.sort(jnp.concatenate(
        [tomb, jnp.where(eff_ins, upd_keys, sent)]))[:cap]
    ridx = jnp.clip(jnp.searchsorted(rkeys, upd_rkeys), 0, cap - 1)
    rtomb = rkeys.at[jnp.where(eff_del, ridx, cap)].set(sent, mode="drop")
    new_rkeys = jnp.sort(jnp.concatenate(
        [rtomb, jnp.where(eff_ins, upd_rkeys, sent)]))[:cap]
    # -- gather: anchor adjacency rows for the delta executables
    ub = int(upd_keys.shape[0])
    lo = jnp.where(upd_valid, upd_keys // n1, 0).astype(jnp.int32)
    hi = jnp.where(upd_valid, upd_keys % n1, 0).astype(jnp.int32)
    old_lo_rows, old_lo_deg = _anchor_rows(keys, rkeys, lo, upd_valid,
                                           n=n, width=width)
    old_hi_rows, old_hi_deg = _anchor_rows(keys, rkeys, hi, upd_valid,
                                           n=n, width=width)
    new_lo_rows, new_lo_deg = _anchor_rows(new_keys, new_rkeys, lo,
                                           upd_valid, n=n, width=width)
    new_hi_rows, new_hi_deg = _anchor_rows(new_keys, new_rkeys, hi,
                                           upd_valid, n=n, width=width)
    del ub
    # -- degrees of the new state: two n-query boundary scans
    live = (new_keys != sent).sum().astype(jnp.int32)
    bnds = jnp.arange(n, dtype=kdt) * n1
    sf = jnp.searchsorted(new_keys, bnds)
    sr = jnp.searchsorted(new_rkeys, bnds)
    deg = (jnp.diff(jnp.append(sf, live)) + jnp.diff(jnp.append(sr, live)))
    stats = jnp.stack([
        live,
        jnp.max(deg, initial=0).astype(jnp.int32),
        eff_ins.sum().astype(jnp.int32),
        eff_del.sum().astype(jnp.int32),
    ])
    return (new_keys, new_rkeys, eff_ins, eff_del, ins_skeys, del_skeys,
            old_lo_rows, old_hi_rows, old_lo_deg, old_hi_deg,
            new_lo_rows, new_hi_rows, new_lo_deg, new_hi_deg, stats)


# ---------------------------------------------------------------------------
# Containers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DeviceCSR:
    """Device-resident CSR arrays (undirected-symmetric or oriented).

    ``col_idx`` is padded to a policy-rounded static length with the
    sentinel ``n``; ``m`` is the true directed edge count.
    """

    n: int
    m: int
    row_ptr: jnp.ndarray  # (n+1,) int32
    col_idx: jnp.ndarray  # (m_pad,) int32, padded with n

    @property
    def m_pad(self) -> int:
        return int(self.col_idx.shape[0])

    @property
    def degrees(self) -> jnp.ndarray:
        return jnp.diff(self.row_ptr)

    @classmethod
    def from_graph(cls, g: Graph,
                   policy: ShapePolicy = DEFAULT_SHAPE_POLICY) -> "DeviceCSR":
        """Upload a host ``Graph``, padding ``col_idx`` to the policy extent."""
        m_pad = policy.round_edges(g.m_directed)
        col = jnp.asarray(g.col_idx, dtype=jnp.int32)
        pad = m_pad - g.m_directed
        if pad:
            col = jnp.concatenate([col, jnp.full(pad, g.n, jnp.int32)])
        return cls(n=g.n, m=g.m_directed,
                   row_ptr=jnp.asarray(g.row_ptr, dtype=jnp.int32),
                   col_idx=col)

    @classmethod
    def from_edges(cls, src, dst, n: int, *, valid=None,
                   policy: ShapePolicy = DEFAULT_SHAPE_POLICY,
                   key_mode: str = "auto") -> "DeviceCSR":
        """Jitted sort-based CSR build from deduplicated directed edges.

        Args:
          src, dst: equal-length int arrays (device or host) of directed
            edges; need not be sorted.
          n: vertex count (static).
          valid: optional bool mask of live slots (padding slots excluded).
          policy: extent-rounding policy for the uploaded arrays.
          key_mode: "auto" promotes the int32 sort keys to wide (int64)
            keys past ``fits_int32_pair_keys``; "int32"/"wide" force a mode.

        Returns:
          A ``DeviceCSR`` whose rows are sorted by destination id.

        Raises:
          GraphTooLargeError: the resolved key mode cannot represent the
            graph (see :func:`resolve_edge_key_mode`).
        """
        mode = resolve_edge_key_mode(n, key_mode, lane="csr-build")
        with edge_key_context(mode):
            src = jnp.asarray(src, dtype=jnp.int32)
            dst = jnp.asarray(dst, dtype=jnp.int32)
            if valid is None:
                valid = jnp.ones(src.shape[0], dtype=bool)
            m_pad = policy.round_edges(int(src.shape[0]))
            pad = m_pad - int(src.shape[0])
            if pad:
                src = jnp.concatenate([src, jnp.zeros(pad, jnp.int32)])
                dst = jnp.concatenate([dst, jnp.zeros(pad, jnp.int32)])
                valid = jnp.concatenate([valid, jnp.zeros(pad, dtype=bool)])
            row_ptr, col, m = _csr_from_edges(
                src, dst, valid, n=n, m_pad=m_pad, wide=(mode == "wide"))
        return cls(n=int(n), m=int(m), row_ptr=row_ptr, col_idx=col)


class _ForwardEdges:
    """The degree-rank-oriented edge set of a ``DeviceGraph`` (cached)."""

    def __init__(self, src, dst, kvalid, row_ptr, degrees, m: int):
        self.src = src          # (mf_pad,) int32, kept edges first
        self.dst = dst          # (mf_pad,) int32
        self.kvalid = kvalid    # (mf_pad,) bool
        self.row_ptr = row_ptr  # (n+1,) int32
        self.degrees = degrees  # (n,) int32 forward out-degrees
        self.m = m              # true kept edge count (= m_directed // 2)


class DeviceGraph:
    """A graph resident on device, with cached prep structure.

    Wraps a ``DeviceCSR`` and a ``ShapePolicy``; the forward orientation and
    padded neighbor matrices are computed lazily by jitted stages and cached
    on the instance, so the intersection and subgraph prep lanes (see
    ``repro.core.prep``) never rebuild them.
    """

    def __init__(self, csr: DeviceCSR, policy: ShapePolicy = DEFAULT_SHAPE_POLICY,
                 name: str = "graph"):
        self.csr = csr
        self.policy = policy
        self.name = name
        self._fwd: Optional[_ForwardEdges] = None
        self._nbrs: Dict[Tuple[int, bool], jnp.ndarray] = {}

    # -- basic accessors ---------------------------------------------------

    @property
    def n(self) -> int:
        return self.csr.n

    @property
    def m(self) -> int:
        """True directed edge count."""
        return self.csr.m

    @property
    def m_undirected(self) -> int:
        return self.csr.m // 2

    def edge_sources(self) -> jnp.ndarray:
        """(m_pad,) CSR row of every directed edge slot."""
        return _edge_sources(self.csr.row_ptr, n=self.n, m_pad=self.csr.m_pad)

    def edge_valid(self) -> jnp.ndarray:
        """(m_pad,) mask of live (non-padding) edge slots."""
        return jnp.arange(self.csr.m_pad) < self.m

    @classmethod
    def from_graph(cls, g: Graph,
                   policy: ShapePolicy = DEFAULT_SHAPE_POLICY) -> "DeviceGraph":
        return cls(DeviceCSR.from_graph(g, policy), policy=policy, name=g.name)

    # -- derived structure (jitted, cached) --------------------------------

    def forward(self) -> _ForwardEdges:
        """Degree-rank forward orientation (rank = (degree, id)), cached."""
        if self._fwd is None:
            mf_pad = max(1, self.csr.m_pad // 2)
            fsrc, fdst, kvalid, frow_ptr, fdeg = _orient_forward_dev(
                self.csr.row_ptr, self.csr.col_idx, self.m,
                n=self.n, m_pad=self.csr.m_pad, mf_pad=mf_pad,
            )
            self._fwd = _ForwardEdges(fsrc, fdst, kvalid, frow_ptr, fdeg,
                                      m=self.m // 2)
        return self._fwd

    def padded_neighbors(self, width: int, *, oriented: bool) -> jnp.ndarray:
        """(n, width) neighbor matrix (in-row sentinel ``n``), cached.

        ``oriented=True`` gathers the forward (N⁺) lists; ``False`` the full
        undirected adjacency rows.
        """
        key = (int(width), bool(oriented))
        if key not in self._nbrs:
            if oriented:
                fwd = self.forward()
                self._nbrs[key] = _padded_neighbors_dev(
                    fwd.src, fwd.dst, fwd.kvalid, fwd.row_ptr,
                    n=self.n, width=int(width),
                )
            else:
                self._nbrs[key] = _padded_neighbors_dev(
                    self.edge_sources(), self.csr.col_idx, self.edge_valid(),
                    self.csr.row_ptr, n=self.n, width=int(width),
                )
        return self._nbrs[key]

    def __repr__(self) -> str:
        return (f"DeviceGraph(name={self.name!r}, n={self.n}, "
                f"m_undirected={self.m_undirected}, policy={self.policy})")


# ---------------------------------------------------------------------------
# ShardedDeviceCSR — the 2D (degree-class × shard) edge partition
# ---------------------------------------------------------------------------

def shard_valid_counts(total: int, num_shards: int) -> np.ndarray:
    """Real-row count per shard under the round-robin deal.

    Row ``j`` lands on shard ``j % num_shards``, so shard ``s`` owns
    ``ceil((total - s) / num_shards)`` real rows — counts differ by at most
    one across shards, which is the static balance guarantee the
    distributed lanes assert on.
    """
    s = np.arange(int(num_shards), dtype=np.int64)
    return np.maximum(0, (int(total) - s + num_shards - 1) // num_shards) \
        .astype(np.int32)


def deal_across_shards(arr, num_shards: int, rows: int, *, fill):
    """Round-robin deal of axis 0 into a ``(num_shards, rows, ...)`` stack.

    Shard ``s``, position ``p`` receives input row ``p * num_shards + s``;
    out-of-range positions are filled with ``fill`` (the caller's padding
    sentinel). Because upstream schedules are heavy-first ordered (the
    matrix lane's tile schedule) or same-cost-per-row within a bucket (the
    degree-class buckets), the deal hands every shard an equal mix of heavy
    and light work — the multi-device analogue of the paper's
    TwoSmall/TwoLarge workload grouping. One vectorized device gather; no
    per-shard host loop.
    """
    arr = jnp.asarray(arr)
    idx = (jnp.arange(int(rows), dtype=jnp.int32)[None, :] * int(num_shards)
           + jnp.arange(int(num_shards), dtype=jnp.int32)[:, None])
    out = jnp.take(arr, idx.reshape(-1), axis=0, mode="fill",
                   fill_value=fill)
    return out.reshape((int(num_shards), int(rows)) + tuple(arr.shape[1:]))


def _deal_chunk(rows: int) -> int:
    """The length-gating granularity for one sharded bucket: the largest
    power of two ≤ 64 dividing ``rows`` (pow2-policy extents give 64; odd
    exact-policy extents degrade gracefully to 1). Padded rows past the
    last active chunk are never dispatched, and the tail chunk is masked,
    so padding contributes zero counted work."""
    rows = int(rows)
    if rows <= 0:
        return 1
    return math.gcd(rows, 64)


@dataclasses.dataclass
class ShardedBucket:
    """One degree-class bucket dealt round-robin across mesh shards.

    ``u_lists`` / ``v_lists`` are ``(num_shards, rows_per_shard, width)``
    int32 stacks, sharded over every mesh axis on their leading dim; shard
    ``s``'s first ``shard_rows[s]`` rows are real, the rest whole-row
    padding (u = -1 / v = -2). ``valid`` is the same per-shard real-row
    count as a sharded ``(num_shards,)`` device array — the executables
    length-gate their chunk loops on it, so padded rows cost nothing.
    """

    width: int
    edges: int            # total real rows across all shards
    rows_per_shard: int   # policy-rounded static per-shard row extent
    chunk: int            # length-gating granularity (divides rows_per_shard)
    u_lists: jnp.ndarray  # (num_shards, rows_per_shard, width)
    v_lists: jnp.ndarray
    valid: jnp.ndarray    # (num_shards,) int32, sharded like the stacks
    shard_rows: Tuple[int, ...]  # host copy of ``valid``

    @property
    def num_shards(self) -> int:
        return int(self.u_lists.shape[0])

    @property
    def shape(self) -> tuple:
        """Per-shard static work-unit shape ``(rows_per_shard, width)`` —
        the distributed executable-cache key component (the mesh itself is
        keyed separately)."""
        return (self.rows_per_shard, self.width)

    def dispatched_rows(self) -> Tuple[int, ...]:
        """Rows each shard actually dispatches: real rows rounded up to the
        chunk granularity (the length-gated loop's trip count × chunk)."""
        c = self.chunk
        return tuple(int(-(-r // c) * c) if r else 0 for r in self.shard_rows)


@dataclasses.dataclass
class ShardedDeviceCSR:
    """A graph's degree-class buckets partitioned across a device mesh.

    The 2D edge partition behind the ``*_distributed`` lanes: axis 1 is the
    paper's degree-class grouping (each bucket one static (rows, width)
    shape), axis 2 the round-robin deal across the mesh's shards
    (``deal_across_shards``), so every shard holds an equal dense/sparse
    mix and the per-shard work imbalance is at most one row per bucket.
    Built once per plan; the arrays are placed with a ``NamedSharding``
    over every mesh axis at construction, so counting is pure sharded
    replay with one scalar ``psum`` per bucket.
    """

    mesh: object             # jax.sharding.Mesh
    variant: str
    buckets: list            # List[ShardedBucket]
    policy: ShapePolicy
    n: int
    edges: int               # total real forward edges across buckets

    @property
    def num_shards(self) -> int:
        return int(np.prod(self.mesh.devices.shape))

    def shard_work(self) -> Tuple[int, ...]:
        """Total dispatched rows per shard, summed over buckets — the
        balance figure ``meta["shard_work"]`` exposes (max/min ≤ 2× is the
        documented contract when every shard has work)."""
        ndev = self.num_shards
        work = np.zeros(ndev, dtype=np.int64)
        for b in self.buckets:
            work += np.asarray(b.dispatched_rows(), dtype=np.int64)
        return tuple(int(w) for w in work)

    @classmethod
    def from_buckets(cls, buckets, mesh, *, variant: str,
                     policy: Optional[ShapePolicy] = None,
                     n: int = 0) -> "ShardedDeviceCSR":
        """Deal already-prepped ``DeviceBucket``s across ``mesh``'s shards.

        Each bucket's rows go round-robin to the mesh's flattened shard
        list; per-shard extents are policy-rounded (so steady-state repeat
        plans land in identical shape classes) and the stacks are placed
        with a ``NamedSharding`` over every mesh axis.
        """
        from jax.sharding import NamedSharding, PartitionSpec

        policy = policy if policy is not None else DEFAULT_SHAPE_POLICY
        ndev = int(np.prod(mesh.devices.shape))
        axes = tuple(mesh.axis_names)
        row_sharding = NamedSharding(mesh, PartitionSpec(axes))
        out = []
        total = 0
        for b in buckets:
            edges = int(b.edges)
            total += edges
            rows = policy.round_edges(-(-edges // ndev))
            chunk = _deal_chunk(rows)
            u = deal_across_shards(b.u_lists, ndev, rows, fill=-1)
            v = deal_across_shards(b.v_lists, ndev, rows, fill=-2)
            valid_h = shard_valid_counts(edges, ndev)
            u = jax.device_put(u, row_sharding)
            v = jax.device_put(v, row_sharding)
            valid = jax.device_put(jnp.asarray(valid_h), row_sharding)
            out.append(ShardedBucket(
                width=int(b.width), edges=edges, rows_per_shard=int(rows),
                chunk=int(chunk), u_lists=u, v_lists=v, valid=valid,
                shard_rows=tuple(int(x) for x in valid_h),
            ))
        return cls(mesh=mesh, variant=variant, buckets=out, policy=policy,
                   n=int(n), edges=total)

    @classmethod
    def from_graph(cls, g, mesh, *, variant: str = "filtered",
                   widths=(8, 32, 128, 512),
                   policy: Optional[ShapePolicy] = None,
                   prep_backend: str = "device") -> "ShardedDeviceCSR":
        """Prep ``g``'s degree-class buckets (device pipeline by default,
        numpy parity path under ``prep_backend="host"``) and deal them
        across ``mesh``'s shards."""
        from repro.core import prep  # deferred: prep imports this module

        policy = policy if policy is not None else DEFAULT_SHAPE_POLICY
        if prep_backend == "device":
            buckets = prep.prepare_intersection_buckets_device(
                g, variant=variant, widths=widths, policy=policy)
        else:
            buckets = [
                prep.DeviceBucket(
                    width=b["width"], edges=int(b["u_lists"].shape[0]),
                    u_lists=jnp.asarray(b["u_lists"]),
                    v_lists=jnp.asarray(b["v_lists"]),
                    src=jnp.asarray(b["src"]), dst=jnp.asarray(b["dst"]),
                )
                for b in prep.prepare_intersection_buckets_host(
                    g, variant=variant, widths=widths)
            ]
        return cls.from_buckets(buckets, mesh, variant=variant,
                                policy=policy, n=int(g.n))

    def __repr__(self) -> str:
        return (f"ShardedDeviceCSR(num_shards={self.num_shards}, "
                f"variant={self.variant!r}, edges={self.edges}, "
                f"buckets={[(b.shape, b.chunk) for b in self.buckets]})")
