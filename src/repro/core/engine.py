"""Plan/execute engine for exact triangle counting.

The paper's pipeline for every method splits into a *host stage* (filtering,
orientation, degree-class grouping, tile scheduling — §3's FORM_FILTERED_
EDGE_LIST / permute-split / INITIALIZE_CANDIDATE_SET steps) and a *device
stage* (the intersection / masked-SpGEMM / join kernels that §4 measures).
The one-shot ``triangle_count_*`` entry points redo the host stage on every
call, so repeated counts and benchmark sweeps are dominated by numpy prep
instead of the kernels the paper compares.

This module makes the split explicit:

    plan = plan_triangle_count(g, algorithm="intersection", backend="jnp")
    plan.count()   # first call traces + compiles (or hits the shared cache)
    plan.count()   # device-only replay: no numpy, no retrace, no recompile

``plan_triangle_count`` runs the host stage ONCE — orientation + bucketing +
padded neighbor gathers for the intersection path; degree permutation + BSR
tile schedule for the matrix path; 2-core peel + induced-subgraph reform +
bucket setup for the subgraph-matching path — uploads the resulting
statically-shaped arrays to the default device, and binds each work unit to a
jit-compiled executable from a process-wide cache keyed by
``(algorithm, strategy, backend, interpret, bitmap_bits, shape)``. Two
consequences:

* ``plan.count()`` is a pure device replay: one traced computation per bucket
  shape (the kernel AND its reduction live inside the same jit), summed as
  Python ints on the way out.
* Plans over same-shaped graphs (e.g. the fig6 R-MAT sweep, or batches of
  generated graphs) hit the executable cache and skip XLA compilation — the
  TRUST-style decoupling of preprocessing/partitioning from counting.

On the intersection lane (and the subgraph lane's join, which reuses it) the
plan stage also selects a *set-intersection strategy* per degree bucket —
``broadcast`` / ``probe`` / ``bitmap``, see ``repro.kernels.intersect.ops`` —
via the documented ``choose_strategy`` cost model (``strategy="auto"``, the
default: bitmap when the bucket's id range fits the packed width, probe for
wide buckets, broadcast for narrow ones). The choice can be overridden per
plan (``strategy="probe"`` etc.), is baked into each stage's executable-cache
key, and is surfaced as ``meta["bucket_strategies"]`` by
``count_with_stats()``.

Since PR 4 the prep stage itself is *device-resident* by default
(``prep_backend="device"``): orientation, bucketing, padded gathers, the
2-core peel, and the induced-subgraph reform run as the jitted stages in
``repro.core.prep`` / ``repro.graphs.device``, with a ``ShapePolicy``
rounding every data-dependent extent to a power of two so same-policy graphs
share traced prep stages and counting executables. ``prep_backend="host"``
keeps the numpy parity path. On top of the static shapes, ``GraphBatch``
stacks same-policy graphs and counts the whole batch in ONE vmapped device
dispatch (the ``TriangleCounter.count_many`` fast path).

Since PR 5 the engine also owns the *edge lane* (``algorithm="edge"``,
``plan_edge_support`` → ``TrussPlan``): cached per-edge support executables
mirroring the "vertex" analysis executables, plus the device k-truss peel
loop (support recompute → filter → re-orient through the same device prep
machinery) — the last host-enumeration hot path (``listing.py``'s
``edge_support``/``k_truss``) made device-resident.

The historical prep helpers (``prepare_intersection_buckets``,
``build_tile_schedule``, ``choose_block``, ``peel_to_two_core``) are thin
wrappers over ``repro.core.prep``, re-exported by the per-algorithm modules
for backward compatibility.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from jax import shard_map

from repro.graphs.formats import (
    Graph,
    apply_permutation,
    bucket_edges_by_degree,
    csr_to_padded_neighbors,
    degree_order_permutation,
    edges_to_csr,
    induced_subgraph,
    orient_forward,
    to_block_sparse,
)
from repro.graphs.device import (
    DEFAULT_SHAPE_POLICY,
    EDGE_KEY_SENTINEL,
    DeviceCSR,
    DeviceGraph,
    GraphTooLargeError,
    ShapePolicy,
    ShardedDeviceCSR,
    bfs_levels,
    deal_across_shards,
    dynamic_update_step,
    edge_key_context,
    edge_key_dtype,
    edge_key_sentinel,
    fits_int32_pair_keys,
    next_pow2,
    resolve_edge_key_mode,
    shard_valid_counts,
)
from repro.core import prep, tracing
# _two_core_peel: back-compat re-export (it lived here before PR 4)
from repro.core.prep import DeviceBucket, _two_core_peel  # noqa: F401
from repro.core.options import DEFAULT_WIDTHS, resolve_interpret
from repro.core.registry import register_algorithm
from repro.kernels.intersect.ops import (
    STRATEGIES,
    choose_mask_strategy,
    choose_strategy,
    intersect_counts,
    intersect_matches,
    intersect_matches_both,
    resolve_mask_strategy,
    resolve_strategy,
)
from repro.kernels.hash_tc.ops import (
    build_hash_table,
    hash_num_buckets,
    hash_probe_counts,
    hash_table_depth,
)
from repro.kernels.masked_spgemm.ops import masked_spgemm_counts

__all__ = [
    "DynamicPlan",
    "GraphBatch",
    "TrianglePlan",
    "TrussPlan",
    "plan_triangle_count",
    "plan_bfs_count",
    "plan_edge_support",
    "plan_dynamic_count",
    "plan_hash_count",
    "prepare_intersection_buckets",
    "build_tile_schedule",
    "choose_block",
    "peel_to_two_core",
    "choose_strategy",
    "resolve_strategy",
    "executable_cache_info",
    "clear_executable_cache",
    "mesh_cache_component",
    "DEFAULT_WIDTHS",
    "DISTRIBUTED_ALGORITHMS",
    "STRATEGIES",
]

ALGORITHMS = ("intersection", "matrix", "subgraph", "hash", "bfs")

# Mesh-planned lanes: same plan/execute machinery, per-shard executables in
# the same process-wide cache (key gains the mesh component), one scalar
# psum per stage. ``plan_triangle_count(..., mesh=...)`` accepts these.
DISTRIBUTED_ALGORITHMS = ("intersection_distributed", "matrix_distributed")


def mesh_cache_component(mesh) -> tuple:
    """The hashable mesh identity folded into distributed cache keys:
    ``(axis names, mesh shape, flat device ids)``. Two meshes with equal
    components produce identical sharded programs, so their executables may
    be shared; any shard-shape change (e.g. (8,) → (4, 2)) misses exactly
    once."""
    return (tuple(str(a) for a in mesh.axis_names),
            tuple(int(s) for s in mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


# ---------------------------------------------------------------------------
# Prep stage — thin wrappers over repro.core.prep (kept for the historical
# import surface; the plan stage below calls prep directly)
# ---------------------------------------------------------------------------

def prepare_intersection_buckets(
    g: Graph,
    variant: str = "filtered",
    widths: Sequence[int] = DEFAULT_WIDTHS,
) -> list:
    """Numpy intersection prep (parity reference) — see
    ``repro.core.prep.prepare_intersection_buckets_host``. The plan stage
    uses the device-resident prep by default (``prep_backend="device"``)."""
    return prep.prepare_intersection_buckets_host(g, variant=variant,
                                                  widths=widths)


def choose_block(g: Graph) -> int:
    """Adaptive matrix-lane tile size — see ``repro.core.prep.choose_block``."""
    return prep.choose_block(g)


def build_tile_schedule(
    g: Graph, block: int = 128, permute: bool = True
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, dict]:
    """Matrix-lane tile schedule — see ``repro.core.prep.build_tile_schedule``."""
    return prep.build_tile_schedule(g, block=block, permute=permute)


def peel_to_two_core(g: Graph, labels: Optional[np.ndarray] = None,
                     query_label: Optional[int] = None) -> np.ndarray:
    """Host-API 2-core peel — see ``repro.core.prep.peel_to_two_core``."""
    return prep.peel_to_two_core(g, labels=labels, query_label=query_label)


# ---------------------------------------------------------------------------
# Executable cache — jit-compiled device programs, shared across plans
# ---------------------------------------------------------------------------

class _BoundedLRU:
    """Thread-safe, size-bounded LRU of jitted executables.

    ``get_or_build`` is the single get-or-compile gate the serving layer
    relies on: a hit moves the key to the MRU end; a miss claims the key
    under the lock, releases it, builds, then inserts and evicts from the
    LRU end. Racing requests for the same key block on the claimant's event
    and pick up the one built callable (counted as hits) — no duplicate
    compiles. Eviction only drops the *cache reference*: live plans hold
    direct references to their executables, so an evicted program keeps
    working and is simply rebuilt on its next cold fetch (jit tracing is
    lazy, so a rebuild is cheap until the shape is actually re-run).
    """

    def __init__(self, maxsize: int):
        if maxsize < 1:
            raise ValueError(f"cache maxsize must be >= 1, got {maxsize}")
        self._data: "OrderedDict[tuple, Callable]" = OrderedDict()
        self._lock = threading.RLock()
        self._pending: Dict[tuple, threading.Event] = {}
        self.maxsize = int(maxsize)
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get_or_build(self, key: tuple, builder: Callable[[], Callable]):
        while True:
            with self._lock:
                fn = self._data.get(key)
                if fn is not None:
                    self._data.move_to_end(key)
                    self.hits += 1
                    return fn
                ev = self._pending.get(key)
                if ev is None:
                    self._pending[key] = threading.Event()
                    self.misses += 1
                    break
            ev.wait()  # someone else is building this key; re-check
        try:
            fn = builder()
        except BaseException:
            with self._lock:
                self._pending.pop(key).set()
            raise
        with self._lock:
            self._data[key] = fn
            self._data.move_to_end(key)
            self._evict_locked()
            self._pending.pop(key).set()
        return fn

    def _evict_locked(self) -> None:
        while len(self._data) > self.maxsize:
            self._data.popitem(last=False)
            self.evictions += 1

    def set_maxsize(self, maxsize: int) -> int:
        if maxsize < 1:
            raise ValueError(f"cache maxsize must be >= 1, got {maxsize}")
        with self._lock:
            old = self.maxsize
            self.maxsize = int(maxsize)
            self._evict_locked()
            return old

    def clear(self) -> None:
        with self._lock:
            self._data.clear()
            self.hits = 0
            self.misses = 0
            self.evictions = 0

    def info(self, include_keys: bool = False) -> dict:
        with self._lock:
            d = dict(size=len(self._data), hits=self.hits,
                     misses=self.misses, maxsize=self.maxsize,
                     evictions=self.evictions)
            if include_keys:
                d["keys"] = tuple(self._data.keys())
            return d

    # dict-compatible read views (tests poke entries by key)
    def __getitem__(self, key: tuple) -> Callable:
        with self._lock:
            return self._data[key]

    def __contains__(self, key: tuple) -> bool:
        with self._lock:
            return key in self._data

    def __len__(self) -> int:
        with self._lock:
            return len(self._data)


def _env_cache_size() -> int:
    raw = os.environ.get("TC_EXEC_CACHE_SIZE", "512")
    try:
        size = int(raw)
    except ValueError as e:
        raise ValueError(f"TC_EXEC_CACHE_SIZE={raw!r} is not an int") from e
    if size < 1:
        raise ValueError(f"TC_EXEC_CACHE_SIZE must be >= 1, got {size}")
    return size


_EXECUTABLE_CACHE = _BoundedLRU(_env_cache_size())


def _build_intersect_executable(strategy: str, backend: str, interpret: bool,
                                bitmap_bits, name: str = "run") -> Callable:
    def run(u_lists, v_lists):
        counts = intersect_counts(
            u_lists, v_lists, strategy=strategy, backend=backend,
            interpret=interpret, bitmap_bits=bitmap_bits,
        )
        return jnp.sum(counts)

    return tracing.jit(name, run)


def _build_matrix_executable(backend: str, interpret: bool,
                             name: str = "run") -> Callable:
    def run(l_tiles, u_tiles, a_tiles):
        partials = masked_spgemm_counts(
            l_tiles, u_tiles, a_tiles, backend=backend, interpret=interpret
        )
        return jnp.sum(partials)

    return tracing.jit(name, run)


def _build_hash_executable(backend: str, interpret: bool,
                           name: str = "run") -> Callable:
    """Per-bucket total for the TRUST-style hashing lane.

    The stage args are ``(v_lists, src, table)``: the bucket's candidate
    rows (N⁺(dst), the standard v-side sentinel layout), their anchor
    vertices, and the plan-wide (n, B, D) per-vertex hash table. The core
    (``repro.kernels.hash_tc``) probes each candidate against its anchor's
    hash row, so per-edge work is O(W·D) instead of the sorted-merge costs.
    The cache ``shape_key`` is ``(e_pad, width, num_buckets, depth)`` — the
    table shape class rides in the key because the traced gather shapes
    depend on it.
    """

    def run(w_lists, src, table):
        counts = hash_probe_counts(
            w_lists, src, table, backend=backend, interpret=interpret
        )
        return jnp.sum(counts)

    return tracing.jit(name, run)


# one block of the credit loop scatters about this many mask slots
_CREDIT_BLOCK_SLOTS = 1 << 21


def _cumsum_1d(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive int32 cumulative sum of the (E,) ``x``, taken along rows of
    1,024 and then over the rows' totals. A one-level cumsum of 2^20
    elements took ~36 s to compile for a v5e, this ~1 s."""
    e, lanes = x.shape[0], 1024
    c = jnp.cumsum(jnp.pad(x, (0, -e % lanes)).reshape(-1, lanes), axis=1,
                   dtype=jnp.int32)
    before = jnp.cumsum(c[:, -1]) - c[:, -1]
    return (c + before[:, None]).reshape(-1)[:e]


def _group_by_source(matched: jnp.ndarray, u_lists: jnp.ndarray,
                     src: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sum the (E, W) match mask over each run of equal ``src`` among the
    real rows (padding rows, u = -1, start no run).

    Returns ``(grouped, firsts, runs)``: ``grouped`` (E, W) int32 holds the
    ``runs`` run sums first and zero rows after, ``firsts`` (E,) each run's
    first row. The sums are one sorted ``segment_sum`` of whole mask rows
    over the rows' run ids; a padding row joins the run before it, which
    its zero mask row leaves as it was."""
    e = matched.shape[0]
    rows = jnp.arange(e, dtype=jnp.int32)
    real = u_lists[:, 0] >= 0
    first = real & ((rows == 0) | (src != jnp.roll(src, 1))
                    | ~jnp.roll(real, 1))
    run = _cumsum_1d(first) - 1
    grouped = jax.ops.segment_sum(matched.astype(jnp.int32), run,
                                  num_segments=e, indices_are_sorted=True)
    firsts = jnp.zeros(e, jnp.int32).at[jnp.where(first, run, e)].set(
        rows, mode="drop")
    return grouped, firsts, first.sum(dtype=jnp.int32)


def _build_vertex_executable(n: int, name: str = "run") -> Callable:
    """Per-vertex triangle counts for one filtered-intersection bucket,
    added to ``acc``.

    ``intersect_matches`` (the mask form of the set-intersection core) marks
    which u-list entries appear in both forward neighbor lists; each match
    (e, j) is one triangle (src[e], dst[e], u_lists[e, j]). The high vertex
    takes each edge's matches by a ``segment_sum`` over ``dst``. Every row
    of a source has the same u row, N⁺(src), so the mask is first summed
    over each run of equal ``src`` (``_group_by_source``): a bucket's rows
    keep CSR order, so a W=512 chunk of 2^18 rows of the Graph500 scale-20
    graph holds at most ~40k runs. A device loop over blocks of B runs,
    B·W ≈ ``_CREDIT_BLOCK_SLOTS``, credits the third vertices at each run's
    u row (the sentinel ``n`` is dropped) and the low vertex its run total.
    Row order sets only the number of runs; the counts are exact for any
    order. ``acc`` is the (n,) int32 sum of the pass so far (zeros where the
    host accumulates).
    """

    def run(u_lists, v_lists, src, dst, acc):
        matched = intersect_matches(u_lists, v_lists)  # (E, W) bool
        t = acc + jax.ops.segment_sum(matched.sum(axis=1, dtype=jnp.int32),
                                      dst, num_segments=n)
        grouped, firsts, runs = _group_by_source(matched, u_lists, src)
        e, w = u_lists.shape
        block = min(e, max(1, _CREDIT_BLOCK_SLOTS // w))
        if e % block:
            grouped = jnp.pad(grouped, ((0, -e % block), (0, 0)))
            firsts = jnp.pad(firsts, (0, -e % block))

        def credit(i, t):
            g = jax.lax.dynamic_slice_in_dim(grouped, i * block, block)
            r = jax.lax.dynamic_slice_in_dim(firsts, i * block, block)
            t = t.at[u_lists[r].reshape(-1)].add(g.reshape(-1), mode="drop")
            return t.at[src[r]].add(g.sum(axis=1))

        return jax.lax.fori_loop(0, (runs + block - 1) // block, credit, t)

    return tracing.jit(name, run)


def _build_edge_executable(strategy: str, bitmap_bits: Optional[int],
                           shape_key: tuple) -> Callable:
    """Per-edge support contributions for one filtered-intersection bucket.

    The edge analogue of the vertex executable: every match (e, j) is one
    triangle (src, dst, w = u_lists[e, j]) whose three undirected edges are
    (src, dst), (src, w) and (dst, w). Support is accumulated in *forward
    CSR slot* order — each undirected edge owns exactly one oriented slot —
    which turns the heavy side-edge scatters into dense per-row adds:

    * (src, dst): slot = row_ptr[src] + (dst's position in the u row); one
      E-sized scatter of the per-edge intersection sizes.
    * (src, w):   w sits at u-row position j, so its slot is
      row_ptr[src] + j. Group the u-side match mask by src
      (one row-wise segment_sum to (n, W)) and add whole rows at
      row_ptr[src] + arange(W) — no per-element binary search.
    * (dst, w):   symmetric via the v-side match mask (``matched_v`` from
      ``intersect_matches_both``) grouped by dst.

    The caller converts slot order to sorted-key (= ``edge_list_unique``)
    order with the permutation from ``prep.forward_edge_keys_*`` — once per
    round, not per bucket.

    ``strategy``/``bitmap_bits`` are the resolved match-mask core — the
    mask-specific ``resolve_mask_strategy`` cost model (bitmap out to ~4·W
    packed bits, since the probe mask pays two searchsorted passes), so
    dense-id buckets get the TRUST bitmap core (pack + gather-test, the big
    win on clique-like graphs), wide ones probe, narrow ones broadcast.
    ``shape_key`` is ``(e_pad, width, mk, n1, *peel_knobs)`` — mk the padded
    slot-array length, n1 = n + 1. The trailing peel knobs
    (``max_peel_iters``, ``peel_early_exit``) do not change the traced
    computation; they are folded into the key so ``CountOptions`` equality
    exactly tracks edge-executable sharing (see ``get_executable``).

    Padding is inert everywhere: padded bucket rows (u = -1 / v = -2) and
    in-row sentinels (n / n+1) never match, so their scatter values are
    zero; positions past a row's true degree carry zeros, and out-of-range
    slots are dropped (``mode="drop"``).
    """
    _, width, mk, n1 = (int(x) for x in shape_key[:4])
    n = n1 - 1

    body = _edge_support_body(strategy, bitmap_bits, width, mk, n)
    return jax.jit(body)


def _edge_support_body(strategy: str, bitmap_bits: Optional[int],
                       width: int, mk: int, n: int) -> Callable:
    """The traced slot-ordered support computation shared by the single-host
    edge executable (jitted directly) and the distributed one (wrapped in
    shard_map over a dealt row partition — the scatters target the full
    (mk,) slot space whichever rows a shard holds, so partial supports sum
    under psum)."""

    def run(u_lists, v_lists, src, dst, row_ptr):
        matched_u, matched_v = intersect_matches_both(
            u_lists, v_lists, strategy=strategy, bitmap_bits=bitmap_bits)
        per_edge = matched_u.sum(axis=1, dtype=jnp.int32)
        # (src, dst): dst's position in the sorted u row
        base_j = jax.vmap(
            lambda u, d: jnp.clip(jnp.searchsorted(u, d), 0, width - 1)
        )(u_lists, dst)
        supp = jnp.zeros(mk, jnp.int32).at[row_ptr[src] + base_j].add(
            per_edge, mode="drop")
        # (src, w) / (dst, w): row-grouped masks, added as whole rows
        by_src = jax.ops.segment_sum(matched_u.astype(jnp.int32), src,
                                     num_segments=max(n, 1))
        by_dst = jax.ops.segment_sum(matched_v.astype(jnp.int32), dst,
                                     num_segments=max(n, 1))
        rowpos = (row_ptr[:n, None]
                  + jnp.arange(width, dtype=jnp.int32)[None, :]).reshape(-1)
        return supp.at[rowpos].add((by_src + by_dst).reshape(-1),
                                   mode="drop")

    return run


def _build_dynamic_step_executable(shape_key: tuple) -> Callable:
    """One jitted device step applying a padded edge-update batch in place.

    ``shape_key`` is ``(cap, ub, n1, width)`` — the packed-key capacity
    class, padded update rows, n + 1, and the anchor-row width class —
    with a trailing ``"wide"`` marker appended in the wide (int64) key
    mode, so the two key dtypes never share a cache slot.
    The numeric extents are :class:`~repro.graphs.device.ShapePolicy` pow2
    classes, so a session re-compiles only when an extent overflows its
    class (and then exactly once: the classes grow monotonically and never
    shrink). The body is :func:`repro.graphs.device.dynamic_update_step` —
    resolve the batch against the sorted key orderings, tombstone deletes,
    merge inserts, and gather the batch's anchor adjacency rows (pre- and
    post-update) for the delta executables; the key dtype follows the
    ``keys`` argument (the caller wraps wide calls in
    ``edge_key_context``).
    """
    if shape_key and shape_key[-1] == "wide":
        shape_key = shape_key[:-1]
    cap, ub, n1, width = (int(x) for x in shape_key)
    del cap, ub  # fixed by the argument shapes; keyed for cache-stats

    @jax.jit
    def run(keys, rkeys, upd_keys, upd_rkeys, upd_ins, upd_valid):
        return dynamic_update_step(keys, rkeys, upd_keys, upd_rkeys,
                                   upd_ins, upd_valid,
                                   n=n1 - 1, width=width)

    return run


def _resolve_delta_classes(bounds: Sequence[int], n: int, strategy: str,
                           bitmap_bits: Optional[int]) -> list:
    """Resolve the per-width match-mask strategy for a delta executable.

    Same cost model as the edge lane (``resolve_mask_strategy`` over
    id_range = n + 2, covering both in-row sentinels), with the same forced
    ``bitmap_bits`` override semantics.
    """
    id_range = n + 2
    resolved = []
    for w in bounds:
        strat, bits = resolve_mask_strategy(int(w), id_range, strategy)
        if bitmap_bits is not None and strat == "bitmap":
            if bitmap_bits < id_range:
                raise ValueError(
                    f"bitmap_bits={bitmap_bits} cannot cover vertex id "
                    f"range {id_range} (n + 2 sentinel rows)")
            bits = int(bitmap_bits)
        resolved.append((strat, bits))
    return resolved


def _build_delta_executable(strategy: str, bitmap_bits: Optional[int],
                            shape_key: tuple) -> Callable:
    """Weighted triangle deltas for one padded batch of anchor edges.

    ``shape_key`` is ``(ub, n1, *bounds)``: padded update rows, n + 1, and
    the session's width classes — deliberately capacity-independent (the
    inputs are the step's (ub, width) anchor-row blocks, not the key
    arrays), so a capacity-class overflow recompiles only the step. The
    wide (int64) key mode appends a trailing ``"wide"`` marker; the packed
    key dtype itself follows the ``skeys`` argument. The
    executable re-buckets only the anchor
    edges (``prep.delta_update_buckets``), runs the strategy-dispatched
    match mask per class, and for every matched triangle (lo, hi, w) weighs
    the contribution by how many of its three edges sit in the anchor set
    ``skeys`` (a sorted packed-key array padded with ``EDGE_KEY_SENTINEL``):
    a triangle containing k anchor edges is discovered once per anchor
    edge, so weighting each hit 6/k — via the integer table [0, 6, 3, 2] —
    makes the grand total exactly 6 x (#triangles touching the anchor set).
    The caller asserts divisibility by 6 (a cheap drift tripwire) and
    divides. Membership probes use clip-searchsorted-equality; sentinel
    neighbors (w = n from in-row padding) can never equal a real key
    (real keys have hi <= n - 1 mod n1) and padded rows (u = -1) go
    negative, so padding contributes zero even before the match mask
    gates it.
    """
    if shape_key and shape_key[-1] == "wide":
        shape_key = shape_key[:-1]
    ub, n1 = int(shape_key[0]), int(shape_key[1])
    bounds = tuple(int(w) for w in shape_key[2:])
    n = n1 - 1
    resolved = _resolve_delta_classes(bounds, n, strategy, bitmap_bits)

    @jax.jit
    def run(lo_rows, hi_rows, lo_deg, hi_deg, lo, hi, valid, skeys):
        weight = jnp.array([0, 6, 3, 2], jnp.int32)
        kdt = skeys.dtype  # int32 fast path / int64 wide key mode
        nn1 = jnp.asarray(n1, kdt)
        total = jnp.int32(0)
        classes = prep.delta_update_buckets(lo_rows, hi_rows, lo_deg,
                                            hi_deg, lo, hi, valid,
                                            n=n, bounds=bounds)
        for (_, u, v, sb, db), (strat, bits) in zip(classes, resolved):
            matched = intersect_matches(u, v, strategy=strat,
                                        bitmap_bits=bits)
            s = sb[:, None].astype(kdt)
            d = db[:, None].astype(kdt)
            uk = u.astype(kdt)
            e1 = jnp.minimum(s, uk) * nn1 + jnp.maximum(s, uk)
            e2 = jnp.minimum(d, uk) * nn1 + jnp.maximum(d, uk)
            i1 = jnp.clip(jnp.searchsorted(skeys, e1), 0, ub - 1)
            i2 = jnp.clip(jnp.searchsorted(skeys, e2), 0, ub - 1)
            k = (1 + (skeys[i1] == e1).astype(jnp.int32)
                 + (skeys[i2] == e2).astype(jnp.int32))
            total = total + jnp.sum(jnp.where(matched, weight[k], 0),
                                    dtype=jnp.int32)
        return total

    return run


def _build_dist_intersect_executable(strategy: str,
                                     bitmap_bits: Optional[int],
                                     shape_key: tuple, mesh,
                                     name: str = "run") -> Callable:
    """One degree bucket's sharded intersection count: every shard runs the
    resolved jnp core over its dealt rows, length-gated so padding costs
    nothing, and ONE scalar psum yields the global partial.

    ``shape_key`` is ``(rows_per_shard, width, chunk)``. The chunk loop has
    a *dynamic* trip count ``ceil(valid / chunk)`` — chunks past a shard's
    last real row are never dispatched — and the tail chunk masks rows at
    index ≥ valid out of the sum, so dealt padding contributes zero to the
    count even if its slots hold garbage (the poison regression test relies
    on exactly this, not on sentinel rows happening to be inert).
    """
    rows, width, chunk = (int(x) for x in shape_key[:3])
    axes = tuple(mesh.axis_names)
    spec = PartitionSpec(axes)

    def run(u, v, valid):
        def local(u, v, valid):
            u, v, valid = u[0], v[0], valid[0]

            def body(i, acc):
                start = i * chunk
                uu = jax.lax.dynamic_slice_in_dim(u, start, chunk)
                vv = jax.lax.dynamic_slice_in_dim(v, start, chunk)
                counts = intersect_counts(
                    uu, vv, strategy=strategy, backend="jnp",
                    bitmap_bits=bitmap_bits)
                rowid = start + jnp.arange(chunk, dtype=jnp.int32)
                return acc + jnp.sum(
                    jnp.where(rowid < valid, counts, 0), dtype=jnp.int32)

            active = (valid + chunk - 1) // chunk
            acc = jax.lax.fori_loop(0, active, body, jnp.int32(0))
            return jax.lax.psum(acc, axes)

        return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=PartitionSpec(),
                         check_vma=False)(u, v, valid)

    return tracing.jit(name, run)


def _build_dist_matrix_executable(shape_key: tuple, mesh,
                                  name: str = "run") -> Callable:
    """The sharded masked block-SpGEMM count: each shard reduces its dealt
    tile triples locally, one scalar psum yields the global sum.

    ``shape_key`` is ``(tiles_per_shard, block, block)``. The tile loop's
    trip count is the shard's *real* tile count, so dealt zero-padding
    tiles dispatch no FLOPs at all (tile granularity = exact gating; the
    NaN-poison regression test asserts padded slots are never touched).
    """
    axes = tuple(mesh.axis_names)
    spec = PartitionSpec(axes)

    def run(l, u, a, valid):
        def local(l, u, a, valid):
            l, u, a, valid = l[0], u[0], a[0], valid[0]

            def body(i, acc):
                lt = jax.lax.dynamic_index_in_dim(l, i, keepdims=False)
                ut = jax.lax.dynamic_index_in_dim(u, i, keepdims=False)
                at = jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
                prod = jnp.dot(lt, ut,
                               preferred_element_type=jnp.float32)
                return acc + (prod * at).sum(dtype=jnp.float32)

            acc = jax.lax.fori_loop(0, valid, body, jnp.float32(0.0))
            return jax.lax.psum(acc, axes)

        return shard_map(local, mesh=mesh, in_specs=(spec,) * 4,
                         out_specs=PartitionSpec(),
                         check_vma=False)(l, u, a, valid)

    return tracing.jit(name, run)


def _build_dist_edge_executable(strategy: str, bitmap_bits: Optional[int],
                                shape_key: tuple, mesh) -> Callable:
    """One bucket's sharded per-edge support: each shard scatters its dealt
    rows' contributions into the full (mk,) slot space and one vector psum
    (communication = the support itself, the lane's output) combines them.
    ``shape_key`` is ``(rows_per_shard, width, mk, n1, *peel_knobs)``;
    ``row_ptr`` is replicated (in_spec ``P()``)."""
    _, width, mk, n1 = (int(x) for x in shape_key[:4])
    body = _edge_support_body(strategy, bitmap_bits, width, mk, n1 - 1)
    axes = tuple(mesh.axis_names)
    spec = PartitionSpec(axes)

    @jax.jit
    def run(u_lists, v_lists, src, dst, row_ptr):
        def local(u, v, s, d, rp):
            supp = body(u[0], v[0], s[0], d[0], rp)
            return jax.lax.psum(supp, axes)

        return shard_map(
            local, mesh=mesh,
            in_specs=(spec, spec, spec, spec, PartitionSpec()),
            out_specs=PartitionSpec(), check_vma=False,
        )(u_lists, v_lists, src, dst, row_ptr)

    return run


# a count lane's (or the per-vertex) executable is named by the lane, its
# strategy and the bucket width ("w") or tile block ("b") in its shape key's
# second place
_COUNT_LANES = {"intersection": ("intersect", "w"),
                "subgraph": ("intersect", "w"),
                "hash": ("hash", "w"),
                "matrix": ("matrix", "b"),
                "intersection_distributed": ("intersect_dist", "w"),
                "matrix_distributed": ("matrix_dist", "b"),
                "vertex": ("vertex", "w")}


def _executable_name(algorithm: str, strategy: Optional[str],
                     shape_key: tuple) -> Optional[str]:
    """The name a device profile shows for a count lane's or a per-vertex
    executable, e.g. ``tc_intersect_broadcast_w512``, ``tc_vertex_w512``;
    None for the other lanes."""
    if algorithm not in _COUNT_LANES:
        return None
    lane, axis = _COUNT_LANES[algorithm]
    strat = f"_{strategy}" if strategy else ""
    return f"tc_{lane}{strat}_{axis}{int(shape_key[1])}"


def get_executable(algorithm: str, backend: str, interpret: bool,
                   shape_key: tuple, strategy: Optional[str] = None,
                   bitmap_bits: Optional[int] = None, mesh=None) -> Callable:
    """Fetch (or build) the jitted executable for one statically-shaped work
    unit.

    Args:
      algorithm: "intersection" | "subgraph" | "bfs" (all three use the
        intersection executables — the BFS lane's wedge closure is the same
        per-bucket computation over level-oriented rows, so it shares the
        compiled kernels) | "matrix" | "hash" (the TRUST-style per-vertex
        hash-probe stage, shape_key ``(e_pad, width, num_buckets, depth)``)
        | "vertex" (per-vertex triangle counts for
        one filtered bucket — the analysis path ``TriangleCounter`` routes
        through the plan) | "edge" (per-edge support contributions for one
        filtered bucket — the ``TrussPlan`` lane) | "dynamic_step" /
        "delta" (the ``DynamicPlan`` lane: the in-place edge-update step
        and the anchored triangle-delta pass) | "intersection_distributed"
        / "matrix_distributed" / "edge_distributed" (the mesh-planned
        sharded stages: shard_map over a round-robin dealt partition,
        length-gated per shard, one psum; require ``mesh``).
      backend: "jnp" | "pallas" | "ref" (see ``repro.kernels.*.ops``).
      interpret: pallas interpret mode flag (part of the key: interpret and
        compiled kernels are distinct executables).
      shape_key: the work unit's static array shape, e.g. one degree bucket's
        (E, W), a tile schedule's (T, B, B), a vertex stage's (E, W, n), or
        an edge stage's (E, W, mk, n1, max_peel_iters, peel_early_exit) —
        the edge lane folds the plan's peel knobs into its key so equal
        ``CountOptions`` (peel knobs included) share one cached edge
        executable and unequal knobs miss.
      strategy: resolved set-intersection strategy ("broadcast" | "probe" |
        "bitmap") for the intersection lanes, or the resolved match-mask
        strategy (same three names, via ``resolve_mask_strategy``) for the
        edge lane; None for matrix/vertex.
      bitmap_bits: static packed-bitmap capacity when strategy="bitmap",
        else None.
      mesh: jax device mesh — required for (and only consumed by) the
        ``*_distributed`` algorithms. ``mesh_cache_component(mesh)`` is
        appended to the cache key, so equal-mesh plans share per-shard
        executables (zero recompiles steady-state) and a shard-shape change
        misses exactly once.

    Returns:
      A jitted callable reducing the work unit (a scalar count, or an (n,)
      per-vertex vector for "vertex"). Cached process-wide under
      ``(algorithm, strategy, backend, interpret, bitmap_bits, shape)``
      (+ the mesh component when sharded) so plans over same-shaped
      buckets/schedules share the compiled kernel.
    """
    # validate BEFORE touching the cache so bad args never claim a key or
    # skew the hit/miss counters
    if backend not in ("jnp", "pallas", "ref"):
        raise ValueError(f"unknown backend {backend!r}; "
                         f"expected 'jnp', 'pallas', or 'ref'")
    if algorithm in ("intersection", "subgraph", "edge",
                     "intersection_distributed", "edge_distributed") \
            and strategy not in STRATEGIES:
        raise ValueError(f"unresolved strategy {strategy!r}; "
                         f"expected one of {STRATEGIES}")
    if algorithm.endswith("_distributed") and mesh is None:
        raise ValueError(
            f"algorithm {algorithm!r} needs a mesh; pass mesh=")
    name = _executable_name(algorithm, strategy, shape_key)
    builders: Dict[str, Callable[[], Callable]] = {
        "intersection": lambda: _build_intersect_executable(
            strategy, backend, interpret, bitmap_bits, name),
        "subgraph": lambda: _build_intersect_executable(
            strategy, backend, interpret, bitmap_bits, name),
        "matrix": lambda: _build_matrix_executable(backend, interpret, name),
        "hash": lambda: _build_hash_executable(backend, interpret, name),
        "vertex": lambda: _build_vertex_executable(int(shape_key[-1]), name),
        "edge": lambda: _build_edge_executable(
            strategy, bitmap_bits, tuple(shape_key)),
        "dynamic_step": lambda: _build_dynamic_step_executable(
            tuple(shape_key)),
        "delta": lambda: _build_delta_executable(
            strategy, bitmap_bits, tuple(shape_key)),
        "intersection_distributed": lambda: _build_dist_intersect_executable(
            strategy, bitmap_bits, tuple(shape_key), mesh, name),
        "matrix_distributed": lambda: _build_dist_matrix_executable(
            tuple(shape_key), mesh, name),
        "edge_distributed": lambda: _build_dist_edge_executable(
            strategy, bitmap_bits, tuple(shape_key), mesh),
    }
    builder = builders.get(algorithm)
    if builder is None:
        raise ValueError(f"unknown algorithm {algorithm!r}")
    key = (algorithm, strategy, backend, bool(interpret), bitmap_bits,
           tuple(shape_key))
    if mesh is not None:
        key = key + (mesh_cache_component(mesh),)
    return _EXECUTABLE_CACHE.get_or_build(key, builder)


def _build_batch_executable(specs: tuple, backend: str,
                            interpret: bool) -> Callable:
    """One jitted program counting a whole stacked batch of graphs.

    ``specs`` is one ``(strategy, bitmap_bits, (e_pad, width))`` triple per
    bucket; the executable takes the flattened (u, v) pairs — each a
    (B, e_pad, width) stack — and returns the (B,) per-graph totals. Every
    bucket's vmapped intersection and the cross-bucket reduction live in a
    single traced computation: ONE device dispatch per batch.
    """

    def run(*arrays):
        total = jnp.zeros(arrays[0].shape[0], jnp.int32)
        for i, (strat, bits, _) in enumerate(specs):
            u, v = arrays[2 * i], arrays[2 * i + 1]

            def one(uu, vv, strat=strat, bits=bits):
                return jnp.sum(intersect_counts(
                    uu, vv, strategy=strat, backend=backend,
                    interpret=interpret, bitmap_bits=bits,
                ))

            total = total + jax.vmap(one)(u, v)
        return total

    return tracing.jit("tc_intersect_batch", run)


def get_batch_executable(specs: tuple, backend: str, interpret: bool,
                         batch: int) -> Callable:
    """Fetch (or build) the vmapped batch executable for one stacked layout.

    Cached in the same process-wide executable cache under
    ``("intersection_batch", None, backend, interpret, None,
    (batch,) + specs)`` — the shape-policy-keyed batch-plan cache: two
    batches whose policy-rounded layouts collide share one compiled program.
    """
    key = ("intersection_batch", None, backend, bool(interpret), None,
           (int(batch),) + tuple(specs))
    return _EXECUTABLE_CACHE.get_or_build(
        key,
        lambda: _build_batch_executable(tuple(specs), backend,
                                        bool(interpret)),
    )


def executable_cache_info() -> dict:
    """``{'size', 'hits', 'misses', 'maxsize', 'evictions'}`` for tests and
    benchmarks. Since PR 8 the cache is a thread-safe bounded LRU (default
    512 entries, override via ``TC_EXEC_CACHE_SIZE`` or
    ``set_cache_limit``), so the snapshot also reports the bound and how
    many cold entries it has dropped."""
    return _EXECUTABLE_CACHE.info()


def clear_executable_cache() -> None:
    _EXECUTABLE_CACHE.clear()


def cache_info() -> dict:
    """``executable_cache_info()`` plus the live ``keys`` tuple (MRU last).

    The introspection handle the serving metrics registry snapshots and
    tests use instead of poking the private cache dict: each key is the
    ``(algorithm, strategy, backend, interpret, bitmap_bits, shape)``
    tuple documented on ``get_executable``.
    """
    return _EXECUTABLE_CACHE.info(include_keys=True)


def clear_caches() -> None:
    """Drop every cached executable and zero the hit/miss/eviction counters
    (the public alias of ``clear_executable_cache``)."""
    clear_executable_cache()


def set_cache_limit(maxsize: int) -> int:
    """Re-bound the process-wide executable cache; returns the old bound.

    Shrinking evicts LRU entries immediately (counted in ``evictions``).
    Live plans keep direct references to their executables, so eviction
    never breaks an existing plan — it only forces a rebuild on the next
    cold ``get_executable`` for that key.
    """
    return _EXECUTABLE_CACHE.set_maxsize(maxsize)


# ---------------------------------------------------------------------------
# TrianglePlan — the device-resident, replayable count
# ---------------------------------------------------------------------------

def _dispatch(fn: Callable, *args):
    """Enqueue one device call (``tc.dispatch``)."""
    with tracing.span("tc.dispatch"):
        out = fn(*args)
    tracing.bump("tc.dispatches")
    return out


def _sync(result, acc: Callable):
    """Block on one device result and read it to the host (``tc.sync``)."""
    with tracing.span("tc.sync"):
        out = acc(result)
    tracing.bump("tc.host_syncs")
    return out


@dataclasses.dataclass
class _Stage:
    executable: Callable
    args: Tuple[jnp.ndarray, ...]  # device-resident
    shape_key: tuple
    strategy: Optional[str] = None  # resolved intersection strategy
    bitmap_bits: Optional[int] = None  # packed capacity when strategy="bitmap"
    # (src, dst) edge endpoints, device-resident — filtered intersection
    # stages only; lets the per-vertex analysis path replay the same buffers
    vertex_args: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None

    def run(self):
        """One device dispatch over the resident buffers."""
        return self.executable(*self.args)

    def count(self, index: int, acc: Callable):
        """This stage's partial count: ``run()``, then one host sync;
        ``acc`` (int or float) reads the result."""
        with tracing.span("tc.stage", index=index, width=self.shape_key[1],
                          strategy=self.strategy or "",
                          rows=self.shape_key[0], chunks=1):
            return _sync(_dispatch(self.run), acc)

    def vertex_calls(self, n: int) -> List[Callable]:
        """The per-vertex pass over the resident buffers: one call, taking
        the pass's (n,) running counts and returning them with this stage's
        added."""
        e, w = self.shape_key
        fn = get_executable("vertex", "jnp", False, (e, w, n))
        return [lambda acc: fn(*self.args, *self.vertex_args, acc)]


@dataclasses.dataclass
class _TiledStage:
    """A bucket too large for the ``max_device_bytes`` budget, streamed
    through ONE cached chunk-shaped executable instead of held resident.

    The bucket's rows come either from padded host arrays (``host_args``,
    uploaded ``chunk_rows`` rows at a time, tail chunks padded with the
    repo-wide inert row fills) or from a device-resident
    ``prep.StreamedBucket`` (``source``), which gathers each chunk on device
    so the whole bucket is never materialized anywhere. Partial counts
    accumulate on host. Chunk rows are a pow2 class ≤ the bucket extent, so
    every chunk of every same-width bucket under the same budget shares a
    single executable — zero steady-state recompiles, cache-stats-asserted
    in ``tests/test_tiled.py`` — and the count is bit-identical to the
    monolithic path (integer partials; the matrix lane's float partials are
    exact integers far below 2^24).
    """

    # the chunk executable; with a ``source``, fused with its gather
    # (``prep.gathered_count``)
    executable: Callable
    host_args: Tuple[np.ndarray, ...]  # full padded bucket, host-resident
    fills: Tuple[Any, ...]  # tail-chunk fill per host array (inert rows)
    chunk_rows: int
    shape_key: tuple  # FULL bucket shape (meta parity with _Stage)
    chunk_shape_key: tuple  # the executable's shape class
    strategy: Optional[str] = None
    bitmap_bits: Optional[int] = None
    # host (src, dst) for the chunked per-vertex path (filtered stages only)
    vertex_args: Optional[Tuple[np.ndarray, np.ndarray]] = None
    args: Tuple = ()  # no resident device buffers (block_until_ready no-op)
    source: Optional[prep.StreamedBucket] = None  # device-gathered rows

    @property
    def rows(self) -> int:
        if self.source is not None:
            return self.source.e_pad
        return int(self.host_args[0].shape[0])

    @property
    def num_chunks(self) -> int:
        return -(-self.rows // self.chunk_rows)

    def _upload(self, arrays, fills, s: int) -> tuple:
        """Rows ``[s, s + chunk_rows)`` of ``arrays``, tail-padded to the
        single chunk shape class, uploaded."""
        out = []
        for a, f in zip(arrays, fills):
            c = a[s:s + self.chunk_rows]
            if c.shape[0] < self.chunk_rows:
                pad = np.full((self.chunk_rows - c.shape[0],)
                              + c.shape[1:], f, a.dtype)
                c = np.concatenate([c, pad], axis=0)
            out.append(jnp.asarray(c))
        return tuple(out)

    def _run_chunk(self, s: int):
        """Gather (device rows) or upload (host rows) the chunk at row
        ``s`` and enqueue its count."""
        if self.source is not None:
            return self.source.count(self.executable, s, self.chunk_rows)
        return self.executable(*self._upload(self.host_args, self.fills, s))

    def count(self, index: int, acc: Callable):
        """Stream every chunk through the cached executable: one dispatch
        and one host sync per chunk, partials accumulated on host with
        ``acc`` (int or float)."""
        with tracing.span("tc.stage", index=index,
                          width=self.chunk_shape_key[1],
                          strategy=self.strategy or "", rows=self.rows,
                          chunks=self.num_chunks):
            total = acc(0)
            for s in range(0, self.rows, self.chunk_rows):
                total += _sync(_dispatch(self._run_chunk, s), acc)
            return total

    def vertex_calls(self, n: int) -> List[Callable]:
        """The per-vertex pass, one call per chunk through the cached
        ``tc_vertex_w<W>`` executable: fused with the chunk's gather
        (``tc_vertex_w<W>_gathered``) on device rows, after the chunk's
        upload on host rows. Each takes the pass's (n,) running counts and
        returns them with the chunk's added."""
        e, w = self.chunk_shape_key
        fn = get_executable("vertex", "jnp", False, (e, w, n))
        starts = range(0, self.rows, self.chunk_rows)
        if self.source is not None:
            gathered = _EXECUTABLE_CACHE.get_or_build(
                ("vertex_gathered", None, "jnp", False, None, (e, w, n)),
                lambda: prep.gathered_count(fn, endpoints=True))
            return [lambda acc, s=s: self.source.count(
                gathered, s, self.chunk_rows, acc) for s in starts]
        assert self.vertex_args is not None
        arrays = self.host_args + tuple(self.vertex_args)
        fills = self.fills + (0, 0)
        return [lambda acc, s=s: fn(*self._upload(arrays, fills, s), acc)
                for s in starts]


# per-vertex counts accumulate on device in int32 while every vertex's
# count fits: t(v) <= C(d(v), 2) < 2**31 for undirected degree d(v) <= 2**16
VERTEX_DEVICE_MAX_DEGREE = 1 << 16


def local_clustering_coefficients(t: np.ndarray,
                                  degrees: np.ndarray) -> np.ndarray:
    """LDBC Graphalytics' LCC in float64: 2·t(v) / (d(v)·(d(v)−1)), 0 where
    d(v) < 2, from exact per-vertex counts ``t`` and undirected degrees."""
    t = np.asarray(t).astype(np.float64)
    d = np.asarray(degrees).astype(np.float64)
    denom = d * (d - 1.0)
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where(denom > 0, 2.0 * t / denom, 0.0)


@dataclasses.dataclass
class TrianglePlan:
    """A prepared triangle count: device buffers + compiled executables.

    ``count()`` replays the device stage only — no host-side numpy runs after
    construction (tests verify this by poisoning the prep helpers). Build via
    ``plan_triangle_count``.
    """

    algorithm: str
    backend: str
    interpret: bool
    stages: List[_Stage]
    divisor: int  # 6 for the full-variant intersection (each triangle ×6)
    meta: Dict[str, Any]
    prep_seconds: float
    executions: int = 0
    # (n,) undirected degrees of the planned graph: the per-vertex pass's
    # accumulation bound and the clustering coefficients' denominators
    degrees: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False, compare=False)

    def count(self) -> int:
        """Exact triangle count; pure device replay of the cached stages
        (tiled stages stream their bucket chunk-by-chunk through the same
        cached executables, accumulating partials on host)."""
        acc = float if self.algorithm in ("matrix",
                                          "matrix_distributed") else int
        with tracing.span("tc.count", algorithm=self.algorithm,
                          stages=len(self.stages)):
            tracing.bump("tc.counts")
            total = acc(0)
            for i, st in enumerate(self.stages):
                total += st.count(i, acc)
        if acc is float:
            total = int(round(total))
        if self.divisor != 1:
            assert total % self.divisor == 0, total
            total //= self.divisor
        self.executions += 1
        return total

    def count_with_stats(self) -> Tuple[int, dict]:
        """Count once and return the plan's prep statistics alongside.

        Returns:
          (count, meta): meta carries statistics gathered at plan time —
          prune fractions, tile schedule sizes, bucket shapes, and on the
          intersection/subgraph lanes ``bucket_strategies``: one
          ``(width, strategy)`` pair per degree bucket as resolved by the
          ``strategy="auto"`` cost model (or the per-plan override).
        """
        c = self.count()
        stats = dict(self.meta)
        if self.algorithm == "subgraph":
            stats["num_embeddings"] = 6 * c
        return c, stats

    def triangles_per_vertex(self) -> np.ndarray:
        """Per-vertex triangle counts, replayed through this plan's cached
        device buffers (the analysis path ``repro.core.api.TriangleCounter``
        routes here instead of the host-side enumeration in ``listing.py``).

        Supported on plans whose stages carry edge endpoints — the filtered
        intersection lane, the BFS lane (level-oriented stages carry the
        same (src, dst) layout), and the subgraph lane (whose counts on the
        pruned graph scatter back through ``meta["vertex_map"]``; peeled
        vertices are in no triangle by construction).

        One pass (``tc.vertex``, bumps ``tc.vertex_passes``) dispatches one
        ``tc_vertex_w<W>`` call per resident stage or streamed chunk. While
        the graph's max degree is at most ``VERTEX_DEVICE_MAX_DEGREE`` every
        count fits int32, so the calls add into one device vector and the
        host reads it once; past it each call's partial is read and summed
        in int64 on the host.

        Returns:
          (n,) int64 numpy array, t[v] = number of triangles containing v.

        Raises:
          NotImplementedError: matrix lane or the full intersection variant
            (no per-edge endpoints to attribute matches to); callers fall
            back to a filtered-intersection sidecar plan.
        """
        if self.algorithm not in ("intersection", "subgraph", "bfs") \
                or self.divisor != 1 \
                or any(st.vertex_args is None
                       and getattr(st, "source", None) is None
                       for st in self.stages):
            raise NotImplementedError(
                f"per-vertex counts need filtered-intersection stages; "
                f"algorithm={self.algorithm!r} divisor={self.divisor} does "
                f"not carry them"
            )
        n_local = int(self.meta.get("vertex_n", self.meta["n"]))
        on_device = self.degrees is not None \
            and int(self.degrees.max(initial=0)) <= VERTEX_DEVICE_MAX_DEGREE
        zeros = jnp.zeros(n_local, jnp.int32)
        total = np.zeros(n_local, dtype=np.int64)
        acc = zeros

        def read(r):
            return np.asarray(r, dtype=np.int64)

        with tracing.span("tc.vertex", algorithm=self.algorithm,
                          stages=len(self.stages)):
            tracing.bump("tc.vertex_passes")
            for i, st in enumerate(self.stages):
                calls = st.vertex_calls(n_local)
                rows, width = st.shape_key[:2]
                with tracing.span("tc.stage", index=i, width=width,
                                  strategy=choose_mask_strategy(width),
                                  rows=rows, chunks=len(calls)):
                    for call in calls:
                        if on_device:
                            acc = _dispatch(call, acc)
                        else:
                            total += _sync(_dispatch(call, zeros), read)
            if on_device:
                total = _sync(acc, read)
        vertex_map = self.meta.get("vertex_map")
        if vertex_map is not None:  # subgraph lane: pruned ids -> original
            out = np.zeros(int(self.meta["n"]), dtype=np.int64)
            out[np.asarray(vertex_map)] = total
            return out
        return total

    def clustering_coefficients(self, t: Optional[np.ndarray] = None
                                ) -> np.ndarray:
        """(n,) float64 local clustering coefficients
        (``local_clustering_coefficients``) of the per-vertex counts ``t``,
        or of a fresh ``triangles_per_vertex()`` pass when None."""
        if t is None:
            t = self.triangles_per_vertex()
        return local_clustering_coefficients(t, self.degrees)

    def block_until_ready(self) -> "TrianglePlan":
        """Force all device buffers resident (useful before timing counts)."""
        for st in self.stages:
            for a in st.args:
                a.block_until_ready()
        return self

    @property
    def num_stages(self) -> int:
        return len(self.stages)

    @property
    def shape_keys(self) -> List[tuple]:
        return [st.shape_key for st in self.stages]


def _resolve_bucket_strategy(width: int, id_range: int, strategy: str,
                             bitmap_bits: Optional[int],
                             backend: str = "jnp"):
    """Resolve one bucket's (strategy, bitmap_bits) for the core ``backend``
    runs, honoring a forced ``bitmap_bits`` override (which must cover the
    id range)."""
    strat, bits = resolve_strategy(width, id_range, strategy=strategy,
                                   backend=backend)
    if bitmap_bits is not None and strat == "bitmap":
        if bitmap_bits < id_range:
            raise ValueError(
                f"bitmap_bits={bitmap_bits} cannot represent id range "
                f"{id_range} (n + 2 sentinel ids); ids past the capacity "
                f"would silently never match"
            )
        bits = int(bitmap_bits)
    return strat, bits


def _buckets_for_plan(g, variant: str, widths: Sequence[int],
                      prep_backend: str, policy: Optional[ShapePolicy],
                      max_bucket_bytes: Optional[int] = None,
                      ) -> List[DeviceBucket]:
    """Run the prep stage on the requested backend; either way the result is
    device-resident ``DeviceBucket``s (the host path uploads its arrays).
    The device path returns a ``prep.StreamedBucket`` for any bucket past
    ``max_bucket_bytes``."""
    if prep_backend == "device":
        return prep.prepare_intersection_buckets_device(
            g, variant=variant, widths=widths, policy=policy,
            max_bucket_bytes=max_bucket_bytes,
        )
    host = prep.prepare_intersection_buckets_host(g, variant=variant,
                                                  widths=widths)
    return [
        DeviceBucket(
            width=b["width"], edges=int(b["u_lists"].shape[0]),
            u_lists=jnp.asarray(b["u_lists"]), v_lists=jnp.asarray(b["v_lists"]),
            src=jnp.asarray(b["src"]), dst=jnp.asarray(b["dst"]),
        )
        for b in host
    ]


# a resident bucket may take this share of an accelerator's memory before
# the plan streams it: chunk executables and their intermediates need the
# rest
_BUCKET_MEMORY_SHARE = 8


def default_device_budget() -> Optional[int]:
    """The per-bucket bytes budget a plan uses when ``max_device_bytes`` is
    None: none on the CPU, where host memory is the limit, and
    1/``_BUCKET_MEMORY_SHARE`` of the default device's memory elsewhere."""
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    return int(limit) // _BUCKET_MEMORY_SHARE if limit else None


def _tile_chunk_rows(rows: int, row_bytes: int,
                     max_device_bytes: int) -> int:
    """Largest pow2 chunk row count whose device footprint fits the budget
    (floored at 1 — graceful degradation: a budget below one row's cost
    still streams row-by-row rather than failing)."""
    c = 1
    while c * 2 <= rows and (c * 2) * row_bytes <= max_device_bytes:
        c *= 2
    return c


def _plan_intersection(g, variant: str, backend: str, interpret: bool,
                       widths: Sequence[int], strategy: str = "auto",
                       bitmap_bits: Optional[int] = None,
                       prep_backend: str = "device",
                       shape_policy: Optional[ShapePolicy] = None,
                       max_device_bytes: Optional[int] = None,
                       ) -> Tuple[List[_Stage], int, dict]:
    buckets = _buckets_for_plan(g, variant, widths, prep_backend,
                                shape_policy, max_device_bytes)
    # id range covers real vertex ids [0, n) plus the in-row padding
    # sentinels n (u rows) and n+1 (v rows); whole-row padding (-1/-2) is
    # negative and never matches in any core
    id_range = g.n + 2
    stages = []
    tiled_buckets = []
    for b in buckets:
        shape_key = b.shape
        strat, bits = _resolve_bucket_strategy(b.width, id_range, strategy,
                                               bitmap_bits, backend)
        e_pad, width = int(shape_key[0]), int(shape_key[1])
        if max_device_bytes is not None \
                and prep.bucket_nbytes(e_pad, width) > max_device_bytes:
            # stream this bucket through one chunk-shaped executable:
            # device prep gathers pow2-row chunks on device at count() time;
            # host prep keeps the padded arrays and uploads their chunks
            chunk = _tile_chunk_rows(e_pad, prep.bucket_nbytes(1, width),
                                     max_device_bytes)
            chunk_key = (chunk, width)
            fn = get_executable("intersection", backend, interpret,
                                chunk_key, strategy=strat, bitmap_bits=bits)
            if isinstance(b, prep.StreamedBucket):
                # the chunk gather fused into the count, cached beside fn
                fn = _EXECUTABLE_CACHE.get_or_build(
                    ("intersection_gathered", strat, backend, bool(interpret),
                     bits, chunk_key),
                    lambda fn=fn: prep.gathered_count(fn))
                host_args, vertex_args, source = (), None, b
            else:
                host_args = (np.asarray(b.u_lists), np.asarray(b.v_lists))
                vertex_args, source = None, None
                if variant == "filtered":
                    vertex_args = (np.asarray(b.src), np.asarray(b.dst))
            stages.append(_TiledStage(
                executable=fn,
                host_args=host_args,
                fills=(-1, -2),  # whole-row padding: zero matches everywhere
                chunk_rows=chunk,
                shape_key=shape_key,
                chunk_shape_key=chunk_key,
                strategy=strat,
                bitmap_bits=bits,
                vertex_args=vertex_args,
                source=source,
            ))
            tiled_buckets.append(dict(shape=shape_key, chunk_rows=chunk,
                                      num_chunks=stages[-1].num_chunks))
            continue
        fn = get_executable("intersection", backend, interpret, shape_key,
                            strategy=strat, bitmap_bits=bits)
        vertex_args = None
        if variant == "filtered":
            vertex_args = (b.src, b.dst)
        stages.append(_Stage(
            executable=fn,
            args=(b.u_lists, b.v_lists),
            shape_key=shape_key,
            strategy=strat,
            bitmap_bits=bits,
            vertex_args=vertex_args,
        ))
    policy = shape_policy if shape_policy is not None else DEFAULT_SHAPE_POLICY
    meta = dict(
        variant=variant,
        widths=tuple(widths),
        strategy=strategy,
        prep_backend=prep_backend,
        shape_policy=policy.key() if prep_backend == "device" else None,
        bucket_shapes=[s.shape_key for s in stages],
        bucket_strategies=[(s.shape_key[1], s.strategy) for s in stages],
        bucket_edges=[b.edges for b in buckets],
        neighbor_table_widths=tuple(b.table_width for b in buckets),
        edges=int(sum(b.edges for b in buckets)),
        max_device_bytes=max_device_bytes,
        tiled_buckets=tiled_buckets,
        num_chunks=int(sum(t["num_chunks"] for t in tiled_buckets)),
    )
    return stages, (6 if variant == "full" else 1), meta


def _plan_matrix(g: Graph, block, permute: bool, backend: str,
                 interpret: bool,
                 max_device_bytes: Optional[int] = None,
                 ) -> Tuple[List[_Stage], int, dict]:
    if block == "auto":
        block = choose_block(g)
    l_sel, u_sel, a_sel, stats = build_tile_schedule(
        g, block=block, permute=permute
    )
    stages = []
    tiled_buckets = []
    if l_sel.shape[0]:
        shape_key = tuple(l_sel.shape)
        t, bsz = int(shape_key[0]), int(shape_key[1])
        # three (T, B, B) float32 stacks resident at once
        tile_bytes = 3 * bsz * bsz * 4
        if max_device_bytes is not None \
                and t * tile_bytes > max_device_bytes:
            chunk = _tile_chunk_rows(t, tile_bytes, max_device_bytes)
            chunk_key = (chunk,) + shape_key[1:]
            fn = get_executable("matrix", backend, interpret, chunk_key)
            st = _TiledStage(
                executable=fn,
                host_args=(np.asarray(l_sel), np.asarray(u_sel),
                           np.asarray(a_sel)),
                fills=(0.0, 0.0, 0.0),  # all-zero tiles contribute 0.0
                chunk_rows=chunk,
                shape_key=shape_key,
                chunk_shape_key=chunk_key,
            )
            stages.append(st)
            tiled_buckets.append(dict(shape=shape_key, chunk_rows=chunk,
                                      num_chunks=st.num_chunks))
        else:
            fn = get_executable("matrix", backend, interpret, shape_key)
            stages.append(_Stage(
                executable=fn,
                args=(jnp.asarray(l_sel), jnp.asarray(u_sel),
                      jnp.asarray(a_sel)),
                shape_key=shape_key,
            ))
    meta = dict(permute=permute, max_device_bytes=max_device_bytes,
                tiled_buckets=tiled_buckets,
                num_chunks=int(sum(t["num_chunks"] for t in tiled_buckets)),
                **stats)
    return stages, 1, meta


def _plan_intersection_distributed(
        g, mesh, variant: str, backend: str, interpret: bool,
        widths: Sequence[int], strategy: str = "auto",
        bitmap_bits: Optional[int] = None, prep_backend: str = "device",
        shape_policy: Optional[ShapePolicy] = None,
) -> Tuple[List[_Stage], int, dict]:
    """The intersection lane over a ``ShardedDeviceCSR``: device prep once,
    each degree bucket dealt round-robin across the mesh's shards, one
    cached length-gated executable + one scalar psum per bucket. The
    intersection cores always run their jnp formulation under shard_map
    (exactly as the pre-engine one-shot lane did); ``backend`` is recorded
    but does not change the sharded program."""
    policy = shape_policy if shape_policy is not None else DEFAULT_SHAPE_POLICY
    sharded = ShardedDeviceCSR.from_graph(
        g, mesh, variant=variant, widths=widths, policy=policy,
        prep_backend=prep_backend,
    )
    id_range = g.n + 2  # real ids + the in-row sentinels n / n+1
    stages = []
    for b in sharded.buckets:
        strat, bits = _resolve_bucket_strategy(b.width, id_range, strategy,
                                               bitmap_bits)
        shape_key = b.shape + (b.chunk,)
        fn = get_executable("intersection_distributed", "jnp", False,
                            shape_key, strategy=strat, bitmap_bits=bits,
                            mesh=mesh)
        stages.append(_Stage(
            executable=fn,
            args=(b.u_lists, b.v_lists, b.valid),
            shape_key=shape_key,
            strategy=strat,
            bitmap_bits=bits,
        ))
    meta = dict(
        variant=variant,
        widths=tuple(widths),
        strategy=strategy,
        prep_backend=prep_backend,
        shape_policy=policy.key(),
        core_backend="jnp",
        bucket_shapes=[s.shape_key for s in stages],
        bucket_strategies=[(s.shape_key[1], s.strategy) for s in stages],
        bucket_edges=[b.edges for b in sharded.buckets],
        edges=sharded.edges,
        mesh_axes=tuple(str(a) for a in mesh.axis_names),
        mesh_shape=tuple(int(s) for s in mesh.devices.shape),
        num_shards=sharded.num_shards,
        rows_per_shard=[b.rows_per_shard for b in sharded.buckets],
        shard_valid=[b.shard_rows for b in sharded.buckets],
        shard_work=sharded.shard_work(),
    )
    return stages, (6 if variant == "full" else 1), meta


def _plan_matrix_distributed(
        g: Graph, mesh, block, permute: bool, backend: str, interpret: bool,
) -> Tuple[List[_Stage], int, dict]:
    """The matrix lane over the mesh: the host-built heavy-first tile
    schedule is dealt round-robin across shards (equal dense/sparse mix per
    shard by construction), zero-padded to the per-shard extent, and the
    cached executable's tile loop runs exactly each shard's real tile count
    — dealt padding dispatches no FLOPs."""
    if block == "auto":
        block = choose_block(g)
    l_sel, u_sel, a_sel, stats = build_tile_schedule(
        g, block=block, permute=permute
    )
    ndev = int(np.prod(mesh.devices.shape))
    axes = tuple(mesh.axis_names)
    row_sharding = NamedSharding(mesh, PartitionSpec(axes))
    stages = []
    t = int(l_sel.shape[0])
    tiles_ps = -(-t // ndev) if t else 0
    valid_h = shard_valid_counts(t, ndev)
    if t:
        l_d, u_d, a_d = (
            jax.device_put(
                deal_across_shards(jnp.asarray(x), ndev, tiles_ps, fill=0),
                row_sharding)
            for x in (l_sel, u_sel, a_sel)
        )
        valid = jax.device_put(jnp.asarray(valid_h), row_sharding)
        shape_key = (tiles_ps,) + tuple(l_sel.shape[1:])
        fn = get_executable("matrix_distributed", "jnp", False, shape_key,
                            mesh=mesh)
        stages.append(_Stage(
            executable=fn,
            args=(l_d, u_d, a_d, valid),
            shape_key=shape_key,
        ))
    meta = dict(
        permute=permute,
        **stats,
        mesh_axes=axes,
        mesh_shape=tuple(int(s) for s in mesh.devices.shape),
        num_shards=ndev,
        tiles_per_shard=tiles_ps,
        shard_valid=[tuple(int(x) for x in valid_h)],
        shard_work=tuple(int(x) for x in valid_h),
    )
    return stages, 1, meta


def _plan_subgraph(g: Graph, backend: str, interpret: bool,
                   widths: Sequence[int], strategy: str = "auto",
                   bitmap_bits: Optional[int] = None,
                   prep_backend: str = "device",
                   shape_policy: Optional[ShapePolicy] = None,
                   max_device_bytes: Optional[int] = None,
                   ) -> Tuple[List[_Stage], int, dict]:
    if prep_backend == "device":
        # FILTER + RECONSTRUCT on device: the induced graph keeps original
        # vertex ids (dead vertices just lose their rows), so stage counts
        # scatter directly into original-id space — no vertex_map needed
        policy = shape_policy if shape_policy is not None \
            else DEFAULT_SHAPE_POLICY
        dg = DeviceGraph.from_graph(g, policy)
        alive = prep.peel_to_two_core_device(dg)
        sub_dg = prep.induced_device_graph(dg, alive)
        alive_count = int(jnp.sum(alive))
        stages, _, inner = _plan_intersection(
            sub_dg, variant="filtered", backend=backend, interpret=interpret,
            widths=widths, strategy=strategy, bitmap_bits=bitmap_bits,
            prep_backend="device", shape_policy=policy,
            max_device_bytes=max_device_bytes,
        )
        # the sub-plan's id range is the parent's (ids are preserved)
        meta = dict(
            vertices_pruned=int(g.n - alive_count),
            prune_fraction=float(1.0 - alive_count / max(g.n, 1)),
            edges_after=sub_dg.m_undirected,
            edges_before=g.m_undirected,
            vertex_n=g.n,
            **inner,
        )
        return stages, 1, meta

    alive = peel_to_two_core(g)
    sub, old_ids = induced_subgraph(g, alive)
    # join on the pruned graph; forward-filtered intersection counts each
    # triangle once (embeddings = 6 × that)
    stages, _, inner = _plan_intersection(
        sub, variant="filtered", backend=backend, interpret=interpret,
        widths=widths, strategy=strategy, bitmap_bits=bitmap_bits,
        prep_backend="host", max_device_bytes=max_device_bytes,
    )
    # subgraph stages share the intersection executables by construction
    meta = dict(
        vertices_pruned=int(g.n - alive.sum()),
        prune_fraction=float(1.0 - alive.sum() / max(g.n, 1)),
        edges_after=sub.m_undirected,
        edges_before=g.m_undirected,
        # per-vertex analysis: stage counts are on the pruned graph's ids;
        # scatter back through old_ids (peeled vertices hold no triangles)
        vertex_n=sub.n,
        vertex_map=np.asarray(old_ids),
        **inner,
    )
    return stages, 1, meta


def _plan_hash(g, backend: str, interpret: bool, widths: Sequence[int],
               prep_backend: str = "device",
               shape_policy: Optional[ShapePolicy] = None,
               ) -> Tuple[List[_Stage], int, dict]:
    """The TRUST-style vertex-centric hashing lane (arXiv:2103.08053).

    Prep reuses the filtered degree-class buckets (the candidate rows are
    exactly the intersection lane's ``v_lists`` = N⁺(dst)), plus one extra
    plan-wide structure: an (n, B, D) per-vertex hash table over the
    oriented neighbor rows (``repro.kernels.hash_tc``). The count stage
    probes each bucket's candidates against ``table[src]`` — each forward
    edge (u, v) contributes |N⁺(v) ∩ N⁺(u)|, so every triangle is counted
    exactly once at its degree-rank-minimum edge, same invariant as the
    filtered intersection lane. One extra scalar sync at plan time measures
    the maximum bucket chain length; B and D are pow2-rounded so the table
    shape is a deterministic function of the graph's shape class.
    """
    buckets = _buckets_for_plan(g, "filtered", widths, prep_backend,
                                shape_policy)
    policy = shape_policy if shape_policy is not None else DEFAULT_SHAPE_POLICY
    stages: List[_Stage] = []
    meta = dict(
        variant="filtered",
        widths=tuple(widths),
        prep_backend=prep_backend,
        shape_policy=policy.key() if prep_backend == "device" else None,
    )
    if buckets:
        table_width = max(b.width for b in buckets)
        num_buckets = hash_num_buckets(table_width)
        if prep_backend == "device":
            dg = DeviceGraph.from_graph(g, policy)
            nbrs = dg.padded_neighbors(table_width, oriented=True)
        else:
            fwd = orient_forward(g)
            nbrs = jnp.asarray(
                csr_to_padded_neighbors(fwd, pad_to=table_width))
        # one scalar sync: the real max chain length, rounded to a pow2 class
        depth = next_pow2(max(1, int(hash_table_depth(
            nbrs, jnp.int32(num_buckets)))))
        table = build_hash_table(nbrs, num_buckets=num_buckets, depth=depth)
        for b in buckets:
            shape_key = (b.e_pad, b.width, num_buckets, depth)
            fn = get_executable("hash", backend, interpret, shape_key)
            stages.append(_Stage(
                executable=fn,
                args=(b.v_lists, b.src, table),
                shape_key=shape_key,
            ))
        meta.update(
            hash_num_buckets=num_buckets,
            hash_depth=depth,
            table_width=table_width,
        )
    meta.update(
        bucket_shapes=[s.shape_key for s in stages],
        bucket_edges=[b.edges for b in buckets],
        edges=int(sum(b.edges for b in buckets)),
    )
    return stages, 1, meta


def _plan_bfs(g: Graph, backend: str, interpret: bool,
              widths: Sequence[int], strategy: str = "auto",
              bitmap_bits: Optional[int] = None,
              shape_policy: Optional[ShapePolicy] = None,
              ) -> Tuple[List[_Stage], int, dict]:
    """The BFS-based lane (Fast BFS-Based Triangle Counting, arXiv:1909.02127).

    A level-ordered traversal replaces the degree rank: BFS levels come from
    the jitted ``graphs.device.bfs_levels`` fixpoint over the ``DeviceCSR``
    (one (n,) sync at plan time), then every edge is oriented toward its
    larger ``(level, id)`` endpoint — a total order, so each triangle closes
    exactly once at its rank-minimum wedge. The count stage is forward-edge
    wedge closure |N_f(u) ∩ N_f(v)| over level-oriented degree-class
    buckets, which is byte-for-byte the intersection lane's computation —
    the stages bind the *same cached intersection executables* (shared
    process-wide), only the oriented rows differ. No packed pair keys ⇒ no
    n ≲ 46k bound.
    """
    policy = shape_policy if shape_policy is not None else DEFAULT_SHAPE_POLICY
    meta = dict(
        variant="bfs-forward",
        widths=tuple(widths),
        strategy=strategy,
        shape_policy=policy.key(),
    )
    if g.n == 0 or g.m_undirected == 0:
        meta.update(bucket_shapes=[], bucket_strategies=[], bucket_edges=[],
                    edges=0, levels_max=0, bfs_sources=int(g.n))
        return [], 1, meta

    dg = DeviceGraph.from_graph(g, policy)
    lvl = np.asarray(bfs_levels(dg))  # one (n,) sync at plan time
    src_all, dst_all = g.edge_endpoints()
    keep = (lvl[src_all] < lvl[dst_all]) | (
        (lvl[src_all] == lvl[dst_all]) & (src_all < dst_all))
    fsrc = src_all[keep].astype(np.int32)
    fdst = dst_all[keep].astype(np.int32)
    counts = np.bincount(fsrc, minlength=g.n)
    outdeg = counts.astype(np.int32)
    row_ptr = np.zeros(g.n + 1, dtype=np.int32)
    np.cumsum(counts, out=row_ptr[1:])
    # rows stay sorted by dst id because the parent CSR rows were
    fg = Graph(n=g.n, row_ptr=row_ptr, col_idx=fdst, name=g.name + "+bfs")

    id_range = g.n + 2
    stages: List[_Stage] = []
    bucket_edges: List[int] = []
    for b in bucket_edges_by_degree(fsrc, fdst, outdeg, widths):
        w = int(b["width"])
        bs, bd = b["src"], b["dst"]
        nbrs = csr_to_padded_neighbors(fg, pad_to=w)  # in-row sentinel n
        u_rows = nbrs[bs]
        v_rows = np.where(nbrs[bd] == g.n, g.n + 1, nbrs[bd])
        e = int(bs.shape[0])
        e_pad = policy.round_edges(e)
        pad = e_pad - e
        if pad:
            u_rows = np.vstack([u_rows, np.full((pad, w), -1, np.int32)])
            v_rows = np.vstack([v_rows, np.full((pad, w), -2, np.int32)])
            bs = np.concatenate([bs, np.zeros(pad, np.int32)])
            bd = np.concatenate([bd, np.zeros(pad, np.int32)])
        shape_key = (e_pad, w)
        strat, bits = _resolve_bucket_strategy(w, id_range, strategy,
                                               bitmap_bits, backend)
        fn = get_executable("intersection", backend, interpret, shape_key,
                            strategy=strat, bitmap_bits=bits)
        stages.append(_Stage(
            executable=fn,
            args=(jnp.asarray(u_rows, dtype=jnp.int32),
                  jnp.asarray(v_rows, dtype=jnp.int32)),
            shape_key=shape_key,
            strategy=strat,
            bitmap_bits=bits,
            vertex_args=(jnp.asarray(bs, dtype=jnp.int32),
                         jnp.asarray(bd, dtype=jnp.int32)),
        ))
        bucket_edges.append(e)
    meta.update(
        bucket_shapes=[s.shape_key for s in stages],
        bucket_strategies=[(s.shape_key[1], s.strategy) for s in stages],
        bucket_edges=bucket_edges,
        edges=int(fsrc.shape[0]),
        levels_max=int(lvl.max(initial=0)),
        bfs_sources=int((lvl == 0).sum()),
    )
    return stages, 1, meta


def plan_triangle_count(
    g: Graph,
    algorithm: str = "intersection",
    *,
    backend: str = "jnp",
    interpret: Optional[bool] = None,
    variant: str = "filtered",
    widths: Sequence[int] = DEFAULT_WIDTHS,
    strategy: str = "auto",
    block="auto",
    permute: bool = True,
    bitmap_bits: Optional[int] = None,
    prep_backend: str = "device",
    shape_policy: Optional[ShapePolicy] = None,
    max_device_bytes: Optional[int] = None,
    mesh=None,
) -> TrianglePlan:
    """Run the host stage once and return a device-resident ``TrianglePlan``.

    Args:
      g: the input ``Graph`` (undirected simple CSR).
      algorithm: "intersection" | "matrix" | "subgraph" | "hash" (the
        TRUST-style per-vertex hashing lane) | "bfs" (level-ordered
        wedge closure) | "intersection_distributed" /
        "matrix_distributed" (the mesh-planned sharded lanes: prep once,
        degree buckets / heavy-first tiles dealt round-robin across the
        mesh's shards, per-shard executables cached under a mesh-extended
        key, one scalar psum per stage).
      backend: "jnp" | "pallas" | "ref" per-kernel execution path.
      interpret: pallas interpret mode (True runs kernel bodies on CPU);
        None (default) resolves from the platform the plan runs on —
        interpreted on the CPU, compiled on a TPU
        (``repro.core.options.resolve_interpret``).
      variant: intersection lane only — "filtered" (forward algorithm) or
        "full" (every directed edge, each triangle found 6×).
      widths: degree-class bucket widths for the intersection/subgraph lanes.
      strategy: intersection/subgraph lanes only — per-bucket set-intersection
        core: "auto" (default; the documented ``choose_strategy`` cost model
        picks bitmap/probe/broadcast per bucket) or a forced "broadcast" |
        "probe" | "bitmap" override applied to every bucket.
      block: matrix lane tile size, or "auto" (``choose_block``).
      permute: matrix lane degree permutation toggle.
      bitmap_bits: optional forced packed capacity for bitmap-strategy
        buckets (must cover the graph's id range ``n + 2``); None sizes it
        via ``resolve_strategy``.
      prep_backend: intersection/subgraph lanes — "device" (default) runs
        the prep stage as the jitted pipeline in ``repro.core.prep``;
        "host" runs the numpy parity path.
      shape_policy: the ``ShapePolicy`` rounding device-prep extents into
        static shape classes; None means ``DEFAULT_SHAPE_POLICY``.
      max_device_bytes: intersection/subgraph/matrix lanes — optional
        per-bucket device-bytes budget. Buckets (or the matrix tile stack)
        whose resident arrays would exceed it are kept host-side and
        streamed through one cached chunk-shaped executable at ``count()``
        time (pow2 chunk rows ⇒ monotone shape classes, zero steady-state
        recompiles; counts bit-identical to monolithic). None (default)
        means ``default_device_budget()``: no budget on the CPU, a share of
        the device's memory on an accelerator. Distributed lanes ignore it —
        the mesh deal already partitions the working set.
      mesh: jax device mesh — consumed by the ``*_distributed`` lanes only
        (None there defaults to a 1-D mesh over every visible device,
        matching the historical one-shot functions); single-host lanes
        ignore it.

    Returns:
      A ``TrianglePlan`` whose ``count()`` replays the device stage only.
      The per-algorithm keyword arguments match ``CountOptions``; the
      facade (``repro.core.api.TriangleCounter``) and the deprecated
      one-shot ``triangle_count_*`` shims both route here.
    """
    interpret = resolve_interpret(interpret, lane=algorithm, backend=backend)
    if max_device_bytes is None:
        max_device_bytes = default_device_budget()
    t0 = time.perf_counter()
    if algorithm == "intersection":
        stages, divisor, meta = _plan_intersection(
            g, variant, backend, interpret, widths, strategy, bitmap_bits,
            prep_backend, shape_policy, max_device_bytes,
        )
    elif algorithm == "matrix":
        stages, divisor, meta = _plan_matrix(g, block, permute, backend,
                                             interpret, max_device_bytes)
    elif algorithm == "subgraph":
        stages, divisor, meta = _plan_subgraph(g, backend, interpret, widths,
                                               strategy, bitmap_bits,
                                               prep_backend, shape_policy,
                                               max_device_bytes)
    elif algorithm == "hash":
        stages, divisor, meta = _plan_hash(g, backend, interpret, widths,
                                           prep_backend, shape_policy)
    elif algorithm == "bfs":
        stages, divisor, meta = _plan_bfs(g, backend, interpret, widths,
                                          strategy, bitmap_bits, shape_policy)
    elif algorithm in DISTRIBUTED_ALGORITHMS:
        if mesh is None:
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((jax.device_count(),), ("data",))
        if algorithm == "intersection_distributed":
            stages, divisor, meta = _plan_intersection_distributed(
                g, mesh, variant, backend, interpret, widths, strategy,
                bitmap_bits, prep_backend, shape_policy,
            )
        else:
            stages, divisor, meta = _plan_matrix_distributed(
                g, mesh, block, permute, backend, interpret,
            )
        meta["mesh"] = mesh_cache_component(mesh)
    else:
        raise ValueError(
            f"unknown algorithm {algorithm!r}; expected one of "
            f"{ALGORITHMS + DISTRIBUTED_ALGORITHMS}"
        )
    meta.setdefault("graph", g.name)
    meta["n"], meta["m"] = g.n, g.m_undirected
    prep_seconds = time.perf_counter() - t0
    return TrianglePlan(
        algorithm=algorithm,
        backend=backend,
        interpret=interpret,
        stages=stages,
        divisor=divisor,
        meta=meta,
        prep_seconds=prep_seconds,
        degrees=g.degrees,
    )


def plan_hash_count(
    g: Graph,
    *,
    backend: str = "jnp",
    interpret: Optional[bool] = None,
    widths: Sequence[int] = DEFAULT_WIDTHS,
    prep_backend: str = "device",
    shape_policy: Optional[ShapePolicy] = None,
) -> TrianglePlan:
    """Plan the TRUST-style vertex-centric hashing lane (see ``_plan_hash``).

    Args mirror ``plan_triangle_count``'s shared subset; the lane has no
    ``strategy`` knob — its count core is the hash probe, not the sorted
    merge. Returns a ``TrianglePlan`` with ``algorithm="hash"``.
    """
    return plan_triangle_count(
        g, "hash", backend=backend, interpret=interpret, widths=widths,
        prep_backend=prep_backend, shape_policy=shape_policy,
    )


def plan_bfs_count(
    g: Graph,
    *,
    backend: str = "jnp",
    interpret: Optional[bool] = None,
    widths: Sequence[int] = DEFAULT_WIDTHS,
    strategy: str = "auto",
    bitmap_bits: Optional[int] = None,
    shape_policy: Optional[ShapePolicy] = None,
) -> TrianglePlan:
    """Plan the BFS-based lane (see ``_plan_bfs``).

    Args mirror ``plan_triangle_count``'s shared subset; ``strategy`` /
    ``bitmap_bits`` select the per-bucket intersection core exactly as on
    the intersection lane (the executables are shared). Returns a
    ``TrianglePlan`` with ``algorithm="bfs"``.
    """
    return plan_triangle_count(
        g, "bfs", backend=backend, interpret=interpret, widths=widths,
        strategy=strategy, bitmap_bits=bitmap_bits, shape_policy=shape_policy,
    )


def _hash_planner(g: Graph, options, *, mesh=None) -> TrianglePlan:
    """Registry planner: CountOptions → hashing-lane TrianglePlan."""
    return plan_hash_count(g, **options.plan_kwargs("hash"))


register_algorithm("hash", _hash_planner)


def _bfs_planner(g: Graph, options, *, mesh=None) -> TrianglePlan:
    """Registry planner: CountOptions → BFS-lane TrianglePlan."""
    return plan_bfs_count(g, **options.plan_kwargs("bfs"))


register_algorithm("bfs", _bfs_planner)


# ---------------------------------------------------------------------------
# TrussPlan — the edge lane: per-edge support + the device k-truss peel
# ---------------------------------------------------------------------------

def _decode_edge_keys(keys: np.ndarray, n1: int):
    """Packed ``lo * n1 + hi`` keys → ((lo, hi) int32 arrays), the single
    place the key encoding is inverted (host side)."""
    keys = np.asarray(keys, dtype=np.int64)
    return (keys // n1).astype(np.int32), (keys % n1).astype(np.int32)


@dataclasses.dataclass
class _EdgeStage:
    executable: Callable
    args: Tuple[jnp.ndarray, ...]  # (u_lists, v_lists, src, dst, row_ptr)
    shape_key: tuple
    strategy: str  # resolved match-mask strategy (broadcast | probe | bitmap)


def _edge_stages(g, *, widths: Sequence[int], strategy: str,
                 bitmap_bits: Optional[int], prep_backend: str,
                 policy: ShapePolicy, peel_key: tuple, mesh=None,
                 key_mode: str = "auto"):
    """Build one graph's edge-support stages: prep the filtered buckets (on
    the requested backend), materialize the slot→key addressing structure
    (sorted keys + permutation + forward row_ptr), and bind each bucket to
    its cached edge executable.

    Returns (stages, edge_keys, perm, m_edges, meta) — ``edge_keys`` is the
    (mk,) sorted device array whose leading ``m_edges`` slots are the real
    edges and ``perm`` reorders slot-indexed support into key order; the
    k-truss peel calls this once per round on the re-oriented survivor
    graph.

    With ``mesh`` set, each bucket's rows are dealt round-robin across the
    mesh's shards (``deal_across_shards``; ``row_ptr`` replicated) and the
    stages bind to the cached "edge_distributed" executables — every shard
    scatters its rows into the full (mk,) slot space and one vector psum
    per bucket combines the partial supports.
    """
    n = g.n
    mode = prep.check_edge_key_range(n, key_mode)
    buckets = _buckets_for_plan(g, "filtered", widths, prep_backend, policy)
    if prep_backend == "device":
        keys, perm, row_ptr, m_edges = prep.forward_edge_keys_device(
            g, policy=policy, key_mode=mode)
    else:
        keys_h, perm_h, row_ptr_h, m_edges = prep.forward_edge_keys_host(
            g, mode)
        with edge_key_context(mode):
            keys = jnp.asarray(keys_h, dtype=jnp.dtype(edge_key_dtype(mode)))
        perm = jnp.asarray(perm_h, dtype=jnp.int32)
        row_ptr = jnp.asarray(row_ptr_h, dtype=jnp.int32)
    mk, n1 = int(keys.shape[0]), n + 1
    id_range = n + 2  # real ids + the in-row sentinels n (u) and n+1 (v)
    if mesh is not None:
        ndev = int(np.prod(mesh.devices.shape))
        row_sharding = NamedSharding(mesh, PartitionSpec(
            tuple(mesh.axis_names)))
        row_ptr = jax.device_put(row_ptr,
                                 NamedSharding(mesh, PartitionSpec()))
    stages = []
    for b in buckets:
        # mask-specific cost model: the probe mask pays two searchsorted
        # passes, so bitmap wins out to ~4·W packed bits (resolve_mask_
        # strategy), not just the counting lane's id_range ≤ packed_bits(W)
        strat, bits = resolve_mask_strategy(b.width, id_range, strategy)
        if bitmap_bits is not None and strat == "bitmap":
            if bitmap_bits < id_range:
                raise ValueError(
                    f"bitmap_bits={bitmap_bits} cannot represent id range "
                    f"{id_range} (n + 2 sentinel ids); ids past the "
                    f"capacity would silently never match"
                )
            bits = int(bitmap_bits)
        if mesh is None:
            shape_key = b.shape + (mk, n1) + tuple(peel_key)
            fn = get_executable("edge", "jnp", False, shape_key,
                                strategy=strat, bitmap_bits=bits)
            args = (b.u_lists, b.v_lists, b.src, b.dst, row_ptr)
        else:
            rows = policy.round_edges(-(-b.edges // ndev))
            u = jax.device_put(
                deal_across_shards(b.u_lists, ndev, rows, fill=-1),
                row_sharding)
            v = jax.device_put(
                deal_across_shards(b.v_lists, ndev, rows, fill=-2),
                row_sharding)
            sb = jax.device_put(
                deal_across_shards(b.src, ndev, rows, fill=0), row_sharding)
            db = jax.device_put(
                deal_across_shards(b.dst, ndev, rows, fill=0), row_sharding)
            shape_key = (rows, b.width, mk, n1) + tuple(peel_key)
            fn = get_executable("edge_distributed", "jnp", False, shape_key,
                                strategy=strat, bitmap_bits=bits, mesh=mesh)
            args = (u, v, sb, db, row_ptr)
        stages.append(_EdgeStage(
            executable=fn,
            args=args,
            shape_key=shape_key,
            strategy=strat,
        ))
    meta = dict(
        bucket_shapes=[s.shape_key[:2] for s in stages],
        bucket_strategies=[(s.shape_key[1], s.strategy) for s in stages],
        bucket_edges=[b.edges for b in buckets],
        key_mode=mode,
    )
    if mesh is not None:
        meta["mesh"] = mesh_cache_component(mesh)
        meta["num_shards"] = ndev
    return stages, keys, perm, m_edges, meta


@dataclasses.dataclass
class TrussPlan:
    """A prepared edge-analytics session: device buffers + cached edge
    executables for per-edge support, plus the device k-truss peel loop.

    Mirrors ``TrianglePlan`` for the edge lane (registered as
    ``algorithm="edge"``): construction runs the prep stage once —
    orientation, bucketing, padded gathers, and the sorted undirected-edge
    key array — and ``support()`` / ``edge_support()`` / ``count()`` are
    device replays of the cached stages. ``k_truss(k)`` iterates the peel
    (support recompute → filter → re-orient through
    ``DeviceCSR.from_edges`` and the device prep pipeline) until fixpoint
    or ``max_peel_iters``; every round's stages come from the same
    process-wide executable cache, so rounds whose policy-rounded shapes
    collide compile nothing new. The host enumeration in
    ``repro.core.listing`` is never called (tests poison it).
    """

    graph: Graph
    stages: List[_EdgeStage]
    edge_keys: jnp.ndarray  # (mk,) sorted keys; padding = key-dtype max
    perm: jnp.ndarray  # (mk,) slot→key-order permutation
    m_edges: int
    widths: Tuple[int, ...]
    strategy: str
    bitmap_bits: Optional[int]
    prep_backend: str
    policy: ShapePolicy
    max_peel_iters: int
    peel_early_exit: bool
    meta: Dict[str, Any]
    prep_seconds: float
    executions: int = 0
    mesh: Any = None  # device mesh when the support stages are sharded
    key_mode: str = "int32"  # resolved packed-key mode (int32 | wide)

    algorithm: str = "edge"

    @staticmethod
    def _run_stages(stages: List[_EdgeStage], keys: jnp.ndarray,
                    perm: jnp.ndarray) -> jnp.ndarray:
        """Sum the per-bucket slot-ordered supports, then reorder into
        sorted-key order (one gather per round, aligned with ``keys``)."""
        total = jnp.zeros(keys.shape[0], jnp.int32)
        for st in stages:
            total = total + st.executable(*st.args)
        return total[perm]

    def support(self) -> np.ndarray:
        """(m,) int64 per-edge triangle-membership counts, in
        ``edge_list_unique`` (lex (lo, hi)) order; pure device replay."""
        total = self._run_stages(self.stages, self.edge_keys, self.perm)
        self.executions += 1
        return np.asarray(total, dtype=np.int64)[: self.m_edges]

    def edge_support(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(src, dst, support) with src < dst — the device replacement for
        ``repro.core.listing.edge_support`` (same order, same dtypes)."""
        keys = np.asarray(self.edge_keys)[: self.m_edges]
        su, sv = _decode_edge_keys(keys, self.graph.n + 1)
        return su, sv, self.support()

    def count(self) -> int:
        """Exact triangle count: every triangle contributes 1 to each of
        its three edges, so Σ support = 3Δ."""
        total = int(self.support().sum())
        assert total % 3 == 0, total
        return total // 3

    def count_with_stats(self) -> Tuple[int, dict]:
        return self.count(), dict(self.meta)

    def _peel(self, start: Optional[Graph], k: int,
              max_iters: int) -> Tuple[np.ndarray, int, bool]:
        """Bulk k-truss peel to fixpoint (or ``max_iters`` rounds).

        ``start=None`` peels the plan's own graph, reusing the cached
        first-round stages. Returns (surviving packed keys as int64 numpy,
        rounds run, converged) — identical semantics to the host oracle:
        every round removes ALL edges with support < k − 2 simultaneously.
        """
        thresh = int(k) - 2
        peel_key = (self.max_peel_iters, self.peel_early_exit)
        kw = dict(widths=self.widths, strategy=self.strategy,
                  bitmap_bits=self.bitmap_bits,
                  prep_backend=self.prep_backend, policy=self.policy,
                  peel_key=peel_key, mesh=self.mesh,
                  key_mode=self.key_mode)
        if start is None:
            stages, keys, perm, m_cur = (self.stages, self.edge_keys,
                                         self.perm, self.m_edges)
        else:
            stages, keys, perm, m_cur, _ = _edge_stages(start, **kw)
        n, n1 = self.graph.n, self.graph.n + 1
        rounds, converged = 0, (m_cur == 0)
        while rounds < max_iters and m_cur > 0:
            supp = self._run_stages(stages, keys, perm)
            keep = supp[:m_cur] >= thresh
            kept = int(jnp.sum(keep))  # one scalar sync per round
            rounds += 1
            if kept == m_cur:
                converged = True
                if self.peel_early_exit:
                    break
                continue  # fixpoint is stable; remaining rounds are no-ops
            if kept == 0:
                # the empty edge set is trivially stable: a fixpoint too
                m_cur, converged = 0, True
                break
            if self.prep_backend == "device":
                # re-orient on device: survivors symmetrized through the
                # jitted sort-based CSR build, then re-prepped (decode runs
                # under the key mode's x64 context; vertex ids fit int32)
                with edge_key_context(self.key_mode):
                    lo = (keys[:m_cur] // n1).astype(jnp.int32)
                    hi = (keys[:m_cur] % n1).astype(jnp.int32)
                csr = DeviceCSR.from_edges(
                    jnp.concatenate([lo, hi]), jnp.concatenate([hi, lo]),
                    n, valid=jnp.concatenate([keep, keep]),
                    policy=self.policy, key_mode=self.key_mode,
                )
                cur = DeviceGraph(csr, policy=self.policy,
                                  name=self.graph.name + "+peel")
            else:
                keys_h = np.asarray(keys)[:m_cur][np.asarray(keep)]
                su, sv = _decode_edge_keys(keys_h, n1)
                cur = edges_to_csr(su, sv, n=n,
                                   name=self.graph.name + "+peel")
            stages, keys, perm, m_cur, _ = _edge_stages(cur, **kw)
        self.executions += rounds
        return np.asarray(keys, dtype=np.int64)[:m_cur], rounds, converged

    def k_truss(self, k: int, *, max_iters: Optional[int] = None) -> Graph:
        """Maximal subgraph where every edge is in ≥ k − 2 triangles.

        The device peel loop: support recompute → filter → re-orient per
        round, stopping at the fixpoint (``peel_early_exit``) or after
        ``max_iters`` rounds (default: the plan's ``max_peel_iters``). The
        surviving edge set is bit-identical to the
        ``repro.core.listing.k_truss`` host oracle. ``meta["peel_rounds"]``
        / ``meta["peel_converged"]`` record the last peel.
        """
        max_iters = self.max_peel_iters if max_iters is None else int(max_iters)
        keys, rounds, converged = self._peel(None, k, max_iters)
        self.meta["peel_rounds"] = rounds
        self.meta["peel_converged"] = converged
        su, sv = _decode_edge_keys(keys, self.graph.n + 1)
        return edges_to_csr(su, sv, n=self.graph.n,
                            name=self.graph.name + f"+truss{k}")

    def truss_decomposition(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Per-edge trussness: the largest k such that the edge survives the
        k-truss. Returns (src, dst, trussness) with src < dst, in
        ``edge_list_unique`` order (edges in no triangle have trussness 2).

        Peels level by level — each k-truss starts from the previous level's
        survivors (the (k)-truss of the (k−1)-truss IS the (k)-truss of the
        graph), so the edges removed between levels are exactly the
        trussness-(k−1) class. Trussness is only defined at the peel's
        fixpoint, so every level must converge within ``max_peel_iters``;
        a bound chosen for truncated ``k_truss`` benchmarking raises here
        instead of silently inflating labels.

        Raises:
          ValueError: a level's peel hit ``max_peel_iters`` before its
            fixpoint.
        """
        n1 = self.graph.n + 1
        orig = np.asarray(self.edge_keys, dtype=np.int64)[: self.m_edges]
        truss = np.full(orig.shape[0], 2, dtype=np.int64)
        cur_keys, cur_graph, k = orig, None, 3
        while cur_keys.size:
            nxt_keys, _, converged = self._peel(cur_graph, k,
                                                self.max_peel_iters)
            if not converged:
                raise ValueError(
                    f"truss_decomposition needs every peel level to reach "
                    f"its fixpoint, but the {k}-truss peel was truncated at "
                    f"max_peel_iters={self.max_peel_iters}; raise the "
                    f"max_peel_iters option"
                )
            removed = cur_keys[~np.isin(cur_keys, nxt_keys)]
            truss[np.searchsorted(orig, removed)] = k - 1
            su, sv = _decode_edge_keys(nxt_keys, n1)
            cur_graph = edges_to_csr(su, sv, n=self.graph.n,
                                     name=self.graph.name + f"+truss{k}")
            cur_keys, k = nxt_keys, k + 1
        su, sv = _decode_edge_keys(orig, n1)
        return su, sv, truss

    def block_until_ready(self) -> "TrussPlan":
        for st in self.stages:
            for a in st.args:
                a.block_until_ready()
        self.edge_keys.block_until_ready()
        return self

    @property
    def num_stages(self) -> int:
        return len(self.stages)

    @property
    def shape_keys(self) -> List[tuple]:
        return [st.shape_key for st in self.stages]


def plan_edge_support(
    g: Graph,
    *,
    widths: Sequence[int] = DEFAULT_WIDTHS,
    strategy: str = "auto",
    bitmap_bits: Optional[int] = None,
    prep_backend: str = "device",
    shape_policy: Optional[ShapePolicy] = None,
    max_peel_iters: int = 1000,
    peel_early_exit: bool = True,
    mesh=None,
    key_mode: str = "auto",
) -> TrussPlan:
    """Run the edge lane's prep once and return a replayable ``TrussPlan``.

    Args:
      g: the input ``Graph`` (undirected simple CSR; packed edge keys are
        int32 while ``(n + 1)² ≤ int32 max`` — n ≲ 46k — and promote to
        the wide (x64 int64) mode past it under ``key_mode="auto"``).
      widths: degree-class bucket widths (as the intersection lane).
      strategy: per-bucket match-mask core — the mask-specific
        ``resolve_mask_strategy`` cost model: "auto" (bitmap while the id
        range stays within ~4·W packed bits — the probe mask pays two
        searchsorted passes — then probe for W ≥ 64, broadcast below) or a
        forced "broadcast" | "probe" | "bitmap".
      bitmap_bits: optional forced packed capacity for bitmap buckets
        (must cover the id range ``n + 2``).
      prep_backend: "device" (default; jitted prep + device peel) or "host"
        (numpy parity prep; the support executables still run on device).
      shape_policy: extent-rounding policy (None ⇒ ``DEFAULT_SHAPE_POLICY``).
      max_peel_iters: k-truss peel round bound (the peel normally stops at
        its fixpoint much earlier).
      peel_early_exit: stop the peel at the fixpoint (default) or run
        exactly ``max_peel_iters`` rounds (identical result; benchmarking
        mode). Both knobs are folded into the edge executables' cache key.
      mesh: optional jax device mesh — shards every bucket's support rows
        round-robin across the mesh (``deal_across_shards``); the partial
        (mk,) supports combine under one vector psum per bucket. Peel
        rounds re-deal the survivor graph over the same mesh. None keeps
        the single-host stages.
      key_mode: "auto" (int32 keys while they fit, wide int64 past that) |
        "int32" | "wide" — resolved through the single capacity checkpoint
        ``repro.graphs.device.resolve_edge_key_mode``, which raises
        ``GraphTooLargeError`` when the requested mode cannot represent
        the graph.

    Returns:
      A ``TrussPlan`` exposing ``edge_support()`` / ``k_truss(k)`` /
      ``truss_decomposition()`` / ``count()``. The facade surfaces these as
      ``TriangleCounter.edge_support()`` etc.; ``CountOptions`` maps onto
      the keyword arguments via ``plan_kwargs("edge")``.
    """
    policy = shape_policy if shape_policy is not None else DEFAULT_SHAPE_POLICY
    max_peel_iters = int(max_peel_iters)
    peel_early_exit = bool(peel_early_exit)
    if max_peel_iters < 1:
        raise ValueError(f"max_peel_iters must be ≥ 1, got {max_peel_iters}")
    t0 = time.perf_counter()
    stages, keys, perm, m_edges, bucket_meta = _edge_stages(
        g, widths=tuple(widths), strategy=strategy, bitmap_bits=bitmap_bits,
        prep_backend=prep_backend, policy=policy,
        peel_key=(max_peel_iters, peel_early_exit), mesh=mesh,
        key_mode=key_mode,
    )
    meta = dict(
        graph=g.name,
        n=g.n,
        m=g.m_undirected,
        edges=m_edges,
        widths=tuple(widths),
        strategy=strategy,
        prep_backend=prep_backend,
        shape_policy=policy.key() if prep_backend == "device" else None,
        max_peel_iters=max_peel_iters,
        peel_early_exit=peel_early_exit,
        **bucket_meta,
    )
    prep_seconds = time.perf_counter() - t0
    return TrussPlan(
        graph=g,
        stages=stages,
        edge_keys=keys,
        perm=perm,
        m_edges=m_edges,
        widths=tuple(widths),
        strategy=strategy,
        bitmap_bits=bitmap_bits,
        prep_backend=prep_backend,
        policy=policy,
        max_peel_iters=max_peel_iters,
        peel_early_exit=peel_early_exit,
        meta=meta,
        prep_seconds=prep_seconds,
        mesh=mesh,
        key_mode=bucket_meta["key_mode"],
    )


def _edge_planner(g: Graph, options, *, mesh=None) -> TrussPlan:
    """Registry planner: CountOptions → edge-lane TrussPlan (support
    stages sharded over ``mesh`` when the session carries one)."""
    return plan_edge_support(g, mesh=mesh, **options.plan_kwargs("edge"))


register_algorithm("edge", _edge_planner)


# ---------------------------------------------------------------------------
# DynamicPlan — the dynamic lane: batched edge updates, incremental count
# ---------------------------------------------------------------------------

class DynamicPlan:
    """Device state + cached executables for one dynamic-graph session.

    The plan owns a mutable device-resident edge set — two sorted
    orderings of packed keys, ``lo * (n + 1) + hi`` and
    ``hi * (n + 1) + lo`` (int32 when ``(n + 1)² ≤ int32 max``, else
    x64-gated int64 "wide" keys), with the mode's sentinel in dead
    slots; the
    orderings ARE the adjacency (any vertex's neighbor row is two
    contiguous runs) — and maintains the exact triangle count
    incrementally across batched
    :class:`~repro.graphs.formats.EdgeUpdate` streams:

    1. a cached "dynamic_step" executable resolves the batch against the
       key set (tombstone deletes, merge inserts, one sort per ordering
       compacts) and gathers the batch's anchor-vertex adjacency rows —
       pre- and post-update — in a single device dispatch that touches
       O(batch) adjacency, never a full CSR/neighbor rebuild;
    2. a cached "delta" executable counts triangles *anchored* on the
       effective deletes against the pre-update adjacency (Δ⁻) and on the
       effective inserts against the post-update adjacency (Δ⁺), with the
       6/k multi-anchor weighting described in
       ``_build_delta_executable``;
    3. ``count = count − Δ⁻ + Δ⁺``.

    Every array extent — key capacity, update rows, neighbor width — lives
    in a :class:`~repro.graphs.device.ShapePolicy` class and only ever
    grows, so steady-state batches replay two cached executables with zero
    recompiles; crossing a class boundary re-buckets and compiles exactly
    once (visible in ``executable_cache_info()``). Every
    ``recount_interval`` batches (and on demand via :meth:`recount`) a full
    from-scratch filtered-intersection recount over the device CSR checks
    the incremental count bit-exactly and raises on drift.
    """

    algorithm = "dynamic"

    def __init__(self, g: Graph, *, backend: str = "jnp",
                 interpret: Optional[bool] = None,
                 widths: Sequence[int] = DEFAULT_WIDTHS,
                 strategy: str = "auto",
                 bitmap_bits: Optional[int] = None,
                 shape_policy: Optional[ShapePolicy] = None,
                 update_batch_size: int = 256,
                 recount_interval: int = 64,
                 key_mode: str = "auto"):
        if backend not in ("jnp", "pallas", "ref"):
            raise ValueError(f"unknown backend {backend!r}; "
                             f"expected 'jnp', 'pallas', or 'ref'")
        self.key_mode = resolve_edge_key_mode(g.n, key_mode, lane="dynamic")
        self._sentinel = int(edge_key_sentinel(self.key_mode))
        self._key_dtype = edge_key_dtype(self.key_mode)
        update_batch_size = int(update_batch_size)
        recount_interval = int(recount_interval)
        if update_batch_size < 1:
            raise ValueError(
                f"update_batch_size must be ≥ 1, got {update_batch_size}")
        if recount_interval < 0:
            raise ValueError(
                f"recount_interval must be ≥ 0 (0 disables the periodic "
                f"oracle), got {recount_interval}")
        t0 = time.perf_counter()
        self.graph = g
        self.name = g.name
        self.n = int(g.n)
        self.backend = backend
        self.interpret = resolve_interpret(interpret, lane="dynamic",
                                           backend=backend)
        self.widths = tuple(int(w) for w in widths)
        self.strategy = strategy
        self.bitmap_bits = bitmap_bits
        self.policy = (shape_policy if shape_policy is not None
                       else DEFAULT_SHAPE_POLICY)
        self.update_batch_size = update_batch_size
        self.recount_interval = recount_interval
        self.ub = self.policy.round_edges(update_batch_size)
        # width class: the configured widths plus an optional pow2 top
        # bound that only ever grows (never recomputed down — a denser
        # interlude must not force a recompile on the way back)
        self._extra_top: Optional[int] = None
        dmax = int(g.max_degree)
        if dmax > self.widths[-1]:
            self._extra_top = next_pow2(dmax)
        # upload the initial edge set as BOTH sorted key orderings
        lo, hi = g.edge_list_unique()
        self.m = int(lo.shape[0])
        self.cap = self.policy.round_edges(self.m)
        n1 = self.n + 1
        host_keys = np.full(self.cap, self._sentinel, np.int64)
        host_keys[: self.m] = np.sort(
            lo.astype(np.int64) * n1 + hi.astype(np.int64))
        host_rkeys = np.full(self.cap, self._sentinel, np.int64)
        host_rkeys[: self.m] = np.sort(
            hi.astype(np.int64) * n1 + lo.astype(np.int64))
        with edge_key_context(self.key_mode):
            self._keys = jnp.asarray(host_keys.astype(self._key_dtype))
            self._rkeys = jnp.asarray(host_rkeys.astype(self._key_dtype))
        self.batches = 0
        self.inserted = 0
        self.deleted = 0
        self.recounts = 0
        self.executions = 0
        # prime: one all-padding step compiles this shape class
        self._apply_step(
            np.full(self.ub, self._sentinel, np.int64),
            np.full(self.ub, self._sentinel, np.int64),
            np.zeros(self.ub, bool), np.zeros(self.ub, bool))
        self._count = self._full_recount()
        self.meta = dict(
            graph=self.name, n=self.n, m=self.m,
            key_mode=self.key_mode,
            widths=self.widths, strategy=self.strategy,
            shape_policy=self.policy.key(),
            update_batch_size=self.update_batch_size,
            update_rows=self.ub,
            recount_interval=self.recount_interval,
            bounds=self.bounds, capacity=self.cap,
            bucket_strategies=self._bucket_strategies(),
            batches=0, inserted=0, deleted=0, recounts=0,
        )
        self.prep_seconds = time.perf_counter() - t0

    # -- shape classes ------------------------------------------------------

    @property
    def bounds(self) -> tuple:
        """The session's width classes (widths plus the monotone top)."""
        if self._extra_top is not None:
            return self.widths + (self._extra_top,)
        return self.widths

    def _bucket_strategies(self) -> list:
        id_range = self.n + 2
        return [(int(w), resolve_mask_strategy(int(w), id_range,
                                               self.strategy)[0])
                for w in self.bounds]

    def _maybe_grow_width(self, dmax: int) -> bool:
        if dmax <= self.bounds[-1]:
            return False
        self._extra_top = next_pow2(dmax)
        return True

    def _grow_capacity(self, needed: int) -> None:
        new_cap = self.policy.round_edges(needed)
        if new_cap <= self.cap:  # pragma: no cover - rounding is monotone
            raise AssertionError("capacity growth must be monotone")
        with edge_key_context(self.key_mode):
            pad = jnp.full(new_cap - self.cap, self._sentinel,
                           self._keys.dtype)
            self._keys = jnp.concatenate([self._keys, pad])
            self._rkeys = jnp.concatenate([self._rkeys, pad])
        self.cap = new_cap

    # -- cached executables -------------------------------------------------

    def _step_executable(self) -> Callable:
        # wide mode appends a trailing marker so int32 sessions keep their
        # exact historical cache keys (the builder strips it)
        wide = ("wide",) if self.key_mode == "wide" else ()
        return get_executable(
            "dynamic_step", "jnp", False,
            (self.cap, self.ub, self.n + 1, int(self.bounds[-1])) + wide)

    def _delta_executable(self) -> Callable:
        wide = ("wide",) if self.key_mode == "wide" else ()
        return get_executable(
            "delta", "jnp", False,
            (self.ub, self.n + 1) + self.bounds + wide,
            strategy=self.strategy, bitmap_bits=self.bitmap_bits)

    # -- update path --------------------------------------------------------

    def _apply_step(self, upd_keys: np.ndarray, upd_rkeys: np.ndarray,
                    upd_ins: np.ndarray, upd_valid: np.ndarray):
        """Run one padded device step and return its full output tuple."""
        with edge_key_context(self.key_mode):
            return self._step_executable()(
                self._keys, self._rkeys,
                jnp.asarray(upd_keys.astype(self._key_dtype)),
                jnp.asarray(upd_rkeys.astype(self._key_dtype)),
                jnp.asarray(upd_ins), jnp.asarray(upd_valid))

    def apply_updates(self, lo: np.ndarray, hi: np.ndarray,
                      insert: np.ndarray) -> dict:
        """Apply a normalized update stream and maintain the count.

        Args are the arrays produced by
        :func:`repro.graphs.formats.normalize_edge_updates` (oriented
        lo < hi pairs, self-loops dropped, last-wins deduped). The stream
        is chunked by ``update_batch_size``; each chunk runs the step +
        two delta dispatches described in the class docstring. Returns the
        refreshed ``meta`` dict.
        """
        lo = np.asarray(lo, dtype=np.int32)
        hi = np.asarray(hi, dtype=np.int32)
        insert = np.asarray(insert, dtype=bool)
        ubs = self.update_batch_size
        for s in range(0, int(lo.shape[0]), ubs):
            self._apply_chunk(lo[s:s + ubs], hi[s:s + ubs],
                              insert[s:s + ubs])
        return self._sync_meta()

    def _apply_chunk(self, lo_c: np.ndarray, hi_c: np.ndarray,
                     ins_c: np.ndarray) -> None:
        nu = int(lo_c.shape[0])
        if nu == 0:
            return
        # host capacity pre-check: grow the key array BEFORE the step so
        # the step executable compiles at most once per capacity class
        n_ins_req = int(ins_c.sum())
        if self.m + n_ins_req > self.cap:
            self._grow_capacity(self.m + n_ins_req)
        n1 = self.n + 1
        upd_keys = np.full(self.ub, self._sentinel, np.int64)
        upd_keys[:nu] = lo_c.astype(np.int64) * n1 + hi_c.astype(np.int64)
        upd_rkeys = np.full(self.ub, self._sentinel, np.int64)
        upd_rkeys[:nu] = hi_c.astype(np.int64) * n1 + lo_c.astype(np.int64)
        upd_ins = np.zeros(self.ub, bool)
        upd_ins[:nu] = ins_c
        upd_valid = np.zeros(self.ub, bool)
        upd_valid[:nu] = True
        d_lo = np.zeros(self.ub, np.int32)
        d_lo[:nu] = lo_c
        d_hi = np.zeros(self.ub, np.int32)
        d_hi[:nu] = hi_c
        step_out = self._apply_step(upd_keys, upd_rkeys, upd_ins, upd_valid)
        d_lo = jnp.asarray(d_lo)
        d_hi = jnp.asarray(d_hi)
        # Δ⁻: delete-anchored triangles against the PRE-update adjacency
        # (launched before the stats sync; the old rows fit the old class)
        (_, _, eff_ins, eff_del, ins_skeys, del_skeys,
         old_lr, old_hr, old_ld, old_hd, _, _, _, _, st) = step_out
        with edge_key_context(self.key_mode):
            sum_del = self._delta_executable()(
                old_lr, old_hr, old_ld, old_hd, d_lo, d_hi, eff_del,
                del_skeys)
        # one small sync: the step stats drive the (rare) width growth
        m_new, dmax_new, n_ins, n_del = (int(x) for x in np.asarray(st))
        if self._maybe_grow_width(dmax_new):
            # re-run the step once at the grown width class so the Δ⁺
            # anchor rows carry the full widened adjacency; the new-class
            # step/delta executables compile exactly once here (the
            # pre-update state is still uncommitted, so this is a pure
            # replay at the wider shape)
            step_out = self._apply_step(upd_keys, upd_rkeys, upd_ins,
                                        upd_valid)
        (new_keys, new_rkeys, eff_ins, eff_del, ins_skeys, del_skeys,
         _, _, _, _, new_lr, new_hr, new_ld, new_hd, st) = step_out
        # Δ⁺: insert-anchored triangles against the POST-update adjacency
        with edge_key_context(self.key_mode):
            sum_ins = self._delta_executable()(
                new_lr, new_hr, new_ld, new_hd, d_lo, d_hi, eff_ins,
                ins_skeys)
        sdel = int(np.asarray(sum_del))
        sins = int(np.asarray(sum_ins))
        if sdel % 6 or sins % 6:
            raise RuntimeError(
                f"dynamic delta drift on {self.name!r}: weighted anchor "
                f"sums ({sdel}, {sins}) are not divisible by 6")
        self._count += sins // 6 - sdel // 6
        # commit the post-update device state
        self._keys = new_keys
        self._rkeys = new_rkeys
        self.m = m_new
        self.inserted += n_ins
        self.deleted += n_del
        self.executions += 1
        self.batches += 1
        if self.recount_interval and self.batches % self.recount_interval == 0:
            self.recount()

    # -- counting & the parity oracle ---------------------------------------

    def _full_recount(self) -> int:
        if self.m == 0:
            return 0
        # the rare oracle path: materialize the live keys as a CSR (the
        # steady-state update path never builds one) and run the ordinary
        # filtered-intersection plan stages over it
        snap = self.snapshot()
        csr = DeviceCSR(n=self.n, m=2 * self.m,
                        row_ptr=jnp.asarray(snap.row_ptr),
                        col_idx=jnp.asarray(snap.col_idx))
        dg = DeviceGraph(csr, policy=self.policy,
                         name=self.name + "+recount")
        stages, _, _ = _plan_intersection(
            dg, "filtered", self.backend, self.interpret, self.widths,
            self.strategy, self.bitmap_bits, "device", self.policy)
        return sum(int(st.executable(*st.args)) for st in stages)

    def count(self) -> int:
        """The incrementally maintained exact triangle count (O(1))."""
        return self._count

    def count_with_stats(self):
        """(count, meta) with the meta refreshed to the current state."""
        return self._count, self._sync_meta()

    def recount(self) -> int:
        """Full-recount parity oracle: count the device CSR from scratch
        and raise ``RuntimeError`` if the incremental count has drifted."""
        full = self._full_recount()
        self.recounts += 1
        if full != self._count:
            raise RuntimeError(
                f"incremental triangle count drifted on {self.name!r}: "
                f"incremental={self._count}, full recount={full} after "
                f"{self.batches} update batches")
        return full

    def snapshot(self) -> Graph:
        """Materialize the current device edge set as a host ``Graph``."""
        keys = np.asarray(self._keys).astype(np.int64)
        keys = keys[keys != self._sentinel]
        lo, hi = _decode_edge_keys(keys, self.n + 1)
        return edges_to_csr(lo, hi, n=self.n, name=self.name + "+dynamic")

    def _sync_meta(self) -> dict:
        self.meta.update(
            m=self.m, capacity=self.cap, bounds=self.bounds,
            bucket_strategies=self._bucket_strategies(),
            batches=self.batches, inserted=self.inserted,
            deleted=self.deleted, recounts=self.recounts)
        return dict(self.meta)

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return (f"DynamicPlan(graph={self.name!r}, n={self.n}, m={self.m}, "
                f"count={self._count}, batches={self.batches})")


def plan_dynamic_count(
    g: Graph,
    *,
    backend: str = "jnp",
    interpret: Optional[bool] = None,
    widths: Sequence[int] = DEFAULT_WIDTHS,
    strategy: str = "auto",
    bitmap_bits: Optional[int] = None,
    shape_policy: Optional[ShapePolicy] = None,
    update_batch_size: int = 256,
    recount_interval: int = 64,
    key_mode: str = "auto",
) -> DynamicPlan:
    """Open a dynamic-graph counting session seeded from ``g``.

    Args:
      g: the seed ``Graph`` (may be empty). Graphs past the int32 packed
        pair-key bound (n ≳ 46k) automatically promote to the x64-gated
        int64 "wide" key mode; see ``key_mode``.
      backend / interpret / widths / strategy / bitmap_bits / shape_policy:
        as the intersection lane — they configure both the delta
        executables and the periodic full recount.
      update_batch_size: updates per device dispatch; longer streams are
        chunked. Padded to a policy extent (the "update rows" class).
      recount_interval: run the full-recount parity oracle every this many
        batches (0 disables it; ``recount()`` is always available).
      key_mode: packed-key representation — ``"auto"`` (int32 when it
        fits, else wide), ``"int32"`` (raise ``GraphTooLargeError`` past
        the bound), or ``"wide"`` (force int64 keys). Resolved by
        :func:`repro.graphs.device.resolve_edge_key_mode`.

    Returns:
      A ``DynamicPlan``; the facade surfaces it as
      ``DynamicTriangleCounter``, and ``CountOptions`` maps onto the
      keyword arguments via ``plan_kwargs("dynamic")``.
    """
    return DynamicPlan(
        g, backend=backend, interpret=interpret, widths=widths,
        strategy=strategy, bitmap_bits=bitmap_bits,
        shape_policy=shape_policy, update_batch_size=update_batch_size,
        recount_interval=recount_interval, key_mode=key_mode)


def _dynamic_planner(g: Graph, options, *, mesh=None) -> DynamicPlan:
    """Registry planner: CountOptions → dynamic-lane DynamicPlan."""
    return plan_dynamic_count(g, **options.plan_kwargs("dynamic"))


register_algorithm("dynamic", _dynamic_planner)


# ---------------------------------------------------------------------------
# GraphBatch — same-policy graphs stacked into one vmapped dispatch
# ---------------------------------------------------------------------------

def _pad_bucket_rows(arr: jnp.ndarray, e_pad: int, fill: int) -> jnp.ndarray:
    pad = e_pad - int(arr.shape[0])
    if pad <= 0:
        return arr
    return jnp.concatenate(
        [arr, jnp.full((pad, arr.shape[1]), fill, arr.dtype)]
    )


@dataclasses.dataclass
class GraphBatch:
    """A batch of graphs prepped under one ``ShapePolicy`` and stacked so the
    whole batch is counted by ONE vmapped device dispatch.

    Build via ``from_graphs``: each member runs the device-resident
    intersection prep, the per-width buckets are harmonized to the maximum
    policy-rounded extent across members (missing widths become all-padding
    buckets, which count zero), and each width's (u, v) pairs are stacked
    into (B, E, W) arrays. ``counts()`` then runs a single jitted program —
    every bucket's vmapped intersection plus the cross-bucket sum — from the
    shape-policy-keyed batch-executable cache. This is the
    ``TriangleCounter.count_many`` fast path.
    """

    graphs: List[Any]
    backend: str
    interpret: bool
    divisor: int
    specs: tuple  # ((strategy, bitmap_bits, (e_pad, width)), ...) per bucket
    arrays: List[jnp.ndarray]  # flattened (u, v) stacks, device-resident
    meta: Dict[str, Any]
    prep_seconds: float
    executions: int = 0

    @property
    def batch_size(self) -> int:
        return len(self.graphs)

    @property
    def shape_keys(self) -> List[tuple]:
        return [shape for _, _, shape in self.specs]

    def counts(self) -> np.ndarray:
        """(B,) exact triangle counts — one device dispatch for the batch."""
        if not self.specs:
            out = np.zeros(self.batch_size, dtype=np.int64)
        else:
            fn = get_batch_executable(self.specs, self.backend,
                                      self.interpret, self.batch_size)
            out = np.asarray(fn(*self.arrays), dtype=np.int64)
        if self.divisor != 1:
            assert (out % self.divisor == 0).all(), out
            out //= self.divisor
        self.executions += 1
        return out

    def block_until_ready(self) -> "GraphBatch":
        for a in self.arrays:
            a.block_until_ready()
        return self

    @classmethod
    def from_graphs(cls, graphs: Sequence[Graph], options=None,
                    **overrides) -> "GraphBatch":
        """Prep + stack ``graphs`` under one options bag.

        Args:
          graphs: host ``Graph``s (any mix of sizes; the stacked layout is
            the per-width maximum of the policy-rounded extents).
          options: a ``CountOptions``; None builds one from ``**overrides``.
            Must have ``backend="jnp"`` (the vmapped cores are the pure-jnp
            paths) and ``prep_backend="device"``.

        Raises:
          ValueError: empty batch, or options outside the batchable regime.
        """
        from repro.core.options import CountOptions

        if options is None:
            options = CountOptions(**overrides)
        elif overrides:
            options = options.replace(**overrides)
        graphs = list(graphs)
        if not graphs:
            raise ValueError("GraphBatch needs at least one graph")
        if options.backend != "jnp":
            raise ValueError(
                f"GraphBatch requires backend='jnp' (vmapped pure-jnp "
                f"cores); got {options.backend!r}"
            )
        if options.prep_backend != "device":
            raise ValueError(
                "GraphBatch requires prep_backend='device' (the stacked "
                "layout is defined by the device prep's ShapePolicy)"
            )
        policy = options.resolved_shape_policy
        interpret = options.resolved_interpret
        t0 = time.perf_counter()
        per_graph = [
            prep.prepare_intersection_buckets_device(
                g, variant=options.variant, widths=options.widths,
                policy=policy,
            )
            for g in graphs
        ]
        # harmonize: per width, every member is padded to the max rounded
        # extent; members without that width contribute all-padding buckets
        widths_union = sorted({b.width for bs in per_graph for b in bs})
        id_range = max(g.n for g in graphs) + 2
        specs, arrays = [], []
        for w in widths_union:
            members = [
                {b.width: b for b in bs}.get(w) for bs in per_graph
            ]
            e_pad = max(policy.round_edges(1) if b is None else b.e_pad
                        for b in members)
            us, vs = [], []
            for b in members:
                if b is None:
                    us.append(jnp.full((e_pad, w), -1, jnp.int32))
                    vs.append(jnp.full((e_pad, w), -2, jnp.int32))
                else:
                    us.append(_pad_bucket_rows(b.u_lists, e_pad, -1))
                    vs.append(_pad_bucket_rows(b.v_lists, e_pad, -2))
            strat, bits = _resolve_bucket_strategy(
                w, id_range, options.strategy, options.bitmap_bits
            )
            specs.append((strat, bits, (e_pad, w)))
            arrays.extend([jnp.stack(us), jnp.stack(vs)])
        prep_seconds = time.perf_counter() - t0
        meta = dict(
            batch_size=len(graphs),
            variant=options.variant,
            widths=tuple(options.widths),
            strategy=options.strategy,
            shape_policy=policy.key(),
            prep_backend="device",
            bucket_shapes=[s[2] for s in specs],
            bucket_strategies=[(s[2][1], s[0]) for s in specs],
            graphs=[g.name for g in graphs],
        )
        return cls(
            graphs=graphs,
            backend=options.backend,
            interpret=interpret,
            divisor=6 if options.variant == "full" else 1,
            specs=tuple(specs),
            arrays=arrays,
            meta=meta,
            prep_seconds=prep_seconds,
        )
