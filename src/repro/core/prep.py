"""Per-lane prep stages: device-resident (jitted) with host parity paths.

The paper's pipeline for every method splits into a *prep stage* (filtering,
orientation, degree-class grouping, tile scheduling) and a *count stage* (the
kernels §4 measures). PR 1 made the split explicit (plan/execute); this
module moves the prep stage itself onto the device: the intersection and
subgraph lanes' orientation, bucketing, padded gathers, 2-core peel, and
induced-subgraph reform all run as the jitted stages in
``repro.graphs.device``, orchestrated here per lane. The only host↔device
traffic during planning is a handful of scalar syncs (per-bucket counts, the
max forward degree, the peel's survivor count) needed to pick static shapes —
which a ``ShapePolicy`` rounds to powers of two so same-policy graphs share
every traced stage.

Lanes:

* ``prepare_intersection_buckets_device`` — orientation + bucket layout +
  padded gathers for the intersection lane (and the subgraph lane's join),
  returning device-resident ``DeviceBucket``s — or, for a bucket past the
  caller's bytes budget, a ``StreamedBucket`` that gathers chunk by chunk.
* ``peel_to_two_core_device`` / ``induced_device_graph`` — the subgraph
  lane's FILTER + RECONSTRUCT as device stages (vertex ids are kept, not
  renumbered: dead vertices just lose their rows).
* ``build_tile_schedule`` / ``choose_block`` — the matrix lane's prep. The
  BSR triple join's output size is data-dependent in a way static shapes
  can't express cheaply, so this stage stays host-side (documented in
  ``docs/ARCHITECTURE.md``); it lives here so every lane's prep has one
  home.
* ``prepare_intersection_buckets_host`` / ``peel_to_two_core`` — the
  original numpy paths, kept as parity references (``prep_backend="host"``
  and ``tests/test_prep_parity.py`` compare the device stages against them)
  and for host-side consumers of bucket dicts (the strat benchmark sweep,
  labeled subgraph queries).

``repro.core.engine`` re-exports the historical names
(``prepare_intersection_buckets``, ``build_tile_schedule``,
``peel_to_two_core``, ``choose_block``) as thin wrappers over this module.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple, Union

import numpy as np
import jax.numpy as jnp

from repro.graphs.formats import (
    Graph,
    apply_permutation,
    bucket_edges_by_degree,
    csr_to_padded_neighbors,
    degree_order_permutation,
    orient_forward,
    to_block_sparse,
)
from repro.graphs.device import (
    DEFAULT_SHAPE_POLICY,
    DeviceCSR,
    DeviceGraph,
    ShapePolicy,
    next_pow2,
    _bucket_sort_dev,
    _gather_bucket_dev,
    _induced_compact_dev,
    _sorted_edge_keys_dev,
    _two_core_peel_dev,
    edge_key_context,
    edge_key_dtype,
    edge_key_sentinel,
    fits_int32_pair_keys,
    resolve_edge_key_mode,
)
from repro.core import tracing
from repro.core.options import DEFAULT_WIDTHS

__all__ = [
    "DeviceBucket",
    "StreamedBucket",
    "bucket_nbytes",
    "build_tile_schedule",
    "check_edge_key_range",
    "choose_block",
    "delta_update_buckets",
    "forward_edge_keys_device",
    "forward_edge_keys_host",
    "gathered_count",
    "induced_device_graph",
    "peel_to_two_core",
    "peel_to_two_core_device",
    "prepare_intersection_buckets_device",
    "prepare_intersection_buckets_host",
]


# ---------------------------------------------------------------------------
# Device prep — the intersection/subgraph lanes
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DeviceBucket:
    """One degree-class bucket, device-resident and statically shaped.

    ``u_lists``/``v_lists`` are (e_pad, width) int32 sorted neighbor lists;
    the first ``edges`` rows are real, the rest whole-row padding (u = -1,
    v = -2 ⇒ zero matches). ``src``/``dst`` are the per-row edge endpoints
    (padding rows carry 0, harmless because their match counts are zero).
    """

    width: int
    edges: int
    u_lists: jnp.ndarray
    v_lists: jnp.ndarray
    src: jnp.ndarray
    dst: jnp.ndarray

    @property
    def e_pad(self) -> int:
        return int(self.u_lists.shape[0])

    @property
    def shape(self) -> tuple:
        return (self.e_pad, self.width)

    @property
    def table_width(self) -> int:
        """Columns of the neighbor table the rows were gathered from: every
        gather takes whole rows."""
        return int(self.u_lists.shape[1])


def bucket_nbytes(e_pad: int, width: int) -> int:
    """Device bytes one gathered intersection bucket costs: the (e, w)
    int32 u/v neighbor-list pair plus the (e,) int32 src/dst endpoints."""
    return int(e_pad) * (8 * int(width) + 8)


@dataclasses.dataclass
class StreamedBucket:
    """A degree-class bucket too large to gather whole.

    Its sorted edge endpoints and its own (n, width) padded neighbor table
    stay on device; ``count(gathered, offset, rows)`` materializes rows
    ``[offset, offset + rows)`` of the ``DeviceBucket`` layout it stands for
    — (u, v, src, dst), with rows past ``edges`` as whole-row padding —
    inside the executable that consumes them. One compiled gather serves
    every chunk of every same-width bucket of a ``rows`` class. The
    bucket's rows ``[start, start + edges)`` of ``sorted_src`` keep CSR
    order, so a chunk's rows come in runs of equal source.
    """

    width: int
    edges: int
    e_pad: int
    n: int
    sorted_src: jnp.ndarray
    sorted_dst: jnp.ndarray
    start: int
    nbrs: jnp.ndarray

    @property
    def shape(self) -> tuple:
        return (self.e_pad, self.width)

    @property
    def table_width(self) -> int:
        return int(self.nbrs.shape[1])

    def _window(self, offset: int, rows: int) -> tuple:
        return (self.sorted_src, self.sorted_dst,
                jnp.int32(self.start + offset),
                jnp.int32(max(0, min(rows, self.edges - offset))), self.nbrs)

    def count(self, gathered, offset: int, rows: int, *extra):
        """``gathered`` (a ``gathered_count`` executable) over the chunk at
        ``offset``, gathered and counted in one dispatch; ``extra`` follows
        the chunk's arguments."""
        return gathered(*self._window(offset, rows), *extra, n=self.n,
                        rows=int(rows))


def gathered_count(fn, endpoints: bool = False):
    """``fn(u, v)`` — with ``endpoints``, ``fn(u, v, src, dst, *extra)`` —
    fused with the chunk gather, jitted under ``fn``'s name with
    ``_gathered`` appended."""

    def gathered(sorted_src, sorted_dst, start, count, nbrs, *extra,
                 n: int, rows: int):
        u, v, src, dst = _gather_bucket_dev(sorted_src, sorted_dst, start,
                                            count, nbrs, n=n, e_pad=rows)
        if endpoints:
            return fn(u, v, src, dst, *extra)
        return fn(u, v)

    return tracing.jit(f"{fn.__name__}_gathered", gathered,
                       static_argnames=("n", "rows"))


def _as_device_graph(g: Union[Graph, DeviceGraph],
                     policy: Optional[ShapePolicy]) -> DeviceGraph:
    if isinstance(g, DeviceGraph):
        return g
    return DeviceGraph.from_graph(g, policy or DEFAULT_SHAPE_POLICY)


def prepare_intersection_buckets_device(
    g: Union[Graph, DeviceGraph],
    *,
    variant: str = "filtered",
    widths: Sequence[int] = DEFAULT_WIDTHS,
    policy: Optional[ShapePolicy] = None,
    max_bucket_bytes: Optional[int] = None,
) -> List[Union[DeviceBucket, StreamedBucket]]:
    """Device-resident intersection prep: orientation + bucket layout +
    padded neighbor gathers, all jitted.

    Each bucket gathers whole rows of a padded neighbor table exactly as
    wide as the bucket (``DeviceGraph.padded_neighbors(width)``, cached;
    the widest is built first and the narrower are its leading columns):
    both endpoints of an edge in bucket ``w`` have degree ≤ ``w``, so the
    table holds their whole lists, and a whole-row gather stays a gather
    on a TPU.

    Args:
      g: a host ``Graph`` (uploaded once) or an existing ``DeviceGraph``.
      variant: "filtered" (forward orientation; each triangle found once) or
        "full" (all directed edges with full lists; each found 6×).
      widths: ascending degree-class bucket widths; wider edges land in a
        final next-pow2 bucket, exactly as the host path.
      policy: the ``ShapePolicy`` rounding per-bucket extents (ignored when
        ``g`` is already a ``DeviceGraph``, which carries its own).
      max_bucket_bytes: a bucket whose gathered arrays
        (``bucket_nbytes``) would exceed this is returned as a
        ``StreamedBucket`` instead of being gathered; None gathers all.

    Returns:
      A list of ``DeviceBucket`` / ``StreamedBucket``; empty degree classes
      are dropped. Host
      syncs: one small transfer for the per-bucket counts and max degree —
      everything else stays on device.
    """
    if variant not in ("filtered", "full"):
        raise ValueError(
            f"unknown variant {variant!r}; expected 'filtered' or 'full'"
        )
    dg = _as_device_graph(g, policy)
    n = dg.n
    if dg.m == 0:
        return []

    if variant == "filtered":
        fwd = dg.forward()
        src, dst, valid = fwd.src, fwd.dst, fwd.kvalid
        deg = fwd.degrees
    else:
        src, dst, valid = dg.edge_sources(), dg.csr.col_idx, dg.edge_valid()
        deg = dg.csr.degrees

    # one scalar sync to pick the static top-bucket width
    dmax = int(jnp.max(deg))
    bounds = [int(w) for w in widths]
    if dmax > bounds[-1]:
        bounds.append(next_pow2(dmax))
    ssrc, sdst, counts, starts = _bucket_sort_dev(
        src, dst, valid, deg, jnp.asarray(bounds, jnp.int32),
        n=n, num_bounds=len(bounds),
    )
    counts_h = np.asarray(counts)  # one small sync for static extents
    oriented = variant == "filtered"
    # the widest table first: each narrower one is then its leading columns
    dg.padded_neighbors(max(w for w, c in zip(bounds, counts_h) if c),
                        oriented=oriented)

    out = []
    for i, w in enumerate(bounds):
        c = int(counts_h[i])
        if c == 0:
            continue
        nbrs = dg.padded_neighbors(w, oriented=oriented)
        e_pad = dg.policy.round_edges(c)
        if max_bucket_bytes is not None \
                and bucket_nbytes(e_pad, w) > max_bucket_bytes:
            out.append(StreamedBucket(
                width=w, edges=c, e_pad=e_pad, n=n, sorted_src=ssrc,
                sorted_dst=sdst, start=int(counts_h[:i].sum()),
                nbrs=nbrs))
            continue
        u, v, sb, db = _gather_bucket_dev(
            ssrc, sdst, starts[i], counts[i], nbrs, n=n, e_pad=e_pad,
        )
        out.append(DeviceBucket(width=w, edges=c, u_lists=u, v_lists=v,
                                src=sb, dst=db))
    return out


def delta_update_buckets(lo_rows: jnp.ndarray, hi_rows: jnp.ndarray,
                         lo_deg: jnp.ndarray, hi_deg: jnp.ndarray,
                         lo: jnp.ndarray, hi: jnp.ndarray,
                         valid: jnp.ndarray, *, n: int,
                         bounds: Sequence[int]) -> list:
    """Incremental re-bucketing of one update batch's anchor edges (traced;
    called from inside the engine's jitted delta executables).

    The dynamic lane's analogue of ``prepare_intersection_buckets_device``,
    restricted to the update batch: each masked anchor edge is assigned to
    the first degree-class bound >= max(deg(lo), deg(hi)), then every class
    is gathered to a **fixed** (ub, width) layout where ub = the batch row
    extent. The adjacency source is the step's slot-indexed anchor-row
    block — ``lo_rows[i]`` / ``hi_rows[i]`` are the endpoint rows of anchor
    edge i, gathered straight from the sorted key orderings — so the whole
    pass touches O(batch · width) data, never the full graph. Unlike the
    static prep there is NO host sync and NO data-dependent extent — empty
    classes are materialized as all-padding rows (u = -1 / v = -2, zero
    matches in every core) — so the whole re-bucketing lives inside one
    cached executable and updates never recompile within a shape class.

    Args:
      lo_rows, hi_rows: (ub, bounds[-1]) padded adjacency rows (in-row
        sentinel ``n``, ascending) of each anchor edge's endpoints against
        the graph side being counted.
      lo_deg, hi_deg: (ub,) the matching endpoint degrees.
      lo, hi: (ub,) anchor edge endpoints (lo < hi on valid rows).
      valid: (ub,) mask of live anchor rows.
      n: vertex count (static).
      bounds: ascending degree-class bounds; ``bounds[-1]`` must be >= the
        graph's max degree (the session maintains this monotonically).

    Returns:
      One ``(width, u_lists, v_lists, src, dst)`` tuple per bound, each
      (ub, width)-shaped with the repo-wide sentinel conventions.
    """
    ub = int(lo.shape[0])
    num_bounds = len(bounds)
    barr = jnp.asarray(list(bounds), jnp.int32)
    w = jnp.maximum(lo_deg, hi_deg)
    b = jnp.searchsorted(barr, w, side="left")
    b = jnp.where(valid, b, num_bounds).astype(jnp.int32)
    order = jnp.argsort(b)  # stable: batch order preserved within a class
    counts = jnp.bincount(b, length=num_bounds + 1)[:num_bounds]
    starts = jnp.concatenate(
        [jnp.zeros(1, counts.dtype), jnp.cumsum(counts)])[:num_bounds]
    rows = jnp.arange(ub)
    out = []
    for i, width in enumerate(bounds):
        width = int(width)
        bvalid = rows < counts[i]
        slot = order[jnp.clip(starts[i] + rows, 0, max(ub - 1, 0))]
        sb = jnp.where(bvalid, lo[slot], 0).astype(jnp.int32)
        db = jnp.where(bvalid, hi[slot], 0).astype(jnp.int32)
        u = jnp.where(bvalid[:, None], lo_rows[slot, :width],
                      -1).astype(jnp.int32)
        vfull = hi_rows[slot, :width]
        v = jnp.where(bvalid[:, None],
                      jnp.where(vfull == n, n + 1, vfull),
                      -2).astype(jnp.int32)
        out.append((width, u, v, sb, db))
    return out


def check_edge_key_range(n: int, key_mode: str = "auto", *,
                         lane: str = "edge-support") -> str:
    """Resolve the edge lane's packed-key mode for a graph.

    The edge-support executables address undirected edges through sorted
    ``lo * (n + 1) + hi`` keys — int32 on the ``fits_int32_pair_keys`` fast
    path, wide (x64 int64) past it. Delegates to the repo's single capacity
    checkpoint, ``repro.graphs.device.resolve_edge_key_mode``.

    Returns:
      The resolved concrete key mode: "int32" or "wide".

    Raises:
      GraphTooLargeError: the requested mode cannot represent the graph.
    """
    return resolve_edge_key_mode(n, key_mode, lane=lane)


def forward_edge_keys_device(
    g: Union[Graph, DeviceGraph],
    *,
    policy: Optional[ShapePolicy] = None,
    key_mode: str = "auto",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, int]:
    """The edge lane's undirected-edge addressing structure, on device.

    The forward orientation keeps exactly one directed copy of every
    undirected edge, so a forward CSR *slot* IS an undirected edge id. The
    engine's edge executables accumulate support in slot order (which makes
    the side-edge scatters dense per-row adds); this function supplies the
    conversion to the canonical order: each slot's packed
    ``min·(n+1)+max`` key, sorted (= ``edge_list_unique``'s (lo, hi) lex
    order), plus the sort permutation mapping sorted positions back to
    slots. Padding slots carry the key-dtype max sentinel and sort to the
    end.

    Args:
      g: a host ``Graph`` (uploaded once) or an existing ``DeviceGraph``.
      policy: extent-rounding policy (ignored when ``g`` is a
        ``DeviceGraph``, which carries its own).
      key_mode: "auto" promotes int32 keys to wide (int64) keys past
        ``fits_int32_pair_keys``; "int32"/"wide" force a mode.

    Returns:
      (keys, perm, row_ptr, m): the (mk_pad,) sorted keys (int32 or int64
      per the resolved mode), the (mk_pad,) slot permutation
      (``supp_slots[perm]`` is support in key order), the forward (n+1,)
      row_ptr the executables scatter through, and the true undirected edge
      count occupying the leading key slots.
    """
    dg = _as_device_graph(g, policy)
    mode = check_edge_key_range(dg.n, key_mode)
    kdt = edge_key_dtype(mode)
    if dg.m == 0:
        mk = dg.policy.round_edges(0)
        with edge_key_context(mode):
            return (jnp.full(mk, edge_key_sentinel(mode), jnp.dtype(kdt)),
                    jnp.arange(mk, dtype=jnp.int32),
                    jnp.zeros(dg.n + 1, jnp.int32), 0)
    fwd = dg.forward()
    with edge_key_context(mode):
        keys, perm = _sorted_edge_keys_dev(fwd.src, fwd.dst, fwd.kvalid,
                                           n1=dg.n + 1,
                                           wide=(mode == "wide"))
    return keys, perm, fwd.row_ptr, dg.m // 2


def forward_edge_keys_host(
    g: Graph, key_mode: str = "auto",
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Numpy parity path of ``forward_edge_keys_device``.

    Host slots are the oriented DAG's CSR positions (``orient_forward``),
    so keys per slot need an explicit lex sort into (lo, hi) order.

    Returns:
      (keys, perm, row_ptr, m): unpadded (m,) sorted keys (int32 fast path,
      int64 wide mode), the (m,) slot permutation, the oriented (n+1,)
      row_ptr, and m itself.
    """
    mode = check_edge_key_range(g.n, key_mode)
    dag = orient_forward(g)
    src, dst = dag.edge_endpoints()
    lo = np.minimum(src, dst).astype(np.int64)
    hi = np.maximum(src, dst).astype(np.int64)
    key = (lo * (g.n + 1) + hi).astype(edge_key_dtype(mode))
    perm = np.argsort(key, kind="stable").astype(np.int32)
    return key[perm], perm, dag.row_ptr.astype(np.int32), int(key.shape[0])


def peel_to_two_core_device(dg: DeviceGraph) -> jnp.ndarray:
    """Device 2-core peel (the subgraph lane's FILTER taken to fixed point).

    Returns the (n,) bool alive mask as a device array.
    """
    if dg.m == 0:
        return jnp.zeros(dg.n, dtype=bool)
    return _two_core_peel_dev(
        dg.edge_sources(), dg.csr.col_idx, dg.edge_valid(),
        jnp.ones(dg.n, dtype=bool), n=dg.n,
    )


def induced_device_graph(dg: DeviceGraph, alive: jnp.ndarray) -> DeviceGraph:
    """RECONSTRUCT on device: keep edges with both endpoints alive.

    Vertex ids are preserved (dead vertices keep ids but lose their rows),
    so per-vertex scatters downstream stay in original-id space — the
    renumbering the host path does is an artifact of compact numpy arrays,
    not of the algorithm. One scalar sync (the survivor edge count) picks
    the policy-rounded static extent of the compacted arrays.
    """
    row_ptr_sub, col, kept_dev = _induced_compact_dev(
        dg.csr.row_ptr, dg.csr.col_idx, alive, dg.m,
        n=dg.n, m_pad=dg.csr.m_pad,
    )
    kept = int(kept_dev)
    m_pad_sub = dg.policy.round_edges(kept)
    csr = DeviceCSR(n=dg.n, m=kept, row_ptr=row_ptr_sub,
                    col_idx=col[:m_pad_sub])
    return DeviceGraph(csr, policy=dg.policy, name=dg.name + "+sub")


# ---------------------------------------------------------------------------
# Host parity paths (numpy) — prep_backend="host" and the parity tests
# ---------------------------------------------------------------------------

def prepare_intersection_buckets_host(
    g: Graph,
    variant: str = "filtered",
    widths: Sequence[int] = DEFAULT_WIDTHS,
) -> list:
    """The original numpy intersection prep, kept as the parity reference.

    Args:
      g: undirected simple ``Graph``.
      variant: "filtered" — forward orientation (rank = (degree, id)), the
        paper's "filter out half of the edges by degree order"; the oriented
        rows double as the reformed induced subgraph's neighbor lists.
        "full" — all directed edges with full neighbor lists (each triangle
        found 6×), the tc-intersection-full ablation.
      widths: ascending degree-class bucket widths; edges wider than
        ``widths[-1]`` land in a final next-pow2 bucket.

    Returns:
      A list of dicts ``{u_lists, v_lists, src, dst, width}``, one per
      non-empty degree-class bucket. ``u_lists``/``v_lists`` are (E_b, W_b)
      int32 numpy arrays of sorted neighbor lists; ``src``/``dst`` are the
      (E_b,) edge endpoints each row belongs to (per-vertex analysis scatters
      through them). Sentinel-padding rule: u rows pad with ``n``, v rows
      with ``n + 1`` (never equal ⇒ padding contributes zero matches); both
      sentinels sort above every real id, keeping rows sorted.
    """
    if variant == "filtered":
        dag = orient_forward(g)
        src, dst = dag.edge_endpoints()
        deg = dag.degrees
        base = dag
    elif variant == "full":
        src, dst = g.edge_endpoints()
        deg = g.degrees
        base = g
    else:
        raise ValueError(
            f"unknown variant {variant!r}; expected 'filtered' or 'full'"
        )

    buckets = bucket_edges_by_degree(src, dst, deg, widths=widths)
    out = []
    for b in buckets:
        w = b["width"]
        nbrs = csr_to_padded_neighbors(base, pad_to=max(w, 1), fill=g.n)
        u_lists = nbrs[b["src"]]
        v_lists = nbrs[b["dst"]].copy()
        v_lists[v_lists == g.n] = g.n + 1  # disjoint sentinel
        out.append(dict(u_lists=u_lists, v_lists=v_lists,
                        src=b["src"], dst=b["dst"], width=w))
    return out


def peel_to_two_core(g: Graph, labels: Optional[np.ndarray] = None,
                     query_label: Optional[int] = None) -> np.ndarray:
    """INITIALIZE_CANDIDATE_SET + iterated filter, to fixed point (host API).

    Args:
      g: undirected simple ``Graph``.
      labels: optional (n,) vertex labels for labeled subgraph queries.
      query_label: with ``labels``, prune vertices whose label cannot match
        any query vertex before the degree peel.

    Returns:
      Bool (n,) numpy mask of vertices surviving the 2-core peel (every
      triangle vertex has ≥ 2 alive neighbors, so counting on the induced
      subgraph is exact).
    """
    src, dst = g.edge_endpoints()
    init = np.ones(g.n, dtype=bool)
    if labels is not None and query_label is not None:
        init &= np.asarray(labels) == query_label
    if g.m_directed == 0:
        return np.zeros(g.n, dtype=bool)
    alive = _two_core_peel(jnp.asarray(src), jnp.asarray(dst),
                           jnp.asarray(init), n=g.n)
    return np.asarray(alive)


def _two_core_peel(src: jnp.ndarray, dst: jnp.ndarray,
                   init_alive: jnp.ndarray, *, n: int) -> jnp.ndarray:
    """Unmasked fixed-point peel over a concrete edge list (host callers)."""
    valid = jnp.ones(src.shape[0], dtype=bool)
    return _two_core_peel_dev(src, dst, valid, init_alive, n=n)


# ---------------------------------------------------------------------------
# Matrix lane prep (host stage — see module docstring)
# ---------------------------------------------------------------------------

def choose_block(g: Graph) -> int:
    """Adaptive tile size (§Perf hillclimb, beyond-paper): degree-permuted
    scale-free graphs densify the bottom-right tile cluster, so 128 (MXU
    native) wins; mesh-like graphs (low, uniform degree) never fill tiles —
    measured 40,000× MXU-flop waste and 25× wall-time regression at 128 vs
    32 on road-like — so low-avg-degree graphs get small tiles."""
    avg_deg = 2.0 * g.m_undirected / max(g.n, 1)
    return 128 if avg_deg >= 8.0 else 32


def build_tile_schedule(
    g: Graph, block: int = 128, permute: bool = True
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, dict]:
    """Host-side stage of the matrix method: degree permutation + BSR tiling +
    the L/U/A triple schedule.

    Args:
      g: undirected simple ``Graph``.
      block: dense tile edge length B (128 = MXU native).
      permute: apply the degree-order permutation first (the paper's
        tc-matrix step 1).

    Returns:
      (l_tiles, u_tiles, a_tiles, stats): three stacked (T, B, B) float32
      arrays — the L tile, U tile, and A mask tile of each scheduled triple —
      plus a stats dict (num_triples, tile counts, grid, block, tile_flops).
      Triples are sorted heavy-first (by block density product); that order is
      the unit of distribution for multi-device TC (core/distributed.py deals
      it round-robin for static load balance — the TPU analogue of
      merge-path's equal-work splitting).
    """
    if permute:
        perm = degree_order_permutation(g)
        g = apply_permutation(g, perm)
    a_bsr = to_block_sparse(g, block=block, part="upper")  # mask: strict upper
    l_bsr = to_block_sparse(g, block=block, part="lower")
    u_bsr = to_block_sparse(g, block=block, part="upper")

    # block-row index of L: row -> list of (K, tile_id); block-col index of U
    l_rows: dict = {}
    for t in range(l_bsr.num_blocks):
        l_rows.setdefault(int(l_bsr.block_row[t]), []).append(
            (int(l_bsr.block_col[t]), t)
        )
    u_cols: dict = {}
    for t in range(u_bsr.num_blocks):
        u_cols.setdefault(int(u_bsr.block_col[t]), []).append(
            (int(u_bsr.block_row[t]), t)
        )

    trip_l, trip_u, trip_a = [], [], []
    for t in range(a_bsr.num_blocks):
        bi, bj = int(a_bsr.block_row[t]), int(a_bsr.block_col[t])
        lk = dict(l_rows.get(bi, ()))
        uk = dict(u_cols.get(bj, ()))
        for k in lk.keys() & uk.keys():
            trip_a.append(t)
            trip_l.append(lk[k])
            trip_u.append(uk[k])

    T = len(trip_a)
    stats = dict(
        num_triples=T,
        a_tiles=a_bsr.num_blocks,
        l_tiles=l_bsr.num_blocks,
        u_tiles=u_bsr.num_blocks,
        grid=a_bsr.grid,
        block=block,
        tile_flops=2 * T * block**3,
    )
    if T == 0:
        z = np.zeros((0, block, block), dtype=np.float32)
        return z, z, z, stats

    l_sel = l_bsr.blocks[np.asarray(trip_l)]
    u_sel = u_bsr.blocks[np.asarray(trip_u)]
    a_sel = a_bsr.blocks[np.asarray(trip_a)]
    # heavy-first ordering by nnz(L)·nnz(U) so chunked execution and
    # round-robin sharding see a monotone work profile
    work = l_sel.sum(axis=(1, 2)) * u_sel.sum(axis=(1, 2))
    order = np.argsort(-work, kind="stable")
    return l_sel[order], u_sel[order], a_sel[order], stats
