"""One front door for triangle counting: ``TriangleCounter`` + ``CountResult``.

The paper's central result is comparative — three formulations with different
winners per graph shape — so the public API is a single session object over a
typed options bag rather than three differently-shaped free functions:

    from repro.core import TriangleCounter, CountOptions

    tc = TriangleCounter(g)                      # algorithm="auto"
    res = tc.count()                             # CountResult
    res.count, res.algorithm                     # count + the lane chosen
    res.bucket_strategies                        # per-bucket kernel picks
    tc.count()                                   # replays the cached plan

``TriangleCounter`` owns ONE ``TrianglePlan`` (built lazily through the
algorithm registry, ``repro.core.registry``): every ``count()`` is a device
replay, ``count_many()`` maps the same options over a graph batch (same-shaped
graphs share the process-wide executable cache), and the analysis surfaces
replay cached device buffers instead of ``listing.py``'s engine-bypassing
host enumeration: per-vertex (``triangles_per_vertex`` /
``clustering_coefficients`` / ``transitivity``, the "vertex" executables)
and per-edge (``edge_support`` / ``k_truss`` / ``truss_decomposition``, the
"edge" executables plus the device k-truss peel loop — see
``repro.core.engine.TrussPlan``).

``CountResult`` replaces the ``(int, dict)`` tuple of the old
``count_with_stats()``: the count plus which lane ran, per-bucket strategies,
prep/exec timings, and the live plan handle. It compares equal to plain ints
(``res == triangle_count_scipy(g)``) so oracle checks read naturally.
(``count_with_stats()`` survives on every session as a thin ``(int, dict)``
view over the same result.)

Both session types share the ``CounterSession`` base — one graph, one
``CountOptions``, one lazily built plan, the process-wide executable cache:

* ``TriangleCounter`` — the static session above.
* ``DynamicTriangleCounter`` — the dynamic-graph session: seed it with a
  ``Graph``, stream batched ``EdgeUpdate`` lists through
  ``apply_updates()``, and the exact triangle count is maintained
  incrementally on the device (``repro.core.engine.DynamicPlan``): updates
  mutate the device-resident CSR in place inside ``ShapePolicy`` shape
  classes (zero recompiles until an extent overflows its class, then
  exactly one re-bucket), deltas come from cached executables that
  intersect only the adjacency lists the batch touched, and a periodic
  full recount asserts bit-exact parity.

The legacy one-shot functions (``triangle_count_intersection`` /
``triangle_count_matrix`` / ``triangle_count_subgraph`` and the
``*_distributed`` pair) are deprecated shims over this facade — signatures
preserved, same return values, plus a ``DeprecationWarning``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import time
import warnings
from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple

import numpy as np

from repro.core import registry
from repro.core.engine import (GraphBatch, executable_cache_info,
                               local_clustering_coefficients,
                               plan_triangle_count)
from repro.core.options import CountOptions
from repro.graphs.formats import Graph, normalize_edge_updates

__all__ = ["CountResult", "CounterSession", "DynamicTriangleCounter",
           "TriangleCounter", "graph_fingerprint", "warn_deprecated"]


def graph_fingerprint(g: Graph) -> str:
    """A stable content hash of a graph's CSR (32 hex chars).

    Two ``Graph`` objects with identical ``(n, row_ptr, col_idx)`` — the
    arrays every plan is built from — fingerprint identically regardless of
    ``name`` or object identity. The serving layer keys its session and
    prepped-plan caches on ``(graph_fingerprint(g), options.key())`` so
    repeat requests for the same graph reuse device prep instead of
    redoing it. Cost is one pass over the CSR (no device work).
    """
    h = hashlib.blake2b(digest_size=16)
    h.update(str(int(g.n)).encode())
    h.update(np.ascontiguousarray(g.row_ptr, dtype=np.int64).tobytes())
    h.update(np.ascontiguousarray(g.col_idx, dtype=np.int64).tobytes())
    return h.hexdigest()


def warn_deprecated(old: str, new: str) -> None:
    """Emit the facade's standard DeprecationWarning (used by the legacy
    ``triangle_count_*`` shims; stacklevel points at the shim's caller)."""
    warnings.warn(
        f"{old} is deprecated; use {new} (see README.md §Migration)",
        DeprecationWarning,
        stacklevel=3,
    )


@dataclasses.dataclass(eq=False)
class CountResult:
    """One triangle count plus everything about how it was produced.

    Attributes:
      count: the exact triangle count.
      algorithm: the lane that ran — the resolved choice when the session's
        options said ``algorithm="auto"``.
      options: the ``CountOptions`` the session was built from (``auto``
        preserved as written; ``algorithm`` above is the resolution).
      bucket_strategies: intersection/subgraph lanes — the per-degree-bucket
        ``(width, strategy)`` picks; None for lanes without buckets.
      prep_seconds: the plan's one-time host stage (0.0 for one-shot lanes).
      exec_seconds: this count's device replay, measured around ``count()``.
      plan: the live plan handle (``TrianglePlan`` or ``OneShotPlan``) —
        replay it directly, inspect ``plan.meta``, or time ``plan.count``.
      meta: the plan's statistics dict (prune fractions, tile schedule
        sizes, bucket shapes, ``num_embeddings`` on the subgraph lane).

    Compares equal to ints via ``count`` (and coerces with ``int()``), so
    ``result == triangle_count_scipy(g)`` is the natural oracle check.
    """

    count: int
    algorithm: str
    options: CountOptions
    bucket_strategies: Optional[List[Tuple[int, str]]]
    prep_seconds: float
    exec_seconds: float
    plan: Any
    meta: Dict[str, Any]

    def __int__(self) -> int:
        return self.count

    def __index__(self) -> int:
        return self.count

    def __eq__(self, other) -> bool:
        if isinstance(other, CountResult):
            return self.count == other.count
        if isinstance(other, (int, np.integer)):
            return self.count == int(other)
        return NotImplemented

    def __repr__(self) -> str:
        return (f"CountResult(count={self.count}, "
                f"algorithm={self.algorithm!r}, "
                f"prep_seconds={self.prep_seconds:.4f}, "
                f"exec_seconds={self.exec_seconds:.4f})")


class CounterSession:
    """Shared machinery for every counting session type.

    One graph, one typed ``CountOptions`` bag, one lazily built plan. Both
    the static session (``TriangleCounter``) and the dynamic one
    (``DynamicTriangleCounter``) expose the same core surface —
    ``count()`` → ``CountResult``, ``count_with_stats()`` → ``(int,
    dict)``, and the ``cache_stats()`` view of the engine's process-wide
    executable cache — so callers can swap session types without touching
    the measurement code around them.

    Args:
      g: the input ``Graph`` (undirected simple CSR).
      options: a ``CountOptions``; None builds one from ``**overrides``.
      mesh: jax device mesh, consumed by the distributed lanes only.
      **overrides: ``CountOptions`` field overrides, applied on top of
        ``options`` (or the defaults) — ``TriangleCounter(g,
        algorithm="matrix", block=64)`` reads like the old free functions.

    Subclasses pick their registry lane via ``_resolve_algorithm`` (called
    ONCE at construction; the choice is exposed as ``.algorithm`` and in
    every ``CountResult``). The plan builds lazily on first use — equal
    options over same-shaped graphs share the engine's process-wide
    executable cache, so a second session compiles nothing new.
    """

    def __init__(self, g: Graph, options: Optional[CountOptions] = None,
                 *, mesh=None, **overrides):
        if options is None:
            options = CountOptions(**overrides)
        elif overrides:
            options = options.replace(**overrides)
        if not isinstance(options, CountOptions):
            raise TypeError(
                f"options must be a CountOptions, got {type(options).__name__}"
            )
        self.graph = g
        self.options = options
        self.mesh = mesh
        self.algorithm = self._resolve_algorithm()
        self._plan = None

    def _resolve_algorithm(self) -> str:
        """Map the session's options to its registry lane (subclass hook)."""
        if self.options.algorithm != "auto":
            return self.options.algorithm
        return self._choose_auto(self.graph)

    def _choose_auto(self, g: Graph) -> str:
        """Resolve ``algorithm="auto"`` per ``options.chooser``: "measured"
        consults the calibration table (``core.calibrate``, heuristic
        fallback built in), "heuristic" keeps the registry's shape rules.
        Either way the session's mesh rides along, so a multi-device session
        promotes the pick to the matching distributed lane."""
        if self.options.chooser == "measured":
            from repro.core.calibrate import choose_measured
            return choose_measured(g, mesh=self.mesh)
        return registry.choose_algorithm(g, mesh=self.mesh)

    @property
    def plan(self):
        """The session's plan, built on first access via the registry."""
        if self._plan is None:
            planner = registry.get_algorithm(self.algorithm)
            self._plan = planner(self.graph, self.options, mesh=self.mesh)
        return self._plan

    def count(self) -> CountResult:
        """Count triangles (device replay after the first call)."""
        plan = self.plan
        t0 = time.perf_counter()
        c = plan.count()
        exec_seconds = time.perf_counter() - t0
        meta = dict(getattr(plan, "meta", None) or {})
        if self.algorithm == "subgraph":
            meta["num_embeddings"] = 6 * c  # all |Aut(K3)| automorphisms
        return CountResult(
            count=c,
            algorithm=self.algorithm,
            options=self.options,
            bucket_strategies=meta.get("bucket_strategies"),
            prep_seconds=float(getattr(plan, "prep_seconds", 0.0)),
            exec_seconds=exec_seconds,
            plan=plan,
            meta=meta,
        )

    def count_with_stats(self) -> Tuple[int, Dict[str, Any]]:
        """The classic ``(count, stats)`` pair: the ``CountResult``'s count
        and its meta dict, with the resolved lane under ``"algorithm"``."""
        res = self.count()
        stats = dict(res.meta)
        stats["algorithm"] = res.algorithm
        return res.count, stats

    @staticmethod
    def cache_stats() -> Dict[str, int]:
        """Process-wide executable-cache statistics — a live ``{"size",
        "hits", "misses"}`` snapshot of ``engine.executable_cache_info()``
        (every session shares one cache, so deltas across calls measure
        compilations caused in between)."""
        return executable_cache_info()

    def session_key(self) -> tuple:
        """The session's reuse identity: ``(graph_fingerprint(graph),
        options.key())``. Two sessions with equal keys are interchangeable —
        same graph content, same resolved options — which is exactly what
        the serving layer's bounded session cache needs to hand concurrent
        tenants a shared session instead of re-prepping per request."""
        return (graph_fingerprint(self.graph), self.options.key())


class TriangleCounter(CounterSession):
    """A static counting session: one graph, one options bag, one cached
    plan (see ``CounterSession`` for the shared surface and constructor).

    On top of the shared surface, this session batches (``count_many`` /
    ``iter_counts``) and carries the per-vertex / per-edge analysis
    accessors, all routed through the cached plan and the engine's
    executable cache.
    """

    def __init__(self, g: Graph, options: Optional[CountOptions] = None,
                 *, mesh=None, **overrides):
        super().__init__(g, options, mesh=mesh, **overrides)
        self._vertex_counts: Optional[np.ndarray] = None
        self._edge_sidecar = None

    def count_many(self, graphs: Iterable[Graph],
                   *, batch_size: int = 8) -> List[CountResult]:
        """Count a batch of graphs under this session's options.

        The input is consumed LAZILY, ``batch_size`` graphs at a time —
        generators are never materialized up front. Within each chunk, every
        graph whose lane resolves to the batchable regime (``intersection``,
        ``backend="jnp"``, ``prep_backend="device"`` — the defaults) is
        device-prepped and stacked into one ``GraphBatch``, so the whole
        chunk is counted by ONE vmapped device dispatch instead of a Python
        loop of per-graph plans. The stacked executable comes from the
        engine's shape-policy-keyed batch-plan cache, so successive chunks
        whose policy-rounded layouts collide compile nothing new.

        Graphs outside the batchable regime (other lanes under
        ``algorithm="auto"``, pallas backends, host prep) fall back to a
        per-graph session; the session's own graph reuses the session plan.
        In particular, a multi-device ``mesh`` promotes lanes to their
        distributed variants, which are NOT batchable — every graph then
        takes the per-graph fallback (results stay correct, but the one
        stacked dispatch is lost). That fallback emits a ``UserWarning``
        once per session; a sharded ``GraphBatch`` is ROADMAP-tracked
        follow-up work.
        Results come back in input order. Batched results share one
        ``GraphBatch`` as their ``plan`` handle, and their
        ``prep_seconds`` / ``exec_seconds`` are the WHOLE chunk's figures
        (``meta["batched"]`` / ``meta["batch_size"]`` mark them) — don't sum
        them across a chunk.

        ``iter_counts`` is the generator twin: identical semantics and the
        same ``batch_size`` chunking kwarg, but it yields results as each
        chunk lands instead of materializing the list.
        """
        return list(self.iter_counts(graphs, batch_size=batch_size))

    def iter_counts(self, graphs: Iterable[Graph],
                    *, batch_size: int = 8) -> Iterator[CountResult]:
        """Generator form of ``count_many``: yields ``CountResult``s in input
        order while pulling at most ``batch_size`` graphs ahead of the
        consumer (the streaming surface for unbounded graph sources)."""
        if batch_size < 1:
            raise ValueError(f"batch_size must be ≥ 1, got {batch_size}")
        it = iter(graphs)
        while True:
            chunk = list(itertools.islice(it, batch_size))
            if not chunk:
                return
            yield from self._count_chunk(chunk)

    def _batchable(self, lane: str) -> bool:
        return (lane == "intersection"
                and self.options.backend == "jnp"
                and self.options.prep_backend == "device")

    def _count_chunk(self, chunk: List[Graph]) -> List[CountResult]:
        results: List[Optional[CountResult]] = [None] * len(chunk)
        batchable: List[Tuple[int, Graph]] = []
        for pos, g in enumerate(chunk):
            if g is self.graph:
                results[pos] = self.count()
                continue
            lane = (self.options.algorithm
                    if self.options.algorithm != "auto"
                    else self._choose_auto(g))
            if self._batchable(lane):
                batchable.append((pos, g))
            else:
                if self.mesh is not None and \
                        not getattr(self, "_warned_mesh_fallback", False):
                    self._warned_mesh_fallback = True
                    warnings.warn(
                        f"count_many: lane {lane!r} under a mesh is not "
                        f"batchable — counting graph {g.name!r} (and any "
                        f"other non-batchable member) in a per-graph "
                        f"session instead of one stacked dispatch; a "
                        f"sharded GraphBatch is tracked follow-up work",
                        UserWarning, stacklevel=4)
                results[pos] = TriangleCounter(
                    g, self.options, mesh=self.mesh
                ).count()
        if len(batchable) == 1:  # nothing to stack; a plain session is cheaper
            pos, g = batchable[0]
            results[pos] = TriangleCounter(g, self.options,
                                           mesh=self.mesh).count()
        elif batchable:
            opts = self.options if self.options.algorithm == "intersection" \
                else self.options.replace(algorithm="intersection")
            batch = GraphBatch.from_graphs([g for _, g in batchable], opts)
            t0 = time.perf_counter()
            counts = batch.counts()
            exec_seconds = time.perf_counter() - t0
            for (pos, g), c in zip(batchable, counts):
                results[pos] = CountResult(
                    count=int(c),
                    algorithm="intersection",
                    options=self.options,
                    bucket_strategies=batch.meta["bucket_strategies"],
                    prep_seconds=batch.prep_seconds,
                    exec_seconds=exec_seconds,
                    plan=batch,
                    meta=dict(batch.meta, graph=g.name, n=g.n,
                              m=g.m_undirected, batched=True),
                )
        return results

    # -- per-vertex analysis, routed through the cached plan ---------------

    def triangles_per_vertex(self) -> np.ndarray:
        """(n,) int64 per-vertex triangle counts.

        Replays the session plan's device buffers when the lane supports it
        (filtered intersection, subgraph); other lanes fall back to a
        filtered-intersection sidecar over the same widths. Either way the
        result is memoized on the session and the executables live in the
        engine's shared cache — no host-side re-enumeration per call.
        """
        if self._vertex_counts is None:
            plan = self.plan
            if not hasattr(plan, "triangles_per_vertex"):
                t = _vertex_counts_sidecar(self.graph, self.options)
            else:
                try:
                    t = plan.triangles_per_vertex()
                except NotImplementedError:
                    t = _vertex_counts_sidecar(self.graph, self.options)
            self._vertex_counts = t
        return self._vertex_counts.copy()

    # -- per-edge analysis (support / k-truss), routed through the engine --

    def _edge_plan(self):
        """The session's edge-lane plan (``TrussPlan``): the session plan
        itself when ``algorithm="edge"``, else a memoized sidecar built from
        the same options — so equal options share the engine's cached edge
        executables either way."""
        if self.algorithm == "edge":
            return self.plan
        if self._edge_sidecar is None:
            planner = registry.get_algorithm("edge")
            self._edge_sidecar = planner(self.graph, self.options,
                                         mesh=self.mesh)
        return self._edge_sidecar

    def edge_support(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(src, dst, support) with src < dst: per-undirected-edge triangle
        membership counts, replayed through the engine's cached edge
        executables (same order and dtypes as the deprecated
        ``repro.core.listing.edge_support``)."""
        return self._edge_plan().edge_support()

    def k_truss(self, k: int, *, max_iters: Optional[int] = None):
        """Maximal subgraph where every edge is in ≥ k − 2 triangles.

        Runs the device peel loop (support recompute → filter → re-orient
        until fixpoint or ``max_iters``, default the session's
        ``max_peel_iters``); the surviving edge set is bit-identical to the
        deprecated host path ``repro.core.listing.k_truss``. Returns a
        ``Graph``.
        """
        return self._edge_plan().k_truss(k, max_iters=max_iters)

    def truss_decomposition(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(src, dst, trussness) with src < dst: for every edge, the largest
        k such that it survives the k-truss (2 for edges in no triangle).
        Raises ValueError if ``max_peel_iters`` truncates any level's peel
        before its fixpoint (trussness is only defined at the fixpoint)."""
        return self._edge_plan().truss_decomposition()

    def clustering_coefficients(self) -> np.ndarray:
        """cc[v] = 2·t(v) / (d(v)·(d(v)−1)); 0 where degree < 2, in float64
        (``engine.local_clustering_coefficients``, the formula
        ``TrianglePlan.clustering_coefficients`` applies) over the
        session's memoized per-vertex counts."""
        return local_clustering_coefficients(self.triangles_per_vertex(),
                                             self.graph.degrees)

    def transitivity(self) -> float:
        """3 · #triangles / #wedges (= Σ t(v) / #wedges)."""
        t = int(self.triangles_per_vertex().sum())
        d = self.graph.degrees.astype(np.int64)
        wedges = int((d * (d - 1) // 2).sum())
        return float(t) / wedges if wedges else 0.0

    def __repr__(self) -> str:
        return (f"TriangleCounter(graph={self.graph.name!r}, "
                f"algorithm={self.algorithm!r}, "
                f"planned={self._plan is not None})")


class DynamicTriangleCounter(CounterSession):
    """A dynamic-graph session: batched edge updates, incremental count.

    Seed it with a ``Graph`` (possibly empty — ``edges_to_csr([], [],
    n=...)``), then stream update batches through ``apply_updates``::

        from repro.core import DynamicTriangleCounter, EdgeUpdate

        dc = DynamicTriangleCounter(g, update_batch_size=256)
        dc.count()                                   # seed count
        dc.apply_updates([EdgeUpdate(0, 1),          # insert (default)
                          EdgeUpdate(2, 3, insert=False),
                          (4, 5)])                   # bare pair = insert
        dc.count()                                   # maintained count

    Updates are normalized on the host (oriented, self-loops dropped,
    last-wins per edge within a batch — exact under set semantics), then
    applied ``update_batch_size`` at a time by the cached device step +
    delta executables of ``repro.core.engine.DynamicPlan``. ``count()`` is
    O(1): the count is maintained, not recomputed. Duplicate inserts and
    deletes of absent edges are no-ops. Every ``recount_interval`` batches
    (a ``CountOptions`` knob; 0 disables) a full from-scratch recount
    asserts the maintained count bit-exactly; ``recount()`` runs the same
    oracle on demand and ``snapshot()`` materializes the current edge set
    as a host ``Graph``.

    The session always runs the "dynamic" registry lane: constructing it
    with ``algorithm`` set to any other lane raises ``ValueError``.
    """

    def _resolve_algorithm(self) -> str:
        if self.options.algorithm not in ("auto", "dynamic"):
            raise ValueError(
                f"DynamicTriangleCounter always runs the dynamic lane; "
                f"got algorithm={self.options.algorithm!r} "
                f"(expected one of ('auto', 'dynamic'))")
        return "dynamic"

    def apply_updates(self, updates) -> CountResult:
        """Apply one batch of edge updates and return the refreshed count.

        ``updates`` is any iterable of ``EdgeUpdate`` named tuples,
        ``(u, v)`` pairs (implicit insert), or ``(u, v, insert)`` triples;
        vertex ids must lie in ``[0, n)``. The returned ``CountResult``'s
        ``exec_seconds`` covers the whole batch (update chunks + delta
        passes), and its ``meta`` reflects the post-update session state.
        """
        lo, hi, ins = normalize_edge_updates(updates, self.graph.n)
        plan = self.plan
        t0 = time.perf_counter()
        plan.apply_updates(lo, hi, ins)
        res = self.count()
        res.exec_seconds = time.perf_counter() - t0
        return res

    def recount(self) -> int:
        """Run the full-recount parity oracle now (raises on drift)."""
        return self.plan.recount()

    def snapshot(self) -> Graph:
        """The current device edge set as a host ``Graph``."""
        return self.plan.snapshot()

    @property
    def m_undirected(self) -> int:
        """The current number of live undirected edges."""
        return self.plan.m

    def __repr__(self) -> str:
        return (f"DynamicTriangleCounter(graph={self.graph.name!r}, "
                f"planned={self._plan is not None})")


def _vertex_counts_sidecar(g: Graph, options: CountOptions) -> np.ndarray:
    """Per-vertex counts for lanes whose plans carry no edge endpoints
    (matrix, full-variant intersection, custom lanes): a filtered-intersection
    plan over the same widths, sharing the cached ``"vertex"`` executables.
    The plan's count executables are jit-lazy, so none compile here."""
    plan = plan_triangle_count(
        g, "intersection", variant="filtered", backend="jnp",
        widths=options.widths,
    )
    return plan.triangles_per_vertex()
