"""Subgraph-matching triangle counting (paper §3.1/§4.1) — a filtering-and-
joining pipeline in the style of Tran et al., with the paper's optimizations.

FILTER (Gunrock Advance/Filter analogue → JAX):
  Candidate vertices must satisfy the triangle query's degree (≥2) and label
  constraints. The paper iterates filter+reconstruct "for a few iterations to
  prune out more edges"; taken to its fixed point that is exactly a 2-core
  peel, which runs as a `lax.while_loop` over a static edge list (no dynamic
  shapes; `segment_sum` plays the role of the Advance frontier) — see
  :func:`repro.core.engine.peel_to_two_core`. This is what wins on mesh-like
  graphs — leaf cascades collapse.

RECONSTRUCT: the surviving vertex mask reforms the induced subgraph on the
  host (the paper's 'reconstructing the data graph updates node degree and
  neighbor list information').

JOIN: candidate edges are joined under the triangle's intersection rule —
  matches(e=(u,v)) = |N(u) ∩ N(v) ∩ alive|, evaluated with the same bucketed
  batch-intersection kernels as tc_intersection (the paper's joining also
  reduces to verification-by-intersection). The join produces *embeddings*
  (all 6 automorphisms per triangle, as a real subgraph matcher must);
  ``triangle_count_subgraph`` divides by |Aut(K₃)| = 6.

This module registers the ``"subgraph"`` lane with the algorithm registry;
the front door is ``TriangleCounter(g, CountOptions(algorithm="subgraph"))``
(filter + reconstruct + bucket setup run once at plan time, the join replays
on device). The one-shot ``triangle_count_subgraph`` below is a deprecated
shim. ``subgraph_match_triangle`` handles labeled queries, which carry
per-query candidate-edge masks and so stay one-shot.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np
import jax.numpy as jnp

from repro.graphs.formats import Graph, induced_subgraph
from repro.core.prep import _two_core_peel
from repro.core.engine import (
    peel_to_two_core,  # re-export (prep lives in repro.core.prep)
    plan_triangle_count,
)
from repro.core.options import resolve_interpret
from repro.core.registry import register_algorithm

__all__ = [
    "peel_to_two_core",
    "triangle_count_subgraph",
    "subgraph_match_triangle",
]


def _planner(g: Graph, options, *, mesh=None):
    """Registry planner: CountOptions → subgraph-lane TrianglePlan."""
    return plan_triangle_count(g, "subgraph", **options.plan_kwargs("subgraph"))


register_algorithm("subgraph", _planner)


def triangle_count_subgraph(
    g: Graph,
    *,
    backend: str = "jnp",
    interpret: Optional[bool] = None,
    return_stats: bool = False,
):
    """Deprecated shim: exact TC via filter(2-core-peel) + reform + join.

    Use ``TriangleCounter(g, CountOptions(algorithm="subgraph", ...))``
    instead — ``CountResult.meta`` carries the stats ``return_stats=True``
    returns here. ``interpret=None`` resolves from the platform
    (``resolve_interpret``). Return values are unchanged: an int, or
    ``(int, stats dict)`` with ``return_stats=True``.
    """
    from repro.core.api import TriangleCounter, warn_deprecated
    from repro.core.options import CountOptions

    warn_deprecated(
        "triangle_count_subgraph(g, ...)",
        'TriangleCounter(g, CountOptions(algorithm="subgraph", ...)).count()',
    )
    opts = CountOptions(algorithm="subgraph", backend=backend,
                        interpret=interpret)
    result = TriangleCounter(g, opts).count()
    if return_stats:
        meta = result.meta
        stats = dict(
            vertices_pruned=meta["vertices_pruned"],
            prune_fraction=meta["prune_fraction"],
            edges_after=meta["edges_after"],
            edges_before=meta["edges_before"],
            num_embeddings=meta["num_embeddings"],
        )
        return result.count, stats
    return result.count


def subgraph_match_triangle(
    g: Graph,
    labels: np.ndarray,
    query_labels: Tuple[int, int, int],
    *,
    backend: str = "jnp",
    interpret: Optional[bool] = None,
) -> int:
    """Count embeddings of a *labeled* triangle query (the generality the
    paper highlights for the SM formulation: 'find the embeddings of triangles
    with certain label patterns').

    ``interpret=None`` resolves from the platform (``resolve_interpret``).

    Returns the number of ordered embeddings (u,v,w) with labels matching
    (q0,q1,q2) and {u,v},{v,w},{u,w} ∈ E.
    """
    interpret = resolve_interpret(interpret, lane="subgraph", backend=backend)
    labels = np.asarray(labels)
    q0, q1, q2 = query_labels
    # candidate vertices: label in query labels, degree ≥ 2, 2-core
    cand = np.isin(labels, list(query_labels))
    src = np.repeat(np.arange(g.n, dtype=np.int32), g.degrees)
    dst = g.col_idx
    if g.m_directed == 0:
        return 0
    alive = np.asarray(
        _two_core_peel(jnp.asarray(src), jnp.asarray(dst), jnp.asarray(cand), n=g.n)
    )
    sub, old_ids = induced_subgraph(g, alive)
    if sub.m_directed == 0:
        return 0
    sl = labels[old_ids]
    # candidate edges for query edge (q0,q1); join rule: w labeled q2
    s_src = np.repeat(np.arange(sub.n, dtype=np.int32), sub.degrees)
    s_dst = sub.col_idx
    e_keep = (sl[s_src] == q0) & (sl[s_dst] == q1)
    if not e_keep.any():
        return 0
    from repro.graphs.formats import bucket_edges_by_degree, csr_to_padded_neighbors
    from repro.core.engine import get_executable, resolve_strategy

    # restrict intersected neighbor ids to label-q2 vertices by remapping
    # non-q2 neighbors to a sentinel on the u side only (so they never match)
    buckets = bucket_edges_by_degree(s_src[e_keep], s_dst[e_keep], sub.degrees)
    total = 0
    q2_ok = sl == q2
    for b in buckets:
        nbrs = csr_to_padded_neighbors(sub, pad_to=b["width"], fill=sub.n)
        u_lists = nbrs[b["src"]].copy()
        v_lists = nbrs[b["dst"]].copy()
        valid = (u_lists < sub.n) & q2_ok[np.clip(u_lists, 0, sub.n - 1)]
        u_lists[~valid] = sub.n
        v_lists[v_lists == sub.n] = sub.n + 1
        # same per-bucket dispatch as the unlabeled lanes (id range covers
        # real ids plus the n / n+1 sentinels)
        strat, bits = resolve_strategy(b["width"], sub.n + 2, backend=backend)
        run = get_executable(
            "intersection", backend, interpret, tuple(u_lists.shape),
            strategy=strat, bitmap_bits=bits,
        )
        total += int(run(jnp.asarray(u_lists), jnp.asarray(v_lists)))
    return total
