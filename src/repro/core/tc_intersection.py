"""Set-intersection (forward-algorithm) triangle counting — the paper's
best-performing method (§3.2/§4.2), adapted to TPU execution.

Pipeline (host numpy prep → JAX compute):

  1. FORM_FILTERED_EDGE_LIST — ``orient_forward`` keeps u→v iff
     rank(u) < rank(v) with rank = (degree, id): the paper's "filter out half
     of the edges by degree order". The oriented graph's rows ARE the induced
     subgraph's neighbor lists, so the "reform the induced subgraph" step
     (removes a further ~⅔ of scanned work) falls out of the same structure.
  2. Degree-class bucketing — the TPU replacement for TwoSmall/TwoLarge
     dynamic grouping: each bucket is a statically-shaped (E_b, W_b) problem.
  3. COMPUTE_INTERSECTION — per bucket, one batched intersection kernel call
     fused with its reduction in a single traced computation.

``variant="full"`` reproduces the paper's tc-intersection-full ablation
(intersect over ALL directed edges with full neighbor lists; each triangle is
then found 6×), so benchmarks can measure exactly what the filtering buys.

This module registers the ``"intersection"`` lane with the algorithm registry
(:mod:`repro.core.registry`); the front door is
``TriangleCounter(g, CountOptions(algorithm="intersection", ...))``. The
one-shot ``triangle_count_intersection`` below is a deprecated shim kept for
source compatibility.
"""

from __future__ import annotations

from typing import Optional

from repro.graphs.formats import Graph
from repro.core.engine import (
    DEFAULT_WIDTHS,
    plan_triangle_count,
    prepare_intersection_buckets,  # re-export (prep lives in repro.core.prep;
    # the plan stage runs the device-resident pipeline by default)
)
from repro.core.registry import register_algorithm

__all__ = ["triangle_count_intersection", "prepare_intersection_buckets"]


def _planner(g: Graph, options, *, mesh=None):
    """Registry planner: CountOptions → intersection-lane TrianglePlan."""
    return plan_triangle_count(
        g, "intersection", **options.plan_kwargs("intersection")
    )


register_algorithm("intersection", _planner)


def triangle_count_intersection(
    g: Graph,
    *,
    variant: str = "filtered",
    backend: str = "jnp",
    interpret: Optional[bool] = None,
    widths=DEFAULT_WIDTHS,
    strategy: str = "auto",
) -> int:
    """Deprecated shim: exact triangle count via batched set intersection.

    Use ``TriangleCounter(g, CountOptions(algorithm="intersection", ...))``
    instead. Keyword arguments map 1:1 onto ``CountOptions`` fields
    (``interpret=None`` resolves from the platform, ``resolve_interpret``).

    Returns:
      The exact triangle count as a Python int (unchanged behavior).
    """
    from repro.core.api import TriangleCounter, warn_deprecated
    from repro.core.options import CountOptions

    warn_deprecated(
        "triangle_count_intersection(g, ...)",
        'TriangleCounter(g, CountOptions(algorithm="intersection", ...)).count()',
    )
    opts = CountOptions(
        algorithm="intersection", variant=variant, backend=backend,
        interpret=interpret, widths=tuple(widths), strategy=strategy,
    )
    return int(TriangleCounter(g, opts).count())
