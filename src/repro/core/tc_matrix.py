"""Matrix-multiplication triangle counting (Azad/Buluç/Gilbert via the paper
§3.3/§4.3), reformulated as a *masked block-SpGEMM* for the TPU MXU.

Algorithm 3 of the paper:
  1. permute A by increasing degree,
  2. split A = L + U (strict lower/upper),
  3. B = L·U,  C = A ∘ B,  count = ½·ΣΣ C.

The paper calls cuSPARSE csrgemm and shows the unmasked SpGEMM is the
bottleneck (intermediate B hits global memory; multiplications run where A is
known zero). Here the host builds a *tile schedule* instead
(:func:`repro.core.engine.build_tile_schedule`):

  * A (permuted) is tiled into dense 128×128 blocks (BSR); only nonzero tiles
    exist.
  * For every strict-upper nonzero tile A[I,J] and every K present in both
    block-row I of L and block-col J of U, emit the triple (A[I,J], L[I,K],
    U[K,J]).  Block-level scheduling = the paper's optimization (2) "avoid
    multiplications where A is known to be zero a priori", lifted to tiles.
  * The fused kernel computes sum(A_IJ ∘ (L_IK @ U_KJ)) per triple and never
    materializes L·U — optimizations (1) upper-only and (3) no-global-output.

count = Σ_t partial_t  exactly (each triangle counted once at its min-rank
wedge, which lands in the strict upper triangle after the degree permutation).

Degenerate diagonal tiles (I == J) carry both L and U nonzeros; they are
handled naturally because L/U tiles are built from the strict parts.

This module registers the ``"matrix"`` lane with the algorithm registry; the
front door is ``TriangleCounter(g, CountOptions(algorithm="matrix", ...))``.
The one-shot ``triangle_count_matrix`` below is a deprecated shim kept for
source compatibility.
"""

from __future__ import annotations

from typing import Optional

from repro.graphs.formats import Graph
from repro.core.engine import (
    build_tile_schedule,  # re-export (prep lives in repro.core.prep)
    choose_block,  # re-export
    plan_triangle_count,
)
from repro.core.registry import register_algorithm

__all__ = ["triangle_count_matrix", "build_tile_schedule", "choose_block"]


def _planner(g: Graph, options, *, mesh=None):
    """Registry planner: CountOptions → matrix-lane TrianglePlan."""
    return plan_triangle_count(g, "matrix", **options.plan_kwargs("matrix"))


register_algorithm("matrix", _planner)


def triangle_count_matrix(
    g: Graph,
    *,
    block=128,  # int or "auto" (adaptive — see choose_block)
    permute: bool = True,
    backend: str = "jnp",
    interpret: Optional[bool] = None,
) -> int:
    """Deprecated shim: exact triangle count via fused masked block-SpGEMM.

    Use ``TriangleCounter(g, CountOptions(algorithm="matrix", ...))``
    instead; ``interpret=None`` resolves from the platform
    (``resolve_interpret``). Returns the exact count as a Python int
    (unchanged behavior).
    """
    from repro.core.api import TriangleCounter, warn_deprecated
    from repro.core.options import CountOptions

    warn_deprecated(
        "triangle_count_matrix(g, ...)",
        'TriangleCounter(g, CountOptions(algorithm="matrix", ...)).count()',
    )
    opts = CountOptions(
        algorithm="matrix", block=block, permute=permute, backend=backend,
        interpret=interpret,
    )
    return int(TriangleCounter(g, opts).count())
