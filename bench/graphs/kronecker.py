"""``"generator": "kronecker"`` — the Graph500 Kronecker (R-MAT) generator,
frozen with the benchmark.

A copy of ``repro.graphs.rmat_graph`` and the ``edges_to_csr`` clean-up it
calls, kept here so that no change to the program can change the graphs the
benchmark counts. ``bench/tests`` checks that both still agree.

Graph500 draws ``edge_factor * 2**scale`` endpoint pairs, one quadrant
choice per bit with probabilities (a, b, c, d = 1 - a - b - c); the result
is symmetrized, self loops and parallel edges are dropped, and each row is
sorted by neighbour id. Graph500 also permutes the vertex ids; like the
program's generator, this one does not.
"""

from __future__ import annotations

import numpy as np

__all__ = ["generate"]


def generate(seed: int, scale: int, edge_factor: int, a: float = 0.57,
             b: float = 0.19, c: float = 0.19):
    """(n, row_ptr, col_idx) of the cleaned, symmetric Kronecker graph.

    ``row_ptr`` is (n + 1,) int32 and ``col_idx`` (2m,) int32, rows sorted:
    the CSR layout of ``repro.graphs.Graph``.
    """
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = n * edge_factor
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    ab, abc = a + b, a + b + c
    for lvl in range(scale):
        r = rng.random(m)
        right = r >= ab  # c or d quadrant: source bit set
        lower = (r >= a) & (r < ab) | (r >= abc)  # b or d: target bit set
        src |= right.astype(np.int64) << lvl
        dst |= lower.astype(np.int64) << lvl
    keep = src != dst
    src, dst = src[keep], dst[keep]
    key = np.unique(np.concatenate([src * n + dst, dst * n + src]))
    u = (key // n).astype(np.int32)
    v = (key % n).astype(np.int32)
    row_ptr = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(np.bincount(u, minlength=n), out=row_ptr[1:])
    return n, row_ptr, v
