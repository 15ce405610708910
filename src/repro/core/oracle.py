"""Ground-truth triangle counters used only by tests and benchmarks.

``triangle_count_scipy`` doubles as the sequential CPU baseline in the
Fig. 5 analogue benchmark (the paper normalizes to Schank & Wagner's forward
algorithm on one core). It multiplies over the forward-oriented DAG: with
L the degree-ordered orientation, ``sum(L ∘ (L @ L))`` counts each triangle
once at its rank-ordered path u→v→w closed by u→w — the forward algorithm's
O(m^1.5) work expressed through a mature sequential sparse kernel, where
trace(A³)/6 over the undirected A would cost O(Σd²).
"""

from __future__ import annotations

import numpy as np

from repro.graphs.formats import Graph, orient_forward

__all__ = ["triangle_count_scipy", "triangle_count_brute", "triangle_count_forward_cpu"]


def triangle_count_scipy(g: Graph) -> int:
    import scipy.sparse as sp

    # orient here rather than through the program's own prep helpers, so a
    # fault there cannot hide behind an oracle that shares it
    a = g.to_scipy().tocoo()
    deg = np.bincount(a.row, minlength=g.n)
    r, c = a.row, a.col
    keep = (deg[r] < deg[c]) | ((deg[r] == deg[c]) & (r < c))
    lo = sp.csr_matrix((np.ones(int(keep.sum()), np.int64),  # no overflow
                        (r[keep], c[keep])), shape=(g.n, g.n))
    return int((lo @ lo).multiply(lo).sum())


def triangle_count_brute(g: Graph) -> int:
    """O(n^3) — tiny fixtures only."""
    a = g.to_scipy().toarray().astype(bool)
    n = g.n
    count = 0
    for i in range(n):
        for j in range(i + 1, n):
            if a[i, j]:
                count += int((a[i] & a[j])[j + 1 :].sum())
    return count


def triangle_count_forward_cpu(g: Graph) -> int:
    """Sequential forward algorithm (Schank & Wagner) in pure numpy —
    the paper's CPU baseline implementation."""
    dag = orient_forward(g)
    count = 0
    rp, ci = dag.row_ptr, dag.col_idx
    for u in range(g.n):
        nu = ci[rp[u] : rp[u + 1]]
        for v in nu:
            nv = ci[rp[v] : rp[v + 1]]
            count += np.intersect1d(nu, nv, assume_unique=True).shape[0]
    return int(count)
