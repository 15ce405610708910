"""The plain per-vertex reference: every vertex's triangles and its local
clustering coefficient, with numpy and scipy alone.

Nothing here imports the program. The graph is oriented as in
``bench.harness.reference``: the forward DAG L keeps u->v where (deg u, u) <
(deg v, v). A triangle a < b < c in that order is the three forward edges
a->b, a->c and b->c, and it is found once from each of its vertices:

* lowest, a: row a of (L @ L) * L (the wedge a->b->c closed by a->c);
* highest, c: column c of the same product;
* middle, b: row b of (L.T @ L) * L (the wedge b<-a->c closed by b->c).

``triangles`` sums the three in int64. Each product runs over blocks of rows,
in a pool of threads (scipy's sparse products release the GIL), so that the
intermediates stay small at any scale.

``lcc`` is LDBC Graphalytics' LCC (arXiv 2011.15028, section 2.3) in
float64: 2 t(v) / (d(v) (d(v) - 1)), and 0 where d(v) < 2.
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench.harness.reference import merge_bytes, oriented

__all__ = ["lcc", "triangles", "vertex_bytes"]

_BLOCK_ROWS = 1 << 12


def triangles(n: int, row_ptr: np.ndarray, col_idx: np.ndarray,
              workers: int | None = None) -> np.ndarray:
    """(n,) int64: t(v), the triangles that hold each vertex."""
    lo = oriented(n, row_ptr, col_idx)
    lo_t = lo.T.tocsr()
    out = np.zeros(n, dtype=np.int64)
    lock = threading.Lock()

    def block(r0: int) -> None:
        blk = lo[r0:r0 + _BLOCK_ROWS]
        low = (blk @ lo).multiply(blk).tocsr()
        mid = (lo_t[r0:r0 + _BLOCK_ROWS] @ lo).multiply(blk)
        rows = np.asarray(low.sum(axis=1) + mid.sum(axis=1),
                          np.int64).ravel()
        high = np.bincount(low.indices, weights=low.data, minlength=n)
        with lock:
            out[r0:r0 + rows.shape[0]] += rows
            out[:] += high.astype(np.int64)

    with ThreadPoolExecutor(max_workers=workers or os.cpu_count()) as pool:
        list(pool.map(block, range(0, n, _BLOCK_ROWS)))
    return out


def lcc(t: np.ndarray, degrees: np.ndarray, dtype=np.float64) -> np.ndarray:
    """Graphalytics LCC of counts ``t`` at undirected ``degrees``, computed
    in ``dtype`` (float64 as the benchmark defines it)."""
    t = np.asarray(t).astype(dtype)
    d = np.asarray(degrees).astype(dtype)
    denom = d * (d - dtype(1))
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where(denom > 0, dtype(2) * t / denom, dtype(0))


def vertex_bytes(n: int, row_ptr: np.ndarray, col_idx: np.ndarray) -> int:
    """HBM bytes one per-vertex pass must move: the merge intersection's
    reads (``reference.merge_bytes``) plus the (n,) int32 counts written
    once. The scatter's updates cost none: a kernel may keep the counts on
    chip."""
    return merge_bytes(n, row_ptr, col_idx) + 4 * int(n)
