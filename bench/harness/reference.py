"""The plain reference: exact triangle counts with numpy and scipy alone.

Nothing here imports the program. The graph is oriented by (degree, id),
the forward algorithm's order, and every triangle is counted once at its
lowest-ranked vertex u as the wedge u->v->w closed by u->w:

    rows[u] = sum over forward edges u->v of |N+(u) & N+(v)|
            = row u of (L @ L) * L, summed.

The product runs over blocks of rows, so that its intermediate stays small
at any scale. ``count`` sums the rows in int64. ``control_count`` sums the
same rows in a lower-precision float, one after another, the way a device
reduction that accumulates in that type would: it is exact only while the
running total stays within the type's integer range.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

__all__ = ["control_count", "count", "merge_bytes", "oriented", "row_counts"]

_BLOCK_ROWS = 1 << 14


def oriented(n: int, row_ptr: np.ndarray, col_idx: np.ndarray):
    """The forward DAG L as a CSR matrix of int64 ones: u->v kept where
    (deg u, u) < (deg v, v)."""
    row_ptr = np.asarray(row_ptr, dtype=np.int64)
    col_idx = np.asarray(col_idx, dtype=np.int64)
    deg = np.diff(row_ptr)
    src = np.repeat(np.arange(n, dtype=np.int64), deg)
    keep = (deg[src] < deg[col_idx]) | ((deg[src] == deg[col_idx])
                                        & (src < col_idx))
    return sp.csr_matrix((np.ones(int(keep.sum()), np.int64),
                          (src[keep], col_idx[keep])), shape=(n, n))


def row_counts(n: int, row_ptr: np.ndarray, col_idx: np.ndarray) -> np.ndarray:
    """(n,) int64: the triangles whose lowest-ranked vertex is each row."""
    lo = oriented(n, row_ptr, col_idx)
    out = np.zeros(n, dtype=np.int64)
    for r0 in range(0, n, _BLOCK_ROWS):
        blk = lo[r0:r0 + _BLOCK_ROWS]
        out[r0:r0 + blk.shape[0]] = np.asarray(
            (blk @ lo).multiply(blk).sum(axis=1), dtype=np.int64).ravel()
    return out


def count(n: int, row_ptr: np.ndarray, col_idx: np.ndarray) -> int:
    """The exact triangle count."""
    return int(row_counts(n, row_ptr, col_idx).sum())


def control_count(rows: np.ndarray, dtype: str) -> int:
    """The total of ``rows`` accumulated one row at a time in ``dtype``
    ("float32" or "bfloat16"): the reference at a precision below exact."""
    import ml_dtypes  # ships with JAX

    t = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}[dtype]
    acc = np.cumsum(np.asarray(rows).astype(t), dtype=t)
    return int(acc[-1]) if acc.size else 0


def merge_bytes(n: int, row_ptr: np.ndarray, col_idx: np.ndarray) -> int:
    """Bytes a merge intersection of int32 lists must read for one count:
    4 * sum over forward edges u->v of (d+(u) + d+(v))."""
    lo = oriented(n, row_ptr, col_idx)
    dplus = np.diff(lo.indptr)
    src = np.repeat(np.arange(n), dplus)
    return int(4 * (dplus[src].sum() + dplus[lo.indices].sum()))
