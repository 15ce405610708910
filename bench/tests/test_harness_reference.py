"""The plain reference on graphs whose counts are known in closed form, the
merge-bytes function on a graph counted by hand, and the controls."""

from math import comb

import numpy as np
import pytest

from bench.harness import manifest as manifests, reference


def kronecker(scale, edge_factor, seed):
    return manifests.load().graph(
        {"generator": "kronecker", "scale": scale, "edge_factor": edge_factor},
        seed)


def csr(n, edges):
    """Symmetric sorted CSR of an undirected edge list."""
    pairs = sorted({(u, v) for a, b in edges for u, v in ((a, b), (b, a))})
    row_ptr = np.zeros(n + 1, np.int32)
    np.cumsum(np.bincount([u for u, _ in pairs], minlength=n),
              out=row_ptr[1:])
    return n, row_ptr, np.array([v for _, v in pairs], np.int32)


@pytest.mark.parametrize("n", [3, 4, 7, 20, 45])
def test_complete_graph(n):
    g = csr(n, [(u, v) for u in range(n) for v in range(u + 1, n)])
    assert reference.count(*g) == comb(n, 3)


@pytest.mark.parametrize("n", [2, 10, 300])
def test_star_and_cycle_have_no_triangles(n):
    assert reference.count(*csr(n + 1, [(0, v) for v in range(1, n + 1)])) \
        == 0
    if n >= 4:
        assert reference.count(*csr(n, [(v, (v + 1) % n)
                                        for v in range(n)])) == 0


def test_reference_agrees_with_brute_force_on_rmat():
    n, row_ptr, col_idx = kronecker(7, 8, 3)
    adj = np.zeros((n, n), bool)
    for u in range(n):
        adj[u, col_idx[row_ptr[u]:row_ptr[u + 1]]] = True
    a = adj.astype(np.int64)
    assert reference.count(n, row_ptr, col_idx) == int(np.trace(a @ a @ a)
                                                       // 6)


def test_merge_bytes_by_hand():
    # triangle 0-1-2 plus a pendant 2-3. Degrees: 0:2, 1:2, 2:3, 3:1.
    # Order by (degree, id): 3 < 0 < 1 < 2. Forward edges: 3->2, 0->1,
    # 0->2, 1->2, so d+ = {0: 2, 1: 1, 2: 0, 3: 1}.
    # Sum over u->v of d+(u) + d+(v): (1+0) + (2+1) + (2+0) + (1+0) = 7.
    g = csr(4, [(0, 1), (1, 2), (0, 2), (2, 3)])
    assert reference.merge_bytes(*g) == 4 * 7
    assert reference.count(*g) == 1


def test_float32_control_breaks_exactness_past_2_pow_24():
    # the g500 control at the smallest Kronecker scale whose total passes
    # 2**24 (scale 17: ~3.6e7 triangles); the cell itself is scale 18
    rows = reference.row_counts(*kronecker(17, 16, 7))
    assert int(rows.sum()) > 2 ** 24
    assert reference.control_count(rows, "float32") != int(rows.sum())


def test_controls_are_exact_below_their_range():
    rows = np.array([3, 5, 7, 11], np.int64)
    assert reference.control_count(rows, "float32") == 26
    assert reference.control_count(rows, "bfloat16") == 26
    assert reference.control_count(np.full(40, 13), "bfloat16") != 520
