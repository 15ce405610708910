"""All three TC formulations must agree exactly with the oracle.

Deliberately exercises the DEPRECATED one-shot shims (the facade equivalents
live in tests/test_api.py): the shims must keep returning unchanged values
while they exist."""

import numpy as np
import pytest

from repro.graphs import (
    complete_graph, erdos_renyi_graph, grid_graph, path_graph, rmat_graph,
    star_graph, watts_strogatz_graph,
)
from repro.core import (
    triangle_count_intersection, triangle_count_matrix,
    triangle_count_subgraph, triangle_count_scipy, triangle_count_brute,
    triangle_count_forward_cpu,
)

GRAPHS = [
    complete_graph(4),
    complete_graph(9),
    star_graph(40),
    path_graph(40),
    grid_graph(10, seed=0),
    grid_graph(8, diagonals=False, spur_fraction=0.0),
    rmat_graph(8, 8, seed=1),
    rmat_graph(9, 4, seed=2),
    erdos_renyi_graph(300, 10.0, seed=3),
    watts_strogatz_graph(200, 8, 0.2, seed=4),
]


@pytest.mark.parametrize("g", GRAPHS, ids=lambda g: g.name)
def test_intersection_matches_oracle(g):
    assert triangle_count_intersection(g) == triangle_count_scipy(g)


@pytest.mark.parametrize("g", GRAPHS, ids=lambda g: g.name)
def test_intersection_full_variant(g):
    assert triangle_count_intersection(g, variant="full") == \
        triangle_count_scipy(g)


@pytest.mark.parametrize("g", GRAPHS, ids=lambda g: g.name)
def test_matrix_matches_oracle(g):
    assert triangle_count_matrix(g, block=32) == triangle_count_scipy(g)


@pytest.mark.parametrize("g", GRAPHS, ids=lambda g: g.name)
def test_subgraph_matches_oracle(g):
    assert triangle_count_subgraph(g) == triangle_count_scipy(g)


def test_closed_forms():
    for n in (3, 5, 8, 12):
        expect = n * (n - 1) * (n - 2) // 6
        assert triangle_count_intersection(complete_graph(n)) == expect
    assert triangle_count_matrix(star_graph(100), block=32) == 0
    assert triangle_count_subgraph(path_graph(100)) == 0


def test_matrix_without_permutation():
    g = rmat_graph(8, 8, seed=5)
    truth = triangle_count_scipy(g)
    assert triangle_count_matrix(g, block=32, permute=False) == truth
    assert triangle_count_matrix(g, block=64, permute=True) == truth


def test_cpu_forward_baseline_agrees():
    g = rmat_graph(7, 6, seed=6)
    assert triangle_count_forward_cpu(g) == triangle_count_scipy(g)


def test_brute_force_tiny():
    g = complete_graph(6)
    assert triangle_count_brute(g) == 20


def test_subgraph_prune_stats_mesh_graph():
    """The paper's claim: mesh-like graphs have many leaves the SM filter
    removes (road-like spur fraction ⇒ large prune)."""
    g = grid_graph(20, diagonals=True, spur_fraction=0.4, seed=7)
    count, stats = triangle_count_subgraph(g, return_stats=True)
    assert count == triangle_count_scipy(g)
    assert stats["prune_fraction"] > 0.2  # leaf spurs pruned
    assert stats["edges_after"] < stats["edges_before"]
    assert stats["num_embeddings"] == 6 * count


def test_labeled_subgraph_match():
    from repro.core import subgraph_match_triangle
    from repro.graphs.formats import edges_to_csr

    # triangle 0-1-2 labeled (0,1,2) + triangle 3-4-5 labeled (0,0,0)
    g = edges_to_csr(np.array([0, 1, 2, 3, 4, 5]),
                     np.array([1, 2, 0, 4, 5, 3]), n=6)
    labels = np.array([0, 1, 2, 0, 0, 0])
    # ordered embeddings of labeled triangle (0,1,2): exactly one per
    # orientation of the 0-1 edge = 1 (u=0,v=1,w=2)
    assert subgraph_match_triangle(g, labels, (0, 1, 2)) == 1
    assert subgraph_match_triangle(g, labels, (0, 0, 0)) == 6  # all perms
    assert subgraph_match_triangle(g, labels, (2, 2, 2)) == 0
