"""The frozen Kronecker generator still makes the program's R-MAT graphs,
and a graph spec is made by the generator file it names."""

import numpy as np
import pytest

from bench.harness import manifest as manifests


def kronecker(scale, edge_factor, seed):
    return manifests.load().graph(
        {"generator": "kronecker", "scale": scale, "edge_factor": edge_factor},
        seed)


@pytest.mark.parametrize("scale,edge_factor,seed", [
    (6, 16, 0), (8, 8, 12345), (10, 16, 2 ** 31 + 7)])
def test_kronecker_matches_rmat_graph(scale, edge_factor, seed):
    from repro.graphs import rmat_graph

    g = rmat_graph(scale, edge_factor, seed=seed)
    n, row_ptr, col_idx = kronecker(scale, edge_factor, seed)
    assert n == g.n
    np.testing.assert_array_equal(row_ptr, g.row_ptr)
    np.testing.assert_array_equal(col_idx, g.col_idx)
    assert row_ptr.dtype == np.int32 and col_idx.dtype == np.int32


@pytest.mark.parametrize("spec", [
    {"generator": "no-such-generator", "scale": 6},
    {"scale": 6, "edge_factor": 8},
], ids=["unknown", "unnamed"])
def test_graph_spec_without_a_generator_file_is_refused(spec):
    with pytest.raises(manifests.ManifestError):
        manifests.load().graph(spec, 1)
