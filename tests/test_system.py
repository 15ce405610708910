"""End-to-end behaviour tests for the paper's system."""

from repro.graphs import load_dataset
from repro.core import CountOptions, TriangleCounter, triangle_count_scipy


def test_end_to_end_all_methods_on_datasets():
    """The paper's core experiment at smoke scale: every lane through the
    front door, both topology classes, exact agreement — plus the auto
    cost model's pick."""
    for name in ("tiny-rmat", "tiny-grid"):
        g = load_dataset(name)
        truth = triangle_count_scipy(g)
        for opts in (CountOptions(algorithm="intersection"),
                     CountOptions(algorithm="matrix"),
                     CountOptions(algorithm="subgraph")):
            assert TriangleCounter(g, opts).count() == truth, (name, opts)
        auto = TriangleCounter(g).count()
        assert auto == truth
        assert auto.algorithm in ("intersection", "matrix", "subgraph")
