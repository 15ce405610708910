"""k-truss decomposition + clustering metrics — the paper's motivating
applications of triangle enumeration (§1), all routed through the session.

    PYTHONPATH=src python examples/ktruss.py
"""

from repro.launch.compile_cache import enable_compile_cache
from repro.graphs import rmat_graph, watts_strogatz_graph
from repro.core import TriangleCounter


def main():
    enable_compile_cache()
    for g in (rmat_graph(10, 8, seed=4), watts_strogatz_graph(2000, 8, 0.05)):
        # one session: clustering metrics replay the cached vertex
        # executables, edge_support/k_truss the cached edge executables and
        # the device peel loop — no host-side enumeration anywhere
        tc = TriangleCounter(g)
        cc = tc.clustering_coefficients()
        print(f"\n=== {g.name}: n={g.n} m={g.m_undirected}")
        print(f"  mean clustering coefficient: {cc.mean():.4f} "
              f"(small-world signature: {'yes' if cc.mean() > 0.1 else 'no'})")
        print(f"  transitivity: {tc.transitivity():.4f}")
        _, _, supp = tc.edge_support()
        print(f"  max edge support: {int(supp.max(initial=0))}")
        for k in (3, 4, 5, 6):
            t = tc.k_truss(k)
            print(f"  {k}-truss: {t.m_undirected:7d} edges "
                  f"({100.0 * t.m_undirected / max(g.m_undirected,1):5.1f}%)")
        _, _, trussness = tc.truss_decomposition()
        if trussness.size:
            print(f"  max trussness: {int(trussness.max())}")


if __name__ == "__main__":
    main()
