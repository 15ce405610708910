"""Quickstart: one front door (`TriangleCounter` + `CountOptions`) over the
paper's three formulations — compare the lanes, let `algorithm="auto"` pick,
then amortize repeated counts through the session's cached plan.

    PYTHONPATH=src python examples/quickstart.py [--scale 10]
"""

import argparse
import time

from repro.launch.compile_cache import enable_compile_cache
from repro.graphs import complete_graph, grid_graph, rmat_graph
from repro.core import (
    CountOptions,
    TriangleCounter,
    triangle_count_scipy,
)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=10)
    args = ap.parse_args()

    # three topology classes, three different winners: skewed R-MAT (the
    # intersection lane), a mesh-like grid (the SM lane — 2-core peel
    # collapses the spurs), and a small dense graph (the matrix lane fills
    # whole MXU tiles; its wide bucket also goes to the bitmap core)
    graphs = (rmat_graph(args.scale, 8, seed=1),
              grid_graph(40, spur_fraction=0.3, seed=2),
              complete_graph(100))
    for g in graphs:
        print(f"\n=== {g.name}: n={g.n} m={g.m_undirected} "
              f"max_deg={g.max_degree} SSD={g.sum_square_degrees}")
        truth = triangle_count_scipy(g)

        # every lane through the same front door, one options bag each
        for opts in (CountOptions(algorithm="intersection"),
                     CountOptions(algorithm="matrix", block=64),
                     CountOptions(algorithm="subgraph")):
            t0 = time.perf_counter()
            res = TriangleCounter(g, opts).count()
            dt = time.perf_counter() - t0
            flag = "OK " if res == truth else "BAD"
            print(f"  [{flag}] algorithm={res.algorithm:13s} "
                  f"{res.count:10d}  ({dt*1e3:7.1f} ms)")

        # the cross-lane cost model: CountOptions() means algorithm="auto";
        # CountResult reports the lane it chose and the per-bucket
        # set-intersection strategies the plan stage resolved
        tc = TriangleCounter(g)  # algorithm="auto"
        res = tc.count()
        assert res == truth
        picks = ", ".join(f"w{w}:{s}" for w, s in res.bucket_strategies or [])
        print(f"  auto chose: {res.algorithm}"
              + (f"  (per-bucket dispatch: {picks})" if picks else ""))

        # the session owns ONE plan: replays are device-only
        t0 = time.perf_counter()
        repeats = 5
        for _ in range(repeats):
            assert tc.count() == truth
        replay_ms = (time.perf_counter() - t0) * 1e3 / repeats
        print(f"  session replay: prep {res.prep_seconds*1e3:.1f} ms once, "
              f"then {replay_ms:.1f} ms per cached count()")

        # per-vertex analysis rides the same cached plan (no host-side
        # re-enumeration): clustering + transitivity from one device replay
        cc = tc.clustering_coefficients()
        print(f"  analysis: mean clustering coeff {cc.mean():.4f}; "
              f"transitivity {tc.transitivity():.4f}")

    # batches share the executable cache: same options, many graphs
    batch = [rmat_graph(args.scale - 2, 6, seed=s) for s in range(4)]
    t0 = time.perf_counter()
    results = TriangleCounter(batch[0]).count_many(batch)
    dt = time.perf_counter() - t0
    print(f"\ncount_many over {len(batch)} R-MAT graphs: "
          f"{[r.count for r in results]} ({dt*1e3:.1f} ms; "
          f"same-shaped plans reuse cached executables)")


if __name__ == "__main__":
    main()
