"""Multi-device triangle counting (the paper's technique on the production
distribution substrate). Uses 8 placeholder CPU devices to demonstrate the
same shard_map decomposition the 512-chip dry-run lowers.

    PYTHONPATH=src python examples/distributed_tc.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time  # noqa: E402

import jax  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.graphs import rmat_graph  # noqa: E402
from repro.core import (  # noqa: E402
    CountOptions, TriangleCounter, triangle_count_scipy,
)


def main():
    enable_compile_cache()
    print(f"devices: {jax.device_count()} × {jax.devices()[0].platform}")
    mesh = make_mesh((4, 2), ("data", "model"))
    g = rmat_graph(12, 8, seed=3)
    truth = triangle_count_scipy(g)
    print(f"graph {g.name}: n={g.n} m={g.m_undirected} truth={truth}")
    # the distributed lanes go through the same front door — select them by
    # name in CountOptions and hand the mesh to the session
    for label, opts in [
        ("distributed masked block-SpGEMM",
         CountOptions(algorithm="matrix_distributed", block=64)),
        ("distributed forward-intersection",
         CountOptions(algorithm="intersection_distributed")),
    ]:
        t0 = time.perf_counter()
        res = TriangleCounter(g, opts, mesh=mesh).count()
        dt = time.perf_counter() - t0
        status = "OK" if res == truth else "MISMATCH"
        print(f"  [{status}] {label}: {res.count}  ({dt*1e3:.1f} ms, "
              f"{mesh.devices.size} devices)")


if __name__ == "__main__":
    main()
