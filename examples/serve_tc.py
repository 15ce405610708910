"""Concurrent triangle-counting service: a mixed multi-tenant burst through
``repro.serve.TriangleService`` — coalesced count requests, a per-vertex
analysis request, a dynamic-session update stream, and a deadline-shed
demonstration, ending with the latency/coalesce/shed summary.

    PYTHONPATH=src python examples/serve_tc.py --tenants 4 --requests 32
"""

import argparse
import time

from repro.launch.compile_cache import enable_compile_cache
from repro.core import CountOptions
from repro.graphs import rmat_graph
from repro.serve import RequestShed, ServeConfig, TriangleService


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--pool", type=int, default=8,
                    help="distinct graphs the tenants request")
    ap.add_argument("--scale", type=int, default=7)
    ap.add_argument("--window-ms", type=float, default=5.0)
    ap.add_argument("--max-batch", type=int, default=8)
    args = ap.parse_args()

    pool = [rmat_graph(args.scale, 6, seed=100 + i, name=f"g{i}")
            for i in range(args.pool)]
    opts = CountOptions(algorithm="intersection")
    svc = TriangleService(opts, config=ServeConfig(
        max_queue_depth=max(64, 2 * args.requests),
        batch_window_ms=args.window_ms, max_batch=args.max_batch))

    t0 = time.perf_counter()
    warm = svc.warmup(pool)
    print(f"warmup: {warm['batchable']} graphs prepped, "
          f"{warm['layouts']} layout(s), {warm['seconds']:.2f}s")

    with svc:
        # the mixed burst: coalescible counts from every tenant...
        futs = [svc.submit("count", pool[i % args.pool],
                           tenant=f"tenant{i % args.tenants}")
                for i in range(args.requests)]
        # ...plus a per-vertex analysis request (single execution)...
        vfut = svc.submit("vertex", pool[0], tenant="tenant0")
        # ...and a dynamic-session update stream (bypasses coalescing)
        handle = svc.open_dynamic_session(pool[1], tenant="tenant1")
        ufut = svc.submit("update", handle=handle,
                          updates=[(0, 1), (1, 2), (0, 2)])

        results = [f.result(timeout=60) for f in futs]
        tri = vfut.result(timeout=60).value
        upd = ufut.result(timeout=60)
        wall = time.perf_counter() - t0

        # a deliberately impossible deadline to show typed load-shedding
        try:
            svc.submit("count", pool[0], deadline_ms=1e-3).result(timeout=60)
            shed_demo = "not shed (machine too fast!)"
        except RequestShed as e:
            shed_demo = f"shed with reason {e.reason!r}"

    counts = {r.tenant: r.count for r in results}
    print(f"{args.requests} counts from {args.tenants} tenants "
          f"over {args.pool} graphs in {wall:.2f}s "
          f"(batch sizes seen: {sorted({r.batch_size for r in results})})")
    print(f"sample counts per tenant: {counts}")
    print(f"per-vertex analysis: n={len(tri)}, total membership "
          f"{int(tri.sum())} (= 3x triangles)")
    print(f"dynamic update batch -> count {upd.count} "
          f"(algorithm={upd.algorithm})")
    print(f"1ms-deadline request: {shed_demo}")

    snap = svc.snapshot()
    lat = snap["latency"]["total"]
    print(f"latency: p50 {lat['p50_ms']:.1f}ms  p99 {lat['p99_ms']:.1f}ms  "
          f"mean {lat['mean_ms']:.1f}ms over {lat['count']} requests")
    print(f"coalesce factor {snap['coalesce_factor']:.2f}  "
          f"shed {snap['counters'].get('shed', 0)}  "
          f"engine cache: {snap['engine_cache']['hits']} hits / "
          f"{snap['engine_cache']['misses']} misses / "
          f"{snap['engine_cache']['evictions']} evictions")


if __name__ == "__main__":
    main()
