#!/usr/bin/env python3
"""On-chip smoke test: the counting engine's main path on one TPU chip.

    python3 chip_smoke.py              # one chip: every phase below
    python3 chip_smoke.py --chips 4    # the sharded lanes on a 4-chip mesh

Phases (one process; every count is checked, and a failed check raises):

* main count — ``TriangleCounter(g).count()`` with default options on the
  Graph500 R-MAT graph ``rmat_graph(--scale, 16)``, then a warm replay, both
  checked against the oriented scipy oracle;
* Pallas kernels — each compiled Pallas core (broadcast, probe and bitmap
  intersection, the masked-SpGEMM matrix kernel, the hash probe) counts a
  graph where it is legal, checked against the ``jnp`` count of that graph;
* strategy — the ``jnp`` probe and broadcast cores, counts and masks, time
  the same wide rows (W = 128 and 512), the measurement behind the TPU
  rules of ``choose_strategy`` and ``choose_mask_strategy``;
* per-vertex — ``TriangleCounter.triangles_per_vertex`` and
  ``clustering_coefficients`` on ``rmat_graph(14, 16)``, checked against
  the host's scipy counts (diagonal of A³ over 2); then one W=512 chunk at
  the LCC cell's shapes, the per-vertex executable timed against the
  whole-mask scatter it replaced (checked equal), and its grouping alone;
* serving — a ``TriangleService`` answers a few ``count`` requests, each
  checked against the facade;
* dynamic update — one ``DynamicTriangleCounter.apply_updates`` batch at
  n > 46,340 (the int64 wide-key path) and a ``recount()``.

``--chips 4`` runs only the sharded lanes (``intersection_distributed`` and
``matrix_distributed``) on a mesh of four chips and compares them with the
one-chip count and the oracle.

Progress and the numbers worth keeping go to stdout; the last line is one
JSON object, ``{"ok": true, "device": {...}}``. Without a TPU the script
exits non-zero before any phase and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}


def log(msg: str) -> None:
    print(msg, flush=True)


def check(name: str, got: int, want: int) -> None:
    if int(got) != int(want):
        raise AssertionError(f"{name}: count {int(got)} != expected "
                             f"{int(want)}")


def device_bytes() -> list:
    """Bytes in use on each device, as the runtime reports them."""
    return [int((d.memory_stats() or {}).get("bytes_in_use", -1))
            for d in jax.devices()]


def oracle(g) -> int:
    from repro.core import triangle_count_scipy
    t0 = time.perf_counter()
    want = triangle_count_scipy(g)
    log(f"  oracle: {want} triangles in {time.perf_counter() - t0:.3f}s "
        f"(host scipy, oriented)")
    return want


def phase_main(scale: int, seed: int) -> int:
    from repro.core import TriangleCounter
    from repro.graphs import rmat_graph

    log(f"[main] rmat_graph({scale}, 16, seed={seed})")
    t0 = time.perf_counter()
    g = rmat_graph(scale, 16, seed=seed)
    log(f"  graph: n={g.n} m={g.m_undirected} max_degree={g.max_degree} "
        f"generated in {time.perf_counter() - t0:.3f}s")
    want = oracle(g)
    tc = TriangleCounter(g)
    t0 = time.perf_counter()
    plan = tc.plan
    prep = time.perf_counter() - t0
    log(f"  planned {tc.algorithm} in {prep:.3f}s")
    first = tc.count()
    log(f"  first count {first.exec_seconds:.3f}s")
    replay = tc.count()
    check("main first count", first.count, want)
    check("main replay", replay.count, want)
    meta = first.meta
    log(f"  auto lane: {tc.algorithm}; buckets {meta.get('bucket_shapes')} "
        f"strategies {meta.get('bucket_strategies')}")
    log(f"  streamed buckets: {meta.get('tiled_buckets')} under "
        f"max_device_bytes={meta.get('max_device_bytes')}")
    log(f"  prep {prep:.3f}s (plan {plan.prep_seconds:.3f}s), first count "
        f"{first.exec_seconds:.3f}s, replay {replay.exec_seconds:.3f}s")
    log(f"  count {first.count} == oracle; device bytes in use "
        f"{device_bytes()}")
    return first.count


def phase_pallas(scale: int, seed: int) -> None:
    from repro.core import CountOptions, TriangleCounter
    from repro.graphs import rmat_graph

    g = rmat_graph(scale, 16, seed=seed + 1)
    log(f"[pallas] rmat_graph({scale}, 16, seed={seed + 1}): n={g.n} "
        f"m={g.m_undirected}")
    want = TriangleCounter(g, backend="jnp").count().count
    check("pallas-phase jnp count", want, oracle(g))
    cases = [("intersection", dict(strategy="broadcast")),
             ("intersection", dict(strategy="probe")),
             ("intersection", dict(strategy="bitmap")),
             ("matrix", {}),
             ("hash", {})]
    for lane, extra in cases:
        opts = CountOptions(algorithm=lane, backend="pallas", **extra)
        tc = TriangleCounter(g, opts)
        first = tc.count()
        replay = tc.count()
        if tc.plan.interpret:
            raise AssertionError(f"{lane} {extra}: Pallas ran interpreted")
        check(f"pallas {lane} {extra}", first.count, want)
        check(f"pallas {lane} {extra} replay", replay.count, want)
        log(f"  {lane:12s} {extra.get('strategy', ''):9s} compiled: "
            f"{first.count} == jnp; first {first.exec_seconds:.3f}s replay "
            f"{replay.exec_seconds:.3f}s")


def phase_strategy(seed: int, rows: int = 4096, reps: int = 5) -> None:
    """The rules behind ``choose_strategy`` and ``choose_mask_strategy``
    sending wide ``jnp`` buckets to broadcast on a TPU: both ``jnp`` cores,
    as counts and as masks, on the same random sorted rows, checked equal,
    best of ``reps`` warm runs each."""
    from repro.kernels.intersect.ops import intersect_counts, intersect_matches

    def counts(a, b, s):
        return intersect_counts(a, b, strategy=s, backend="jnp").sum()

    def masks(a, b, s):  # one number per run: the matches, weighted by place
        m = intersect_matches(a, b, strategy=s)
        return (m * jnp.arange(1, a.shape[1] + 1, dtype=jnp.int32)).sum()

    rng = np.random.default_rng(seed)
    log(f"[strategy] jnp probe vs jnp broadcast, {rows} rows, best of {reps}")
    for width in (128, 512):
        # W distinct ids per row from [0, 4W): about W/4 matches per row
        u, v = (jnp.asarray(np.sort(rng.random((rows, 4 * width))
                                    .argsort(axis=1)[:, :width], axis=1)
                            .astype(np.int32)) for _ in range(2))
        for core, body in (("count", counts), ("mask", masks)):
            best, got = {}, {}
            for strategy in ("probe", "broadcast"):
                fn = jax.jit(lambda a, b, s=strategy: body(a, b, s))
                got[strategy] = int(fn(u, v))
                times = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    fn(u, v).block_until_ready()
                    times.append(time.perf_counter() - t0)
                best[strategy] = min(times)
            check(f"strategy {core} W={width} probe", got["probe"],
                  got["broadcast"])
            log(f"  {core} W={width}: probe {best['probe']:.6f}s broadcast "
                f"{best['broadcast']:.6f}s ratio "
                f"{best['probe'] / best['broadcast']:.1f}x; {got['probe']} "
                f"both")


def phase_vertex(scale: int, seed: int) -> None:
    """Per-vertex counts and clustering coefficients through the session's
    plan, against the host's scipy counts, and the pass's calls."""
    import scipy.sparse as sp

    from repro.core import TriangleCounter, tracing
    from repro.graphs import rmat_graph

    g = rmat_graph(scale, 16, seed=seed + 2)
    log(f"[vertex] rmat_graph({scale}, 16, seed={seed + 2}): n={g.n} "
        f"m={g.m_undirected}")
    a = sp.csr_matrix((np.ones(g.col_idx.shape[0], np.int64), g.col_idx,
                       g.row_ptr), shape=(g.n, g.n))
    want = np.asarray((a @ a).multiply(a).sum(axis=1)).ravel() // 2
    tc = TriangleCounter(g)
    plan = tc.plan
    t0 = time.perf_counter()
    first = plan.triangles_per_vertex()
    t1 = time.perf_counter()
    before = tracing.counters()
    t = plan.triangles_per_vertex()
    t2 = time.perf_counter()
    after = tracing.counters()
    check("vertex sum", int(first.sum()), int(want.sum()))
    if not (np.array_equal(first, want) and np.array_equal(t, want)):
        raise AssertionError("vertex: per-vertex counts differ from scipy's")
    d = g.degrees.astype(np.float64)
    cc = np.where(d > 1, 2.0 * want / np.maximum(d * (d - 1), 1), 0.0)
    if not np.array_equal(tc.clustering_coefficients(), cc):
        raise AssertionError("vertex: clustering coefficients differ")
    calls = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("tc.vertex_passes", "tc.dispatches", "tc.host_syncs")}
    log(f"  {tc.algorithm}: buckets {plan.meta.get('bucket_shapes')}; first "
        f"pass {t1 - t0:.3f}s, replay {t2 - t1:.3f}s; {calls}; "
        f"{int(want.sum()) // 3} triangles == scipy")
    vertex_chunk_timings(seed)


def _best(fn, *args, reps: int = 3):
    """(result, best of ``reps`` warm seconds) of ``fn(*args)``."""
    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return out, min(times)


def _scatter_vertex(n: int):
    """The per-vertex body before the credit was grouped by source run:
    every E·W mask slot scattered to its third vertex."""

    def run(u, v, src, dst, acc):
        from repro.kernels.intersect.ops import intersect_matches

        matched = intersect_matches(u, v)
        per_edge = matched.sum(axis=1, dtype=jnp.int32)
        t = acc + jax.ops.segment_sum(per_edge, src, num_segments=n)
        t = t + jax.ops.segment_sum(per_edge, dst, num_segments=n)
        return t + jax.ops.segment_sum(
            matched.reshape(-1).astype(jnp.int32),
            jnp.clip(u.reshape(-1), 0, n - 1), num_segments=n)

    run.__name__ = "scatter_vertex"
    return run


def vertex_chunk_timings(seed: int, n: int = 1 << 20, rows: int = 1 << 18,
                         width: int = 512, sources: int = 40960) -> None:
    """One W=512 chunk at the LCC cell's shapes (2^18 rows, n = 2^20, ~40k
    distinct sorted sources as its busiest chunks hold): the engine's
    ``tc_vertex_w512_gathered``, which credits by source run, against the
    whole-mask scatter it replaced, checked equal, and its grouping alone."""
    from repro.core import engine, prep
    from repro.graphs.device import _gather_bucket_dev
    from repro.kernels.intersect.ops import intersect_matches

    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    # sorted rows over a narrow id range, so that rows share ids and match
    nbrs = jnp.sort(jax.random.randint(keys[0], (n, width), 0, 4 * width,
                                       jnp.int32), axis=1)
    step = n // sources
    src = jnp.sort(jax.random.randint(keys[1], (rows,), 0, sources,
                                      jnp.int32)) * step
    dst = jax.random.randint(keys[2], (rows,), 0, n, jnp.int32)
    start, count = jnp.int32(0), jnp.int32(rows)
    args = (src, dst, start, count, nbrs, jnp.zeros(n, jnp.int32))
    log(f"[vertex chunk] W={width}, {rows} rows, n={n}, "
        f"{int(jnp.unique(src).shape[0])} distinct sources")

    forms = {"grouped": engine._build_vertex_executable(n, "grouped"),
             "scatter": _scatter_vertex(n)}
    got, best = {}, {}
    for name, fn in forms.items():
        run = prep.gathered_count(fn, endpoints=True)
        got[name], best[name] = _best(
            lambda *a, run=run: run(*a, n=n, rows=rows), *args)
    if not np.array_equal(np.asarray(got["grouped"]),
                          np.asarray(got["scatter"])):
        raise AssertionError("vertex chunk: grouped counts differ from the "
                             "whole-mask scatter's")

    @jax.jit
    def mask(ss, sd, st, ct, nb):
        u, v, s, _ = _gather_bucket_dev(ss, sd, st, ct, nb, n=n, e_pad=rows)
        return intersect_matches(u, v), u, s

    matched, u, s = jax.block_until_ready(mask(*args[:5]))
    (_, _, runs), group_s = _best(jax.jit(engine._group_by_source),
                                  matched, u, s)
    log(f"  chunk: grouped {best['grouped']:.6f}s, whole-mask scatter "
        f"{best['scatter']:.6f}s ({best['scatter'] / best['grouped']:.1f}x); "
        f"grouping {int(runs)} runs {group_s:.6f}s; "
        f"{int(got['grouped'].sum())} credits both")


def phase_serve(scale: int, seed: int, requests: int = 8) -> None:
    from repro.core import CountOptions, TriangleCounter
    from repro.graphs import rmat_graph
    from repro.serve import TriangleService

    pool = [rmat_graph(scale, 16, seed=seed + 10 + i, name=f"serve{i}")
            for i in range(4)]
    log(f"[serve] {len(pool)} graphs rmat_graph({scale}, 16), "
        f"{requests} count requests")
    want = {g.name: TriangleCounter(g).count().count for g in pool}
    svc = TriangleService(CountOptions())
    warm = svc.warmup(pool)
    log(f"  warmup {warm['seconds']:.3f}s ({warm['batchable']} batchable, "
        f"{warm['layouts']} layout(s))")
    t0 = time.perf_counter()
    with svc:
        futs = [svc.submit("count", pool[i % len(pool)],
                           tenant=f"tenant{i % 2}")
                for i in range(requests)]
        results = [f.result(timeout=600) for f in futs]
    wall = time.perf_counter() - t0
    for i, r in enumerate(results):
        check(f"serve request {i}", r.count, want[pool[i % len(pool)].name])
    lat = svc.snapshot()["latency"]["total"]
    log(f"  {requests} requests == facade in {wall:.3f}s; batch sizes "
        f"{sorted({r.batch_size for r in results})}; p50 "
        f"{lat['p50_ms']:.3f}ms p99 {lat['p99_ms']:.3f}ms")


def phase_dynamic(scale: int, seed: int, batch: int = 512) -> None:
    from repro.core import DynamicTriangleCounter, triangle_count_scipy
    from repro.graphs import rmat_graph

    g = rmat_graph(scale, 8, seed=seed + 20)
    log(f"[dynamic] rmat_graph({scale}, 8): n={g.n} m={g.m_undirected}")
    dc = DynamicTriangleCounter(g)
    check("dynamic seed count", dc.count().count, oracle(g))
    if dc.plan.key_mode != "wide":
        raise AssertionError(f"n={g.n} planned {dc.plan.key_mode!r} keys")
    rng = np.random.default_rng(seed)
    src, dst = g.edge_endpoints()
    gone = rng.choice(src.shape[0], batch // 2, replace=False)
    updates = [(int(src[i]), int(dst[i]), False) for i in gone]
    updates += [(int(u), int(v), True) for u, v in
                rng.integers(0, g.n, size=(batch - len(updates), 2))]
    res = dc.apply_updates(updates)
    after = triangle_count_scipy(dc.snapshot())
    check("dynamic apply_updates", res.count, after)
    check("dynamic recount", dc.recount(), after)
    log(f"  wide int64 keys; {batch} updates -> {res.count} "
        f"triangles in {res.exec_seconds:.3f}s; recount agrees")


def phase_sharded(seed: int, chips: int) -> None:
    from repro.core import TriangleCounter
    from repro.graphs import rmat_graph
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((chips,), ("data",))
    # both sharded lanes prep on the first chip before the deal: scale 16 is
    # the largest R-MAT whose intersection buckets fit there twice over, and
    # the matrix lane's host tile schedule stays under a GiB at scale 12
    for lane, sc in (("intersection_distributed", 16),
                     ("matrix_distributed", 12)):
        g = rmat_graph(sc, 16, seed=seed)
        log(f"[sharded] {lane} on {chips} chips, rmat_graph({sc}, 16): "
            f"n={g.n} m={g.m_undirected}")
        want = oracle(g)
        single = lane.replace("_distributed", "")
        one = TriangleCounter(g, algorithm=single).count()
        check(f"1-chip {single}", one.count, want)
        tc = TriangleCounter(g, algorithm=lane, mesh=mesh)
        first = tc.count()
        replay = tc.count()
        check(f"{lane} first count", first.count, want)
        check(f"{lane} replay", replay.count, want)
        log(f"  {first.count} == 1-chip == oracle; first "
            f"{first.exec_seconds:.3f}s replay {replay.exec_seconds:.3f}s "
            f"(1-chip {one.exec_seconds:.3f}s); shard work "
            f"{first.meta.get('shard_work')}")
        log(f"  device bytes in use {device_bytes()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded lanes on a 4-chip mesh")
    ap.add_argument("--scale", type=int, default=18,
                    help="R-MAT scale of the main count")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    cache = {"hits": 0, "misses": 0}

    def on_event(event: str, **_) -> None:
        if event in _CACHE_EVENTS:
            cache[_CACHE_EVENTS[event]] += 1

    jax.monitoring.register_event_listener(on_event)
    log(f"device: {devices[0].device_kind} x{len(devices)}; jax "
        f"{jax.__version__}; compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(args.seed, args.chips)
    else:
        phase_main(args.scale, args.seed)
        phase_pallas(12, args.seed)
        phase_strategy(args.seed)
        phase_vertex(14, args.seed)
        phase_serve(14, args.seed)
        phase_dynamic(16, args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.3f}s; compile "
        f"cache hits {cache['hits']} misses {cache['misses']}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
