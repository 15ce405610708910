"""Benchmark harness — one function per paper table/figure.

Prints ``name,prep_us,count_us,derived`` CSV rows:

  prep_us    — one-time cost per cell: host plan construction (orientation,
               bucketing, tile scheduling), device upload, and the first
               count (which traces + compiles); what the engine amortizes
  count_us   — device replay of a cached ``TrianglePlan`` (best of N); the
               kernel time the paper's figures compare
  table1_*   — dataset statistics (derived = exact triangle count)
  fig5_*     — per-method wall clock per dataset, normalized to the
               sequential CPU baseline (derived = count-time speedup ×; the
               paper's Fig. 5 bar chart). Includes a beyond-paper ``tc-auto``
               row per dataset: the facade's ``algorithm="auto"`` cost model,
               derived = ``<speedup>x;auto=<lane chosen>``
  fig6_*     — runtime vs Σd² scaling for intersection- and matrix-based TC
               (derived = fitted log-log slope of count time; the paper's
               Fig. 6 shows slope ≈ 1) plus the leading-constant ratio
               matrix/intersection (paper: ~20×)
  strat_*    — beyond-paper: per-degree-bucket set-intersection strategy ×
               width sweep (broadcast / probe / bitmap; see
               repro.kernels.intersect.ops). Every cell asserts exact
               agreement with the per-bucket oracle, and each bucket's rows
               record which strategy ``strategy="auto"`` would pick
               (derived = ``edges=E;auto=<choice>``). Cells outside the
               single-core budget emit explicit skipped rows.
  fig_batch_* — beyond-paper: ``count_many`` batch-size sweep — the Python
               loop of per-graph cached plans vs ONE vmapped ``GraphBatch``
               dispatch over the same graphs (derived records the
               loop/vmapped speedup). Tracks the batching win across PRs.
  fig_truss_* — beyond-paper: k-truss peel sweep — the host path (listing's
               numpy enumeration per round) vs the device edge lane
               (cached per-edge support executables + the device peel
               loop), one ``_host``/``_device`` row pair per graph plus a
               clique-heavy fixture. Every pair asserts bit-identical
               surviving edge sets; the device row's derived field records
               the host/device speedup and the peel round count.
  fig_auto_*  — beyond-paper: the measured ``algorithm="auto"`` chooser —
               calibrates a per-device ``CalibrationTable`` from timed
               micro-runs over the datasets (written as a
               ``CALIB_<device>.json`` sidecar into ``--json-dir``), then
               re-resolves every dataset through the facade with
               ``chooser="measured"``: one row per (dataset, lane) records
               that lane's measured count time, and the ``_auto`` row
               records the table's pick, the true fastest lane, and the
               pick/best time ratio (derived =
               ``auto=<lane>;best=<lane>;ratio=<x>``; 1.00 = perfect —
               ``tests/test_auto_dominance.py`` gates this at its
               tolerance). Every auto count asserts the scipy oracle.
  fig_stream_* — beyond-paper: dynamic-session streaming — identical random
               insert/delete batches applied two ways: the incremental lane
               (``DynamicTriangleCounter``: cached step + delta executables,
               zero recompiles asserted across the timed stream) vs a
               from-scratch intersection plan + count per batch. Per-batch
               counts must agree and the final count is anchored against
               the scipy oracle; derived records update throughput and the
               recount/incremental speedup (gated ≥3× in smoke).

  fig_dist_*  — beyond-paper: the sharded plan/execute engine — run in a
               SUBPROCESS with ``--xla_force_host_platform_device_count=8``
               so the deal is real (the parent keeps its single device). Per
               graph: the warm single-device intersection plan, the
               pre-engine one-shot ``shard_map`` lane reconstructed honestly
               (full prep + a fresh jitted closure on every call, nothing
               cached), and the warm planned ``intersection_distributed`` /
               ``matrix_distributed`` lanes. Every row asserts the scipy
               oracle; the planned rows assert ZERO executable-cache misses
               across the timed replays and record the measured speedup
               over the one-shot baseline (smoke gate: planned beats
               one-shot) plus the per-shard dealt work.

  fig_tile_*  — beyond-paper: tiled out-of-core streaming — the same graph
               counted by the monolithic intersection plan and by a plan
               whose ``max_device_bytes`` budget is forced to a quarter of
               the largest bucket, so the big buckets stream through
               chunk-shaped cached executables in ≥2 chunks (``_mono`` /
               ``_tiled`` row pair). The tiled count must equal the
               monolithic count AND the scipy oracle bit-exactly, the timed
               replays assert ZERO executable-cache misses (steady-state
               streaming never recompiles), and the tiled row's derived
               field records ``chunks=K;recompiles=0;overhead=<x>`` — the
               streaming overhead relative to monolithic, gated ≤2× in
               smoke.

  fig_serve_* — beyond-paper: the ``repro.serve`` front end under load — a
               multi-tenant pool of same-policy R-MAT graphs played through
               ``TriangleService`` as (a) the sequential per-request facade
               baseline, (b) a coalescible burst (derived records
               throughput, coalesce factor, and the speedup over
               sequential — the smoke gate is ≥2×), and (c) an offered-QPS
               sweep: a paced below-knee step (shed rate asserted exactly
               0), a deadline burst above the knee (sheds asserted > 0 and
               p99 asserted bounded — requests shed, never queued
               unboundedly), and a queue-full burst against a small-depth
               service. Every completed count asserts the scipy oracle and
               the whole serving phase asserts ZERO executable-cache
               misses (both services are pool-warmed first).

Alongside the CSV, every executed figure is written as machine-readable
``BENCH_<figure>.json`` (rows + env + device + the exact argv) into
``--json-dir`` (default: the working directory), so the perf trajectory can
be compared across PRs without re-parsing stdout.

CPU-only proxy: all methods run their jnp backends on the host; relative
orderings (intersection-filtered fastest, matrix slowest with a large
constant, SM wins from pruning on mesh-like graphs) are the reproducible
claims — see README.md §Experiments.

``--smoke`` swaps the dataset list for the tiny fixtures and drops the budget
gates (the CI smoke job runs the default table1+fig5 subset; any
``--figures`` selection, e.g. ``--figures strat --smoke`` or ``--figures
fig_batch --smoke``, honors it). Every fig5, strat, and fig_batch cell
asserts exact agreement with its oracle, so a correctness regression fails
the process. See docs/BENCHMARKS.md for the full contract.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import re
import subprocess
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.launch.compile_cache import enable_compile_cache
from repro.graphs import (
    DATASETS, edges_to_csr, load_dataset, normalize_edge_updates,
)
from repro.core import (
    CountOptions, DynamicTriangleCounter, GraphBatch, TriangleCounter,
    calibrate, executable_cache_info, save_table, set_default_table,
    triangle_count_scipy,
)
from repro.core.calibrate import calib_path
from repro.core.engine import get_executable, prepare_intersection_buckets
from repro.core.listing import _k_truss_host
from repro.kernels.intersect import (
    STRATEGIES, intersect_counts_probe, intersect_counts_ref, resolve_strategy,
)
from repro.graphs.generators import complete_graph, rmat_graph
from repro.configs.paper import DATASETS_FIG5, FIG6_SCALES, FIG6_EDGE_FACTOR

_ROWS = []


def _emit(name: str, prep_us: float, count_us: float, derived) -> None:
    row = f"{name},{prep_us:.1f},{count_us:.1f},{derived}"
    _ROWS.append(dict(name=name, prep_us=round(prep_us, 1),
                      count_us=round(count_us, 1), derived=str(derived)))
    print(row, flush=True)


def _write_json(figures, json_dir: str, smoke: bool) -> None:
    """One ``BENCH_<figure>.json`` per executed figure: its CSV rows plus
    enough environment to compare runs across PRs/machines."""
    env = dict(
        python=platform.python_version(),
        jax=jax.__version__,
        numpy=np.__version__,
        platform=platform.platform(),
    )
    device = str(jax.devices()[0])
    os.makedirs(json_dir, exist_ok=True)
    for fig in figures:
        rows = [r for r in _ROWS if r["name"].startswith(fig + "_")]
        path = os.path.join(json_dir, f"BENCH_{fig}.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump(
                dict(figure=fig, smoke=smoke, argv=sys.argv[1:],
                     env=env, device=device, rows=rows),
                f, indent=2,
            )
            f.write("\n")
        print(f"# wrote {path} ({len(rows)} rows)", flush=True)


def _time(fn, *, warmup: int = 1, iters: int = 2) -> float:
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


# method -> the facade's typed options (benchmarks go through the same
# front door users do; "tc-auto" exercises the cross-lane cost model)
_METHOD_OPTIONS = {
    "tc-intersection-filtered": CountOptions(algorithm="intersection"),
    "tc-intersection-full": CountOptions(algorithm="intersection",
                                         variant="full"),
    "tc-matrix": CountOptions(algorithm="matrix"),  # block="auto"
    "tc-SM": CountOptions(algorithm="subgraph"),
    "tc-auto": CountOptions(),  # algorithm="auto"
}


def _timed_plan(g, meth: str, **overrides):
    """Build the session AND run its first count for one fig5/fig6 cell, so
    prep_us covers the whole one-time cost: host prep, device upload, and
    the first trace+compile. Returns (result, prep_us); ``result.plan.count``
    is the replay to time."""
    opts = _METHOD_OPTIONS[meth]
    if overrides:
        opts = opts.replace(**overrides)
    t0 = time.perf_counter()
    result = TriangleCounter(g, opts).count()
    prep_us = (time.perf_counter() - t0) * 1e6
    return result, prep_us


def table1(datasets) -> None:
    for name in datasets:
        g = load_dataset(name)
        t0 = time.perf_counter()
        tri = triangle_count_scipy(g)
        us = (time.perf_counter() - t0) * 1e6
        _emit(f"table1_{name}_v{g.n}_e{g.m_undirected}_d{g.max_degree}"
              f"_{DATASETS[name]['type']}", 0.0, us, tri)


# single-core budget policy: the filtered method and SM run everywhere;
# the quadratic full-list ablation runs under 150k edges; the matrix method
# runs on the datasets whose tile schedules fit the budget (measured) —
# skips are explicit rows. The smoke subset lifts both limits (tiny graphs).
_FULL_LIMIT = 150_000  # undirected edges
_MATRIX_SETS = {"coauthors-like", "road-like"}


def fig5(datasets, *, budget: bool = True, iters: int = 2) -> None:
    for name in datasets:
        g = load_dataset(name)
        truth = triangle_count_scipy(g)
        base_us = _time(lambda: triangle_count_scipy(g), iters=iters)
        _emit(f"fig5_{name}_cpu-baseline", 0.0, base_us, "1.00x")
        for meth in ("tc-intersection-filtered", "tc-intersection-full",
                     "tc-matrix", "tc-SM", "tc-auto"):
            if (budget and meth == "tc-intersection-full"
                    and g.m_undirected > _FULL_LIMIT):
                _emit(f"fig5_{name}_{meth}", 0.0, 0.0, "skipped(budget)")
                continue
            if budget and meth == "tc-matrix" and name not in _MATRIX_SETS:
                _emit(f"fig5_{name}_{meth}", 0.0, 0.0, "skipped(budget)")
                continue
            result, prep_us = _timed_plan(g, meth)
            assert result == truth, (name, meth)
            count_us = _time(result.plan.count, iters=iters)
            derived = f"{base_us / count_us:.2f}x"
            if meth == "tc-auto":  # surface the cost model's lane choice
                derived += f";auto={result.algorithm}"
            _emit(f"fig5_{name}_{meth}", prep_us, count_us, derived)


def fig6(scales, *, iters: int = 2) -> None:
    ssds, t_int, t_mat = [], [], []
    for scale in scales:
        g = rmat_graph(scale, FIG6_EDGE_FACTOR, seed=scale)
        ssd = g.sum_square_degrees
        # fixed block=128 so every scale times the same tile size and the
        # slope fit stays comparable (choose_block could flip mid-sweep)
        res_i, prep_i = _timed_plan(g, "tc-intersection-filtered")
        res_m, prep_m = _timed_plan(g, "tc-matrix", block=128)
        us_i = _time(res_i.plan.count, iters=iters)
        us_m = _time(res_m.plan.count, iters=iters)
        ssds.append(ssd)
        t_int.append(us_i)
        t_mat.append(us_m)
        _emit(f"fig6_rmat{scale}_ssd{ssd}_intersection", prep_i, us_i,
              f"ssd={ssd}")
        _emit(f"fig6_rmat{scale}_ssd{ssd}_matrix", prep_m, us_m, f"ssd={ssd}")
    # log-log slope fits on count time (paper: slope ≈ 1 for both)
    lx = np.log(np.asarray(ssds, dtype=np.float64))
    for label, ts in (("intersection", t_int), ("matrix", t_mat)):
        ly = np.log(np.asarray(ts, dtype=np.float64))
        slope, intercept = np.polyfit(lx, ly, 1)
        _emit(f"fig6_slope_{label}", 0.0, float(np.mean(ts)),
              f"slope={slope:.3f}")
    # leading-constant ratio at the largest size (paper: ~20x)
    _emit("fig6_constant_ratio_matrix_over_intersection", 0.0,
          t_mat[-1], f"{t_mat[-1] / t_int[-1]:.1f}x")


# strat sweep budget policy (single-core): the O(E·W²) broadcast core only
# runs on buckets under the compare budget, and bitmap only when the packed
# bitmap stays small; skips are explicit rows, mirroring the fig5 policy
_STRAT_BROADCAST_BUDGET = 1 << 30  # E·W² compares per bucket
_STRAT_BITMAP_MAX_BITS = 4096


def _bucket_oracle(u: np.ndarray, v: np.ndarray) -> int:
    """Per-bucket reference total: the chunked broadcast-compare oracle when
    the bucket fits the compare budget, else the probe path (whose global sum
    the caller anchors against the scipy oracle)."""
    e, w = u.shape
    uj, vj = jnp.asarray(u), jnp.asarray(v)
    if e * w * w <= _STRAT_BROADCAST_BUDGET:
        total, chunk = 0, max(1, (1 << 24) // (w * w))
        for s in range(0, e, chunk):
            total += int(jnp.sum(intersect_counts_ref(uj[s:s + chunk],
                                                      vj[s:s + chunk])))
        return total
    return int(jnp.sum(intersect_counts_probe(uj, vj)))


def strat(datasets, *, iters: int = 2) -> None:
    """Per-bucket strategy × width sweep on the filtered intersection lane.

    One row per (dataset, bucket width, strategy) timing the engine's cached
    jnp executable for that (strategy, shape); every executed cell asserts
    exact agreement with the per-bucket oracle, and the per-dataset bucket
    totals are anchored against the scipy oracle.
    """
    for name in datasets:
        g = load_dataset(name)
        truth = triangle_count_scipy(g)
        buckets = prepare_intersection_buckets(g, variant="filtered")
        id_range = g.n + 2  # real ids + the n / n+1 in-row sentinels
        refs = [_bucket_oracle(b["u_lists"], b["v_lists"]) for b in buckets]
        assert sum(refs) == truth, (name, sum(refs), truth)
        for b, ref_total in zip(buckets, refs):
            w = b["width"]
            e = b["u_lists"].shape[0]
            auto_choice, _ = resolve_strategy(w, id_range)
            u, v = jnp.asarray(b["u_lists"]), jnp.asarray(b["v_lists"])
            derived = f"edges={e};auto={auto_choice}"
            for s in STRATEGIES:
                row = f"strat_{name}_w{w}_{s}"
                if s == "broadcast" and e * w * w > _STRAT_BROADCAST_BUDGET:
                    _emit(row, 0.0, 0.0, "skipped(budget)")
                    continue
                if s == "bitmap":
                    _, bits = resolve_strategy(w, id_range, strategy="bitmap")
                    if bits > _STRAT_BITMAP_MAX_BITS:
                        _emit(row, 0.0, 0.0, "skipped(id-range)")
                        continue
                else:
                    bits = None
                t0 = time.perf_counter()
                fn = get_executable("intersection", "jnp", True, u.shape,
                                    strategy=s, bitmap_bits=bits)
                first = int(fn(u, v))
                prep_us = (time.perf_counter() - t0) * 1e6
                assert first == ref_total, (name, w, s, first, ref_total)
                count_us = _time(lambda: int(fn(u, v)), iters=iters)
                _emit(row, prep_us, count_us, derived)


def fig_batch(sizes, *, iters: int = 2, scale: int = 7,
              edge_factor: int = 6) -> None:
    """``count_many`` batching sweep: per-graph loop vs one vmapped dispatch.

    For each batch size B, generates B same-policy R-MAT graphs, then times
    (a) a Python loop replaying B cached per-graph plans and (b) one
    ``GraphBatch.counts()`` device dispatch over the stacked buckets. Both
    lanes assert exact agreement with the scipy oracle; derived records the
    loop/vmapped speedup.
    """
    opts = CountOptions(algorithm="intersection")
    for B in sizes:
        graphs = [rmat_graph(scale, edge_factor, seed=200 + i,
                             name=f"rmat{scale}b{i}") for i in range(B)]
        truth = [triangle_count_scipy(g) for g in graphs]

        t0 = time.perf_counter()
        sessions = [TriangleCounter(g, opts) for g in graphs]
        loop_counts = [int(s.count()) for s in sessions]
        loop_prep_us = (time.perf_counter() - t0) * 1e6
        assert loop_counts == truth, ("fig_batch loop", B)
        loop_us = _time(lambda: [s.plan.count() for s in sessions],
                        iters=iters)
        _emit(f"fig_batch_rmat{scale}_B{B}_loop", loop_prep_us, loop_us,
              f"graphs={B}")

        t0 = time.perf_counter()
        batch = GraphBatch.from_graphs(graphs, opts)
        batch_counts = [int(c) for c in batch.counts()]
        batch_prep_us = (time.perf_counter() - t0) * 1e6
        assert batch_counts == truth, ("fig_batch vmapped", B)
        batch_us = _time(batch.counts, iters=iters)
        _emit(f"fig_batch_rmat{scale}_B{B}_vmapped", batch_prep_us, batch_us,
              f"graphs={B};speedup={loop_us / max(batch_us, 1e-9):.2f}x")


# fig_truss budget policy (single-core): the host path re-enumerates every
# triangle per peel round, so under budget it only runs on graphs below this
# edge count; skips are explicit rows (the device row still runs)
_TRUSS_HOST_LIMIT = 150_000  # undirected edges
_TRUSS_K = 4


def _clique_heavy_graph(n_clique: int = 96, n_spurs: int = 64):
    """The fig_truss fixture: one K_{n_clique} plus pendant spur edges off
    vertex 0 — the regime the device peel wins hardest (wide dense
    neighbor lists make the host path's per-round O(E·W²) eq tensors
    expensive) while still peeling >1 round (the spurs go first)."""
    base = complete_graph(n_clique)
    src, dst = base.edge_list_unique()
    spur_src = np.zeros(n_spurs, dtype=np.int64)
    spur_dst = np.arange(n_clique, n_clique + n_spurs, dtype=np.int64)
    return edges_to_csr(np.concatenate([src.astype(np.int64), spur_src]),
                        np.concatenate([dst.astype(np.int64), spur_dst]),
                        n=n_clique + n_spurs, name="clique-heavy")


def fig_truss(datasets, *, budget: bool = True, iters: int = 2,
              k: int = _TRUSS_K) -> None:
    """k-truss peel: host enumeration (listing oracle) vs the device edge
    lane.

    One row pair per graph (the given datasets plus the clique-heavy
    fixture): ``_host`` times ``listing._k_truss_host`` (full numpy peel,
    re-enumerating triangles each round) and ``_device`` times
    ``TriangleCounter.k_truss`` (cached edge executables + the device peel
    loop). Every pair asserts the surviving edge sets are bit-identical;
    the device row's derived field records the host/device speedup and the
    peel round count.
    """
    graphs = [load_dataset(name) for name in datasets]
    graphs.append(_clique_heavy_graph())
    for g in graphs:
        if budget and g.m_undirected > _TRUSS_HOST_LIMIT:
            _emit(f"fig_truss_{g.name}_k{k}_host", 0.0, 0.0,
                  "skipped(budget)")
            host_us = None
        else:
            truth = _k_truss_host(g, k)
            host_us = _time(lambda: _k_truss_host(g, k), iters=iters)
            _emit(f"fig_truss_{g.name}_k{k}_host", 0.0, host_us,
                  f"edges={truth.m_undirected}")
        t0 = time.perf_counter()
        tc = TriangleCounter(g, CountOptions(algorithm="edge"))
        dev = tc.k_truss(k)  # builds the plan + compiles the peel stages
        prep_us = (time.perf_counter() - t0) * 1e6
        if host_us is not None:
            assert dev.n == truth.n, g.name
            assert np.array_equal(dev.row_ptr, truth.row_ptr), g.name
            assert np.array_equal(dev.col_idx, truth.col_idx), g.name
        dev_us = _time(lambda: tc.k_truss(k), iters=iters)
        rounds = tc.plan.meta.get("peel_rounds")
        derived = f"edges={dev.m_undirected};rounds={rounds}"
        if host_us is not None:
            derived += f";speedup={host_us / max(dev_us, 1e-9):.2f}x"
        _emit(f"fig_truss_{g.name}_k{k}_device", prep_us, dev_us, derived)


def fig_auto(datasets, *, iters: int = 2, json_dir: str = ".") -> None:
    """Measured auto chooser: calibrate, persist the sidecar, audit picks.

    Builds a per-device ``CalibrationTable`` by timing every chooser lane
    on every dataset (warm best-of micro-runs, same policy as ``_time``),
    writes it as ``CALIB_<device>.json`` into ``json_dir``, then installs
    it and re-resolves each dataset through the facade with
    ``chooser="measured"``. Per dataset: one row per lane with its
    measured count time, plus the ``_auto`` row whose derived field
    records the table's pick, the true fastest lane, and the pick/best
    measured-time ratio. Every auto count asserts the scipy oracle; the
    previously installed table is always restored.
    """
    graphs = [load_dataset(name) for name in datasets]
    t0 = time.perf_counter()
    table = calibrate(graphs, iters=iters, warmup=1)
    calib_us = (time.perf_counter() - t0) * 1e6
    os.makedirs(json_dir, exist_ok=True)
    path = save_table(table, calib_path(json_dir))
    print(f"# wrote {path} ({len(table.entries)} bins, "
          f"calibrated in {calib_us / 1e6:.2f}s)", flush=True)
    prev = set_default_table(table)
    try:
        for name, g in zip(datasets, graphs):
            truth = triangle_count_scipy(g)
            timings = table.lookup(g) or {}
            for lane in sorted(timings):
                _emit(f"fig_auto_{name}_{lane}", 0.0, timings[lane] * 1e6,
                      "measured")
            t0 = time.perf_counter()
            result = TriangleCounter(g, CountOptions(chooser="measured")
                                     ).count()
            prep_us = (time.perf_counter() - t0) * 1e6
            assert result == truth, (name, result.algorithm)
            count_us = _time(result.plan.count, iters=iters)
            best = min(sorted(timings), key=lambda l: timings[l])
            ratio = (timings[result.algorithm]
                     / max(timings[best], 1e-12))
            _emit(f"fig_auto_{name}_auto", prep_us, count_us,
                  f"auto={result.algorithm};best={best};ratio={ratio:.2f}")
    finally:
        set_default_table(prev)


def fig_stream(*, num_batches: int = 12, batch_edges: int = 64,
               scale: int = 12, edge_factor: int = 6, seed: int = 17,
               min_speedup: float = 0.0) -> None:
    """Dynamic-session streaming: incremental deltas vs per-batch recounts.

    One R-MAT graph takes ``num_batches`` random insert/delete batches two
    ways over identical update streams: the ``_incremental`` row times a
    ``DynamicTriangleCounter`` applying every batch through its cached
    step + delta executables (asserting ZERO executable-cache misses across
    the timed stream — the shape-class contract), and the ``_full-recount``
    row times the static alternative, a from-scratch
    ``TriangleCounter(..., algorithm="intersection")`` plan + count per
    batch (the host edge set is maintained outside the timing). Both lanes
    must produce identical per-batch counts, and the final count is
    anchored against the scipy oracle. The incremental row's derived field
    records batches/updates-per-second/recompiles; the full-recount row
    records the recount/incremental speedup, gated at ``min_speedup`` when
    non-zero (the smoke CI gate).
    """
    g = rmat_graph(scale, edge_factor, seed=seed)
    n = g.n
    rng = np.random.default_rng(seed)
    # steady-state stream: per batch, half deletes sampled from the LIVE
    # edge set and half random-pair inserts, so the edge count stays inside
    # its capacity class (the zero-recompile contract under test — growing
    # past the class is covered by tests/test_dynamic.py, not timed here).
    # The same host walk records the post-batch snapshots the full-recount
    # lane counts, all before any timing starts.
    edges = set(zip(*(a.tolist() for a in g.edge_list_unique())))
    batches, snapshots = [], []
    for i in range(num_batches + 1):  # +1 warmup batch
        k = batch_edges // 2
        live = sorted(edges)
        dels = [live[j] for j in
                rng.choice(len(live), size=min(k, len(live)), replace=False)]
        u = rng.integers(0, n, size=batch_edges - len(dels))
        v = rng.integers(0, n, size=batch_edges - len(dels))
        ups = [(a, b, False) for a, b in dels]
        ups += [(int(a), int(b), True) for a, b in zip(u, v)]
        batch = normalize_edge_updates(ups, n)
        batches.append(batch)
        for a, b, f in zip(*(x.tolist() for x in batch)):
            (edges.add if f else edges.discard)((a, b))
        if i > 0:  # snapshots for the full-recount lane (warmup excluded)
            src = np.array([e[0] for e in sorted(edges)], dtype=np.int64)
            dst = np.array([e[1] for e in sorted(edges)], dtype=np.int64)
            snapshots.append(edges_to_csr(src, dst, n=n, name=f"stream{i}"))
    warm_batch, stream = batches[0], batches[1:]

    # incremental lane: prep covers session construction + the warmup batch
    # (which compiles the step/delta executables for this shape class)
    t0 = time.perf_counter()
    dc = DynamicTriangleCounter(
        g, CountOptions(algorithm="dynamic", update_batch_size=batch_edges,
                        recount_interval=0))
    dc.count()
    dc.plan.apply_updates(*warm_batch)
    inc_prep_us = (time.perf_counter() - t0) * 1e6
    cache_before = dc.cache_stats()
    inc_counts = []
    t0 = time.perf_counter()
    for lo, hi, ins in stream:
        dc.plan.apply_updates(lo, hi, ins)
        inc_counts.append(int(dc.count()))
    inc_us = (time.perf_counter() - t0) * 1e6
    recompiles = dc.cache_stats()["misses"] - cache_before["misses"]
    assert recompiles == 0, f"fig_stream recompiled {recompiles}x mid-stream"
    assert int(dc.count()) == triangle_count_scipy(dc.snapshot())
    dc.recount()
    upd_per_s = num_batches * batch_edges / (inc_us / 1e6)
    _emit(f"fig_stream_rmat{scale}_incremental", inc_prep_us,
          inc_us / num_batches,
          f"batches={num_batches};upd_per_s={upd_per_s:.0f};"
          f"recompiles={recompiles}")

    # full-recount lane: the same stream counted from scratch per batch
    # (the host snapshots were materialized before any timing)
    opts = CountOptions(algorithm="intersection")
    t0 = time.perf_counter()
    # compile warmup over EVERY snapshot: per-snapshot bucket layouts can
    # land in different shape classes, and leaving any compile inside the
    # timed loop would inflate the speedup (and make it depend on what the
    # process compiled earlier)
    for s in snapshots:
        int(TriangleCounter(s, opts).count())
    full_prep_us = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    full_counts = [int(TriangleCounter(s, opts).count()) for s in snapshots]
    full_us = (time.perf_counter() - t0) * 1e6
    assert full_counts == inc_counts, "fig_stream lanes disagree"
    speedup = full_us / max(inc_us, 1e-9)
    if min_speedup:
        assert speedup >= min_speedup, \
            f"fig_stream speedup {speedup:.2f}x below gate {min_speedup}x"
    _emit(f"fig_stream_rmat{scale}_full-recount", full_prep_us,
          full_us / num_batches,
          f"batches={num_batches};speedup={speedup:.2f}x")


def fig_serve(*, pool_size: int = 8, scale: int = 7, edge_factor: int = 6,
              requests: int = 32, sweep_requests: int = 24,
              burst_requests: int = 48, min_speedup: float = 0.0) -> None:
    """``repro.serve`` under load: coalescing throughput + the shed knee.

    One pool of same-policy R-MAT graphs plays a multi-tenant request mix
    through ``TriangleService`` in four phases, every completed count
    asserted bit-identical to the scipy oracle and ZERO executable-cache
    misses asserted across all serving phases (both services are warmed
    over the pool first, so steady state compiles nothing):

      _sequential     — the per-request facade loop (fresh ``TriangleCounter``
                        per request): the baseline the service must beat.
      _service-batch  — the same requests burst through the service; derived
                        records throughput, the coalesce factor, and the
                        speedup over sequential (gated at ``min_speedup``
                        when non-zero — the smoke CI gate is 2x).
      _qps<r>         — the offered-QPS sweep: a below-knee paced step
                        (asserts shed rate exactly 0), an above-knee
                        deadline burst (asserts sheds > 0 AND p99 stays
                        bounded by deadline + window + execution — shed,
                        not queued unboundedly), and a queue-full burst
                        against a small-depth service (asserts depth-based
                        sheds). Each row records p50/p99 latency,
                        throughput, coalesce factor, and shed rate.
    """
    from repro.serve import RequestShed, ServeConfig, TriangleService
    from repro.core import executable_cache_info

    opts = CountOptions(algorithm="intersection")
    pool = [rmat_graph(scale, edge_factor, seed=300 + i,
                       name=f"serve{scale}p{i}") for i in range(pool_size)]
    oracle = [int(triangle_count_scipy(g)) for g in pool]
    base = f"fig_serve_rmat{scale}"

    def pick(i):  # the synthetic multi-tenant mix: tenants cycle the pool
        return i % pool_size, f"tenant{i % 4}"

    def run_burst(svc, n, *, deadline_ms=None, pace_s=None):
        """Submit n pool requests (burst, or paced at ``pace_s``); returns
        (results keyed by graph index, shed reasons, wall seconds)."""
        futs = []
        t0 = time.perf_counter()
        for i in range(n):
            gi, tenant = pick(i)
            futs.append((gi, svc.submit("count", pool[gi], tenant=tenant,
                                        deadline_ms=deadline_ms)))
            if pace_s:
                time.sleep(pace_s)
        done, shed = [], []
        for gi, f in futs:
            try:
                done.append((gi, f.result(timeout=120)))
            except RequestShed as e:
                shed.append(e.reason)
        wall = time.perf_counter() - t0
        for gi, r in done:
            assert r.count == oracle[gi], (pool[gi].name, r.count, oracle[gi])
        return done, shed, wall

    def stats(done, shed, wall):
        n = len(done) + len(shed)
        lat = sorted(r.total_s for _, r in done)
        p50 = 1e3 * lat[len(lat) // 2] if lat else 0.0
        p99 = 1e3 * lat[min(len(lat) - 1, int(0.99 * len(lat)))] if lat \
            else 0.0
        dispatches = sum(1.0 / r.batch_size for _, r in done)
        coalesce = len(done) / dispatches if dispatches else 1.0
        thr = len(done) / wall if wall else 0.0
        return dict(p50=p50, p99=p99, coalesce=coalesce, thr=thr,
                    shed_rate=len(shed) / n if n else 0.0)

    # sequential facade baseline: fresh session per request (re-prep every
    # time — exactly what a per-request front end without the serve layer
    # would do). Warm one session per graph first so the timed loop measures
    # steady-state per-request cost, not compilation.
    t0 = time.perf_counter()
    for gi, g in enumerate(pool):
        assert int(TriangleCounter(g, opts).count()) == oracle[gi], g.name
    seq_prep_us = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    for i in range(requests):
        gi, _ = pick(i)
        c = int(TriangleCounter(pool[gi], opts).count())
        assert c == oracle[gi], pool[gi].name
    seq_wall = time.perf_counter() - t0
    seq_thr = requests / seq_wall
    _emit(f"{base}_sequential", seq_prep_us, 1e6 * seq_wall / requests,
          f"requests={requests};throughput={seq_thr:.0f}")

    # both services warm over the whole pool BEFORE the zero-recompile
    # watch starts: prep caches filled, monotone layouts fixed, every pow-2
    # batch executable + single pass-through compiled
    svc = TriangleService(opts, config=ServeConfig(
        max_queue_depth=max(256, requests + burst_requests),
        batch_window_ms=5.0, max_batch=8,
        plan_cache_size=max(128, 2 * pool_size)))
    svc.warmup(pool)
    small_depth = 12
    svc_small = TriangleService(opts, config=ServeConfig(
        max_queue_depth=small_depth, batch_window_ms=2.0, max_batch=8,
        plan_cache_size=max(128, 2 * pool_size)))
    svc_small.warmup(pool)
    misses0 = executable_cache_info()["misses"]

    with svc:
        # coalescible burst: the throughput head-to-head vs sequential
        done, shed, wall = run_burst(svc, requests)
        assert not shed, f"ample-depth burst shed {len(shed)} requests"
        st = stats(done, shed, wall)
        speedup = st["thr"] / seq_thr
        if min_speedup:
            assert speedup >= min_speedup, \
                f"service throughput {speedup:.2f}x sequential is below " \
                f"the {min_speedup}x gate"
        _emit(f"{base}_service-batch", 0.0, 1e6 * wall / requests,
              f"requests={requests};throughput={st['thr']:.0f};"
              f"coalesce={st['coalesce']:.2f};speedup={speedup:.2f}x")

        # below the knee: paced at ~40% of measured service capacity —
        # nothing sheds, latency is queue-window dominated
        offered = 0.4 * st["thr"]
        done, shed, wall = run_burst(svc, sweep_requests,
                                     pace_s=1.0 / offered)
        assert not shed, f"below-knee step shed {len(shed)} requests"
        st_lo = stats(done, shed, wall)
        _emit(f"{base}_qps{offered:.0f}", 0.0, 1e6 * wall / sweep_requests,
              f"offered_qps={offered:.0f};p50_ms={st_lo['p50']:.1f};"
              f"p99_ms={st_lo['p99']:.1f};throughput={st_lo['thr']:.0f};"
              f"coalesce={st_lo['coalesce']:.2f};shed_rate=0.000")

        # above the knee: a burst whose deadline budget covers only part of
        # the backlog — late requests shed with reason "deadline", and p99
        # of what completes stays bounded by deadline + window + execution
        # (requests are rejected, never queued unboundedly)
        drain_s = burst_requests / st["thr"]
        deadline_ms = max(15.0, 1e3 * 0.35 * drain_s)
        # the knee is measured, not known: a fully-warm process can drain
        # the whole burst inside the first deadline guess, so halve the
        # budget until it really covers only part of the backlog (halving
        # from a deadline the service just beat keeps the head servable)
        for _ in range(16):
            done, shed, wall = run_burst(svc, burst_requests,
                                         deadline_ms=deadline_ms)
            if shed:
                break
            deadline_ms /= 2.0
        assert shed, "above-knee burst must shed"
        assert done, "above-knee burst must still serve the head"
        assert all(r == "deadline" for r in shed), sorted(set(shed))
        st_hi = stats(done, shed, wall)
        max_exec_ms = 1e3 * max(r.exec_s for _, r in done)
        bound_ms = deadline_ms + 5.0 + 2.0 * max_exec_ms + 100.0
        assert st_hi["p99"] <= bound_ms, \
            f"p99 {st_hi['p99']:.1f}ms exceeds shed bound {bound_ms:.1f}ms"
        offered_hi = burst_requests / wall
        _emit(f"{base}_qps{offered_hi:.0f}", 0.0,
              1e6 * wall / burst_requests,
              f"offered_qps={offered_hi:.0f};p50_ms={st_hi['p50']:.1f};"
              f"p99_ms={st_hi['p99']:.1f};throughput={st_hi['thr']:.0f};"
              f"coalesce={st_hi['coalesce']:.2f};"
              f"shed_rate={st_hi['shed_rate']:.3f};"
              f"deadline_ms={deadline_ms:.0f}")

    # depth-based shedding: the same burst against a small admission queue —
    # request max_queue_depth+1 is rejected at the door, not buffered
    with svc_small:
        done, shed, wall = run_burst(svc_small, burst_requests)
        assert shed, "small-depth burst must shed on queue-full"
        assert done, "small-depth burst must still serve the backlog"
        assert all(r == "queue-full" for r in shed), sorted(set(shed))
        st_q = stats(done, shed, wall)
        _emit(f"{base}_qps-burst-depth{small_depth}", 0.0,
              1e6 * wall / burst_requests,
              f"offered_qps=burst;p50_ms={st_q['p50']:.1f};"
              f"p99_ms={st_q['p99']:.1f};throughput={st_q['thr']:.0f};"
              f"coalesce={st_q['coalesce']:.2f};"
              f"shed_rate={st_q['shed_rate']:.3f};depth={small_depth}")

    recompiles = executable_cache_info()["misses"] - misses0
    assert recompiles == 0, \
        f"fig_serve recompiled {recompiles}x in steady state"
    snap = svc.snapshot()
    _emit(f"{base}_steady-state", 0.0, 0.0,
          f"recompiles={recompiles};plan_cache_hits={snap['plan_cache']['hits']};"
          f"plan_cache_misses={snap['plan_cache']['misses']};"
          f"coalesce={snap['coalesce_factor']:.2f};"
          f"shed={snap['counters'].get('shed', 0)}")


def _dist_rows(ndev: int, scale: int, edge_factor: int, iters: int) -> list:
    """The ``fig_dist`` rows over a 1-D mesh of this process's ``ndev``
    devices; every row oracle-asserted."""
    from repro.launch.mesh import make_mesh
    from repro.core import engine
    from repro.core.engine import plan_triangle_count
    from repro.graphs.device import ShardedDeviceCSR

    assert jax.device_count() == ndev, jax.device_count()
    g = rmat_graph(scale, edge_factor, seed=11, name="dist%d" % scale)
    want = int(triangle_count_scipy(g))
    mesh = make_mesh((ndev,), ("data",))
    rows = []

    def best(fn):
        b = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            b = min(b, time.perf_counter() - t0)
        return b * 1e6

    # single-device reference: the warm planned intersection lane
    t0 = time.perf_counter()
    p1 = plan_triangle_count(g, "intersection")
    assert p1.count() == want
    prep1 = (time.perf_counter() - t0) * 1e6
    rows.append(dict(name="rmat%d_single" % scale, prep_us=prep1,
                     count_us=best(p1.count), derived="devices=1;oracle=ok"))

    # the pre-engine one-shot shard_map lane, reconstructed honestly: full
    # prep, the deal, and a FRESH jitted closure on EVERY call — nothing
    # shared with the executable cache (what core/distributed.py did before
    # the planned lanes)
    def one_shot():
        sh = ShardedDeviceCSR.from_graph(g, mesh)
        total = 0
        for b in sh.buckets:
            strat, bits = engine._resolve_bucket_strategy(
                b.width, g.n + 2, "auto", None)
            fn = engine._build_dist_intersect_executable(
                strat, bits, b.shape + (b.chunk,), mesh)
            total += int(fn(b.u_lists, b.v_lists, b.valid))
        return total

    us_os = float("inf")
    for _ in range(max(2, iters)):  # every call pays prep + trace + compile
        t0 = time.perf_counter()
        assert one_shot() == want
        us_os = min(us_os, (time.perf_counter() - t0) * 1e6)
    rows.append(dict(name="rmat%d_oneshot%d" % (scale, ndev), prep_us=0.0,
                     count_us=us_os,
                     derived="devices=%d;oracle=ok;cached=no" % ndev))

    # the planned distributed lanes: prep once, cached per-shard executables,
    # zero recompiles across the timed replays
    for lane, tag in (("intersection_distributed", "planned"),
                      ("matrix_distributed", "matrix")):
        t0 = time.perf_counter()
        p = plan_triangle_count(g, lane, mesh=mesh)
        assert p.count() == want
        prep_us = (time.perf_counter() - t0) * 1e6
        m0 = executable_cache_info()["misses"]
        us = best(p.count)
        rec = executable_cache_info()["misses"] - m0
        assert rec == 0, (lane, rec)
        work = p.meta["shard_work"]
        balance = max(work) / max(min(work), 1)
        rows.append(dict(
            name="rmat%d_%s%d" % (scale, tag, ndev), prep_us=prep_us,
            count_us=us,
            derived="devices=%d;oracle=ok;recompiles=%d;speedup=%.2fx;"
                    "balance=%.2f" % (ndev, rec, us_os / us, balance)))

    return rows


# CPU runs only: forced host devices in a child that sets its own platform
# (jax locks the device count at first init, so the parent cannot shard
# itself). argv: ndev scale edge_factor iters benchmarks_dir. Prints one
# ``ROWS:<json>`` line.
_DIST_CHILD = r"""
import json, os, sys
ndev, scale, ef, iters = (int(a) for a in sys.argv[1:5])
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % ndev
sys.path.insert(0, sys.argv[5])
import run
print("ROWS:" + json.dumps(run._dist_rows(ndev, scale, ef, iters)), flush=True)
"""


def fig_dist(*, ndev: int = 8, scale: int = 8, edge_factor: int = 8,
             iters: int = 3, min_speedup: float = 0.0) -> None:
    """Single device vs a mesh of devices.

    On the CPU the mesh is ``ndev`` forced host devices in a child process
    (``_DIST_CHILD``); on an accelerator it is every device this process
    holds, in-process, since a child could not reach a device the parent
    already holds. Every row asserts the scipy oracle and zero recompiles
    across the planned lanes' timed replays; the rows are re-emitted here and
    the planned-vs-one-shot speedup is gated at ``min_speedup`` when
    non-zero.
    """
    if jax.default_backend() != "cpu":
        ndev = jax.device_count()
        rows = _dist_rows(ndev, scale, edge_factor, iters)
    else:
        env = dict(os.environ)
        here = os.path.dirname(os.path.abspath(__file__))
        env["PYTHONPATH"] = os.path.join(here, "..", "src") + os.pathsep \
            + env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, "-c", _DIST_CHILD, str(ndev), str(scale),
             str(edge_factor), str(iters), here],
            env=env, capture_output=True, text=True, timeout=900)
        assert proc.returncode == 0, proc.stderr[-3000:]
        lines = [l for l in proc.stdout.splitlines() if l.startswith("ROWS:")]
        assert lines, proc.stdout
        rows = json.loads(lines[0][len("ROWS:"):])
    speedup = None
    for r in rows:
        assert "oracle=ok" in r["derived"], r
        _emit("fig_dist_" + r["name"], r["prep_us"], r["count_us"],
              r["derived"])
        m = re.search(r"speedup=([0-9.]+)x", r["derived"])
        if m and f"_planned{ndev}" in r["name"]:
            speedup = float(m.group(1))
    assert speedup is not None, "planned row missing from subprocess output"
    if min_speedup:
        assert speedup >= min_speedup, \
            f"fig_dist planned lane {speedup:.2f}x one-shot is below the " \
            f"{min_speedup}x gate"


def fig_tile(*, scale: int = 9, edge_factor: int = 8, iters: int = 3,
             max_overhead: float = 0.0) -> None:
    """Monolithic vs tiled out-of-core intersection on one R-MAT graph.

    The tiled plan's ``max_device_bytes`` is forced to a quarter of the
    largest monolithic bucket's resident bytes, so the big buckets stream
    in ≥2 (typically ≥4) chunks through chunk-shaped cached executables.
    Asserts bit-identical counts (tiled == monolithic == scipy), ≥2 chunks
    actually streamed, and ZERO executable-cache misses across the timed
    replays; gates tiled/monolithic overhead at ``max_overhead`` when
    non-zero.
    """
    g = rmat_graph(scale, edge_factor, seed=5)
    oracle = int(triangle_count_scipy(g))

    t0 = time.perf_counter()
    mono = TriangleCounter(g, CountOptions(algorithm="intersection"))
    res_m = mono.count()
    mono_prep_us = (time.perf_counter() - t0) * 1e6
    assert int(res_m) == oracle, (int(res_m), oracle)

    # budget = largest bucket / 4: every bucket above it streams, and the
    # top bucket streams in ≥4 chunks (pow2 chunk rows round down)
    bucket_bytes = [int(e) * (8 * int(w) + 8)
                    for e, w in res_m.meta["bucket_shapes"]]
    budget = max(bucket_bytes) // 4
    t0 = time.perf_counter()
    tiled = TriangleCounter(g, CountOptions(algorithm="intersection",
                                            max_device_bytes=budget))
    res_t = tiled.count()
    tiled_prep_us = (time.perf_counter() - t0) * 1e6
    assert int(res_t) == oracle, (int(res_t), oracle)
    chunks = int(res_t.meta["num_chunks"])
    assert chunks >= 2, res_t.meta

    before = executable_cache_info()["misses"]
    mono_us = _time(mono.plan.count, iters=iters)
    tile_us = _time(tiled.plan.count, iters=iters)
    recompiles = executable_cache_info()["misses"] - before
    assert recompiles == 0, \
        f"fig_tile: {recompiles} recompiles during steady-state replays"
    overhead = tile_us / mono_us
    _emit("fig_tile_mono", mono_prep_us, mono_us,
          f"oracle=ok;budget={budget}")
    _emit("fig_tile_tiled", tiled_prep_us, tile_us,
          f"oracle=ok;chunks={chunks};recompiles={recompiles};"
          f"overhead={overhead:.2f}x")
    if max_overhead:
        assert overhead <= max_overhead, \
            f"fig_tile streaming overhead {overhead:.2f}x exceeds the " \
            f"{max_overhead}x gate"


_SMOKE_DATASETS = ["tiny-rmat", "tiny-grid"]
_SMOKE_SCALES = [7, 8]
_BATCH_SIZES = (2, 4, 8, 16)
_SMOKE_BATCH_SIZES = (4, 8)

_FIGURES = ("table1", "fig5", "fig6", "strat", "fig_batch", "fig_truss",
            "fig_stream", "fig_auto", "fig_serve", "fig_dist", "fig_tile")


def _parse_figures(spec: str):
    """Split and validate a ``--figures`` list. Unknown names raise
    ``ValueError`` naming every valid figure, mirroring
    ``repro.graphs.datasets.load_dataset``'s unknown-dataset error."""
    figures = [f for f in spec.split(",") if f]
    unknown = sorted(set(figures) - set(_FIGURES))
    if unknown:
        raise ValueError(
            f"unknown figure(s) {', '.join(repr(f) for f in unknown)}; "
            f"available: {', '.join(_FIGURES)}"
        )
    return figures


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--figures", default=None,
                    help=f"comma list from {{{','.join(_FIGURES)}}}")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced subset on the tiny fixtures (CI job): "
                         "table1+fig5 by default, any --figures supported")
    ap.add_argument("--json-dir", default=".",
                    help="directory for the BENCH_<figure>.json sidecars "
                         "(default: current directory)")
    args = ap.parse_args()

    if args.smoke:
        spec = args.figures or "table1,fig5"
        datasets, scales, budget, iters = _SMOKE_DATASETS, _SMOKE_SCALES, False, 1
        batch_sizes = _SMOKE_BATCH_SIZES
    else:
        spec = args.figures or ",".join(_FIGURES)
        datasets, scales, budget, iters = DATASETS_FIG5, FIG6_SCALES, True, 2
        batch_sizes = _BATCH_SIZES
    try:
        figures = _parse_figures(spec)
    except ValueError as e:
        ap.error(str(e))

    print("name,prep_us,count_us,derived")
    if "table1" in figures:
        table1(datasets)
    if "fig5" in figures:
        fig5(datasets, budget=budget, iters=iters)
    if "fig6" in figures:
        fig6(scales, iters=iters)
    if "strat" in figures:
        strat(datasets, iters=iters)
    if "fig_batch" in figures:
        fig_batch(batch_sizes, iters=iters)
    if "fig_truss" in figures:
        fig_truss(datasets, budget=budget, iters=iters)
    if "fig_stream" in figures:
        if args.smoke:
            fig_stream(num_batches=6, batch_edges=32, scale=12,
                       min_speedup=3.0)
        else:
            fig_stream()
    if "fig_auto" in figures:
        fig_auto(datasets, iters=iters, json_dir=args.json_dir)
    if "fig_serve" in figures:
        if args.smoke:
            fig_serve(requests=32, sweep_requests=24, burst_requests=48,
                      min_speedup=2.0)
        else:
            fig_serve(pool_size=12, requests=96, sweep_requests=48,
                      burst_requests=96)
    if "fig_dist" in figures:
        if args.smoke:
            fig_dist(scale=8, edge_factor=8, iters=2, min_speedup=1.0)
        else:
            fig_dist(scale=10, edge_factor=16, iters=3, min_speedup=1.0)
    if "fig_tile" in figures:
        if args.smoke:
            fig_tile(scale=11, edge_factor=8, iters=2, max_overhead=2.0)
        else:
            fig_tile(scale=12, edge_factor=16, iters=3)
    _write_json(figures, args.json_dir, args.smoke)


if __name__ == "__main__":
    main()
