#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``). The run makes its inputs from ``--seed``,
sets the system up and warms every shape the window uses (``setup_s``),
drives the window for ``--seconds``, then checks every answer of the window
against the plain reference. With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` the window runs under the JAX
profiler and the result carries the per-layer metrics, the device's busy
time and a breakdown of device ops and idle gaps. Each metric is read by
its own file in ``bench/metrics/``.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each number compared with the reference, beside its
limit. The same numbers end stderr. Without a TPU, or with fewer chips than
the cell asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# libtpu would otherwise log to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench.harness import manifest as manifests  # noqa: E402
from bench.harness import trace as traces  # noqa: E402
from bench.harness.record import Events, Run  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache" / "bench"


def log(msg: str) -> None:
    print(msg, flush=True)


def enable_compile_cache(path: pathlib.Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    holding every program however fast it compiled."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def peak_memory(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run_cell(man: manifests.Manifest, cell: manifests.Cell, seed: int,
             seconds: float, trace: bool, devices, events: Events,
             peaks: dict, trace_dir=None, control: bool = False) -> dict:
    """Drive one run of ``cell`` on ``devices``; return its result object.
    With ``control`` the configuration's control answers in the program's
    place."""
    system = man.system(cell.config["system"], control)(
        cell.config, cell.traffic, man)
    t0 = time.perf_counter()
    inputs = system.make_inputs(seed, seconds)
    work = system.work(inputs)
    log(f"inputs: {work} made in {time.perf_counter() - t0:.6f}s")

    before = events.snapshot()
    t0 = time.perf_counter()
    info = system.setup(inputs)
    setup_s = time.perf_counter() - t0
    setup_events = Events.delta(before, events.snapshot())
    log(f"setup: {setup_s:.6f}s {system.spans}; {info}")
    log(f"setup events: {setup_events}")

    before = events.snapshot()
    summary = None
    if trace:
        import jax

        log_dir = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        with jax.profiler.trace(log_dir):
            records, window_s = system.window(inputs, seconds)
        t0 = time.perf_counter()
        summary = traces.summarize(traces.load(log_dir))
        log(f"trace: busy {summary.busy_s:.6f}s of {summary.window_s:.6f}s "
            f"on {summary.devices} device(s), read in "
            f"{time.perf_counter() - t0:.3f}s")
        log(f"trace: longest gaps {summary.longest_gaps}")
        if trace_dir is None:
            shutil.rmtree(log_dir, ignore_errors=True)
    else:
        records, window_s = system.window(inputs, seconds)
    window_events = Events.delta(before, events.snapshot())
    log(f"window: {len(records)} calls in {window_s:.6f}s; events "
        f"{window_events}")
    if cell.traffic["loop"] == "closed":
        log(f"window calls (s): {[round(e - s, 6) for s, e, _ in records]}")
    else:
        late = sorted(r.sent - r.due for r in records)
        if late:
            log(f"generator lateness: p50 {late[len(late) // 2]:.6f}s "
                f"max {late[-1]:.6f}s")
    memory = peak_memory(devices)
    run = Run(loop=cell.traffic["loop"], setup_s=setup_s,
              spans=dict(system.spans), setup_events=setup_events,
              window_s=window_s, records=records, work=work, peaks=peaks,
              trace=summary)
    system.close()

    t0 = time.perf_counter()
    checks, attempted, failed = system.check(inputs, records)
    log(f"reference: checked in {time.perf_counter() - t0:.6f}s")

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = man.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": attempted, "failed": failed, "metrics": metrics,
              "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in summary.device_ops],
            "idle_gaps": [list(x) for x in summary.idle_gaps]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profile here instead of a temporary dir")
    args = ap.parse_args(argv)

    man = manifests.load()
    cell = man.cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s), found "
              f"{len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 2
    with open(ROOT / "bench" / "peaks.json") as f:
        peaks = json.load(f)["devices"].get(devices[0].device_kind)
    if peaks is None:
        print(f"bench: no peaks for device kind {devices[0].device_kind!r} "
              f"in bench/peaks.json", file=sys.stderr)
        return 2
    enable_compile_cache(CACHE_DIR)
    events = Events().register()
    log(f"device: {devices[0].device_kind} x{len(devices)}; jax "
        f"{jax.__version__}; cell {cell.name}; seed {args.seed}; pid "
        f"{os.getpid()}")
    result = run_cell(man, cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell.chips], events, peaks,
                      trace_dir=args.trace_dir)
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
