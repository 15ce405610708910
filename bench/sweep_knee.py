#!/usr/bin/env python3
"""Find the knee of an open-loop service cell: the highest offered rate at
which no request is shed and the backlog does not grow.

    python3 bench/sweep_knee.py --workload svc-ego-zipf --seed 11 \
        --seconds 10 --rates 250,500,1000,2000

One process sets the cell's service up once, then offers each rate in turn
for ``--seconds`` with the cell's own pool, popularity and tenants (only
the rate changes), waiting for every request before the next rate. A rate
holds when nothing was shed or failed and the backlog stayed flat: the
median latency of the last quarter of the window is under twice that of
the first quarter plus 5 ms. The sweep stops after two rates in a row fail.
Each row is printed as it comes; the last line is
``{"knee": <rate or null>, "rows": [...]}``. The cell's rate is then
written into its traffic file by hand, at about 0.8 of the knee.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as bench_run  # noqa: E402
from bench.harness import manifest as manifests  # noqa: E402
from bench.harness import traffic as mixes  # noqa: E402
from bench.harness.record import percentile  # noqa: E402


def judge(reqs, seconds: float) -> dict:
    """One rate's row: shed/failed, latency quantiles, backlog growth."""
    failed = sum(r.error is not None or r.done is None for r in reqs)
    lat = [r.latency for r in reqs if r.error is None and r.done is not None]
    first = [r.latency for r in reqs if r.due < seconds / 4
             and r.error is None and r.done is not None]
    last = [r.latency for r in reqs if r.due >= 3 * seconds / 4
            and r.error is None and r.done is not None]
    grows = bool(first and last) and statistics.median(last) \
        >= 2 * statistics.median(first) + 0.005
    return {"requests": len(reqs), "failed": failed,
            "p50_ms": (percentile(lat, 50) or 0.0) * 1e3,
            "p95_ms": (percentile(lat, 95) or 0.0) * 1e3,
            "first_quarter_p50_ms": statistics.median(first) * 1e3
            if first else None,
            "last_quarter_p50_ms": statistics.median(last) * 1e3
            if last else None,
            "holds": failed == 0 and not grows}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True, help="ascending, req/s")
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep_knee: needs a TPU", file=sys.stderr)
        return 2
    bench_run.enable_compile_cache(bench_run.CACHE_DIR)
    man = manifests.load()
    cell = man.cell(args.workload)
    system = man.system(cell.config["system"])(cell.config, cell.traffic,
                                               man)
    inputs = system.make_inputs(args.seed, 0.0)
    print(f"setup: {system.setup(inputs)}", flush=True)
    rows, knee, misses = [], None, 0
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic, rate_per_s=rate)
        sched = mixes.make_schedule(traffic, args.seed, args.seconds,
                                    len(inputs["pool"]))
        reqs, _ = mixes.open_loop(system.submit, sched, args.seconds)
        row = dict(rate_per_s=rate, **judge(reqs, args.seconds))
        rows.append(row)
        print(json.dumps(row), flush=True)
        if row["holds"]:
            knee, misses = rate, 0
        else:
            misses += 1
            if misses == 2:
                break
    system.close()
    print(json.dumps({"knee": knee, "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
