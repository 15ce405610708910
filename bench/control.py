#!/usr/bin/env python3
"""The control: the plain reference in the program's place, below exact.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds s]

Each configuration states its guarantee (exact counts) and, under
``control.accumulate``, the lower precision that would break it: the
reference's per-row counts summed one after another in that float type.
This script drives the cell's own run (``run.run_cell``: the same inputs
from each seed, the same window and the same comparison) with the
``Control`` of the configuration's system file answering in the program's
place, and prints each seed's compared numbers.
The control has to come out not correct; its readings are the upper ends
between which and the program's own readings each limit was set. The
benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as bench_run  # noqa: E402
from bench.harness import manifest as manifests  # noqa: E402
from bench.harness.record import Events  # noqa: E402


def run_control(man, cell, seed: int, seconds: float, devices) -> dict:
    """One run of ``cell`` with the control in the program's place."""
    return bench_run.run_cell(man, cell, seed, seconds, False, devices,
                              Events(), {}, control=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    ap.add_argument("--seconds", type=float, default=None,
                    help="window (default: the manifest's run_seconds)")
    args = ap.parse_args(argv)
    import jax

    man = manifests.load()
    cell = man.cell(args.workload)
    seconds = args.seconds if args.seconds is not None \
        else float(man.data["run_seconds"])
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run_control(man, cell, seed, seconds, jax.devices()[:1])
        rows.append({"seed": seed, "correct": res["correct"],
                     "attempted": res["attempted"], "checks": res["checks"]})
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"workload": cell.name, "control":
                      cell.config["control"]["accumulate"],
                      "all_incorrect": not any(r["correct"] for r in rows),
                      "runs": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
