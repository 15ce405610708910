#!/usr/bin/env python3
"""Split a traced run's device-idle time by the program's own spans.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace 1 --trace-dir <dir>
    python3 bench/program_gaps.py <dir>

Reads the profile the run kept and prints one JSON object: the window and
the device's busy seconds (as the run's own trace line has them),
``program_gaps`` (device-idle seconds of the window by the innermost
``tc.*`` span open over them), ``idle_share`` (each of those as a percent
of the window, and their sum), ``device_executables`` (device seconds per
executable) with ``modules_busy_s`` (the union of the executables' runs,
beside ``busy_s``, the union of their operations), ``dropped_s`` (where
the profiler dropped the device's events: time that reads as idle though
the device may have been busy), ``spans`` (per ``tc.*``
span name: how many, and their host seconds inside the window) and
``stages`` (per stage index and width: its chunks, and the host seconds of
its spans and of the dispatches and syncs inside them).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import program, trace  # noqa: E402


def _inside(window, spans):
    w0, w1 = window
    for name, s, e in spans:
        d = min(e, w1) - max(s, w0)
        if d > 0:
            yield name, s, e, d / 1e9


def _stages(prog: program.Program, window) -> dict:
    stages = [(s, e, a) for (n, s, e), a in zip(prog.spans, prog.attrs)
              if n == "tc.stage"]
    out: dict = {}
    for name, s, e, d in _inside(window, prog.spans):
        if name not in ("tc.stage", "tc.dispatch", "tc.sync"):
            continue
        owner = next((a for s0, e0, a in stages if s0 <= s and e <= e0),
                     None)
        if owner is None:
            continue
        key = f"{owner.get('index')}:w{owner.get('width')}"
        row = out.setdefault(key, {"chunks": owner.get("chunks"),
                                   "strategy": owner.get("strategy"),
                                   "n": 0, "host_s": 0.0, "dispatch_s": 0.0,
                                   "sync_s": 0.0})
        if name == "tc.stage":
            row["n"] += 1
            row["host_s"] += d
        else:
            row[name[3:] + "_s"] += d
    return out


def split(log_dir: str) -> dict:
    raw = trace.load(log_dir)
    summary = trace.summarize(raw)
    prog = program.load(log_dir)
    window = next((s, e) for n, s, e in raw.host if n == trace.WINDOW_SPAN)
    gaps = program.gaps(raw.device_ops, prog.spans, window)
    w = summary.window_s
    spans: dict = {}
    for name, _, _, d in _inside(window, prog.spans):
        c = spans.setdefault(name, {"n": 0, "host_s": 0.0})
        c["n"] += 1
        c["host_s"] += d

    def covered(planes) -> float:
        per = [sum(e - s for s, e in trace._clip(trace.union(
            [(s, e) for _, s, e in events]), *window)) / 1e9
            for events in planes.values()]
        return sum(per) / max(len(per), 1)

    return {
        "window_s": w,
        "busy_s": summary.busy_s,
        "program_gaps": gaps,
        "idle_share": dict({k: 100.0 * v / w for k, v in gaps.items()},
                           total=100.0 * sum(gaps.values()) / w),
        "device_executables": program.executables(prog.modules, window),
        "modules_busy_s": covered(prog.modules),
        "dropped_s": covered(prog.dropped),
        "spans": spans,
        "stages": _stages(prog, window),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir")
    args = ap.parse_args(argv)
    print(json.dumps(split(args.trace_dir)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
