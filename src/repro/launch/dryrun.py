"""Multi-pod dry-run of the paper core: prove the planned
``"matrix_distributed"`` lane lowers, compiles, shards coherently, and fits
memory on the production meshes — without hardware.

MUST set the placeholder-device flag before any other import (jax locks the
device count on first init). Only this entrypoint sees 512 devices; tests and
benches see 1.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --tc
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

# ruff: noqa: E402
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import roofline_terms


def _cost_dict(compiled):
    c = compiled.cost_analysis()
    if isinstance(c, (list, tuple)):
        c = c[0]
    return dict(c) if c else {}


def _memory_dict(compiled):
    try:
        m = compiled.memory_analysis()
    except Exception:
        return {}
    if m is None:
        return {}
    keys = ["argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes"]
    return {k: int(getattr(m, k)) for k in keys if hasattr(m, k)}


def lower_tc(mesh, *, tiles: int = 8192, block: int = 128) -> dict:
    """Dry-run the paper core: the planned ``"matrix_distributed"`` lane on
    the production mesh — the SAME cached per-shard executable
    ``plan_triangle_count(g, "matrix_distributed", mesh=mesh)`` binds, here
    lowered against ShapeDtypeStructs (a synthetic dealt tile schedule, no
    graph), so the structural check covers exactly what production runs:
    the length-gated tile loop and the single scalar psum."""
    from repro.core import engine

    chips = mesh.devices.size
    axes = tuple(mesh.axis_names)
    t_per = -(-tiles // chips)
    sh = NamedSharding(mesh, P(axes))
    abs_tiles = jax.ShapeDtypeStruct((chips, t_per, block, block),
                                     jnp.float32, sharding=sh)
    abs_valid = jax.ShapeDtypeStruct((chips,), jnp.int32, sharding=sh)

    fn = engine.get_executable("matrix_distributed", "jnp", False,
                               (t_per, block, block), mesh=mesh)
    t0 = time.time()
    lowered = fn.lower(abs_tiles, abs_tiles, abs_tiles, abs_valid)
    compiled = lowered.compile()
    dt = time.time() - t0
    cost = _cost_dict(compiled)
    rl = roofline_terms(cost, compiled.as_text(),
                        model_flops_per_chip=2 * t_per * block**3)
    return dict(arch="tc-masked-spgemm", shape=f"tiles{tiles}",
                mesh="x".join(map(str, mesh.devices.shape)), chips=chips,
                status="ok", compile_s=round(dt, 2),
                memory=_memory_dict(compiled), roofline=rl.as_dict())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tc", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args()
    if not args.tc:
        ap.error("--tc required")

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(make_production_mesh(multi_pod=False))
    if args.mesh in ("multi", "both"):
        meshes.append(make_production_mesh(multi_pod=True))

    failures = 0
    for mesh in meshes:
        try:
            rec = lower_tc(mesh)
        except Exception as e:  # a dry-run failure is a bug: report it
            failures += 1
            rec = dict(arch="tc", shape=None,
                       mesh="x".join(map(str, mesh.devices.shape)),
                       status="error", error=repr(e),
                       trace=traceback.format_exc()[-2000:])
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
