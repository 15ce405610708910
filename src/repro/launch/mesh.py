"""Device meshes: production topologies and local test meshes.

``make_production_mesh`` is a FUNCTION (never a module constant) so importing
this module never touches jax device state — dryrun.py must set XLA_FLAGS
before first jax init, and tests must keep seeing 1 device.

Production target: TPU v5e pods, 256 chips/pod.
  single-pod:  (16, 16)    axes ("data", "model")
  multi-pod:   (2, 16, 16) axes ("pod", "data", "model")

"pod" composes with "data" for hierarchical gradient reduction
(reduce-scatter intra-pod over ICI, all-reduce across pods over DCI); "model"
carries TP/EP collectives and is kept inside a pod where ICI is fastest.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh", "mesh_axes"]


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """jax.make_mesh with explicit Auto axis types."""
    return jax.make_mesh(
        tuple(shape),
        tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1) -> Mesh:
    """Whatever devices exist, split (data, model). Used by tests/examples."""
    ndev = jax.device_count()
    assert ndev % model_parallel == 0, (ndev, model_parallel)
    return make_mesh((ndev // model_parallel, model_parallel), ("data", "model"))


def mesh_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Axes carrying the batch dimension: ("pod","data") when pod exists."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
