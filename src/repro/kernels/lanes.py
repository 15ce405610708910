"""Lane-axis helpers shared by the Pallas TPU kernels.

Mosaic lowers a per-element gather along the lane (last) axis only when the
source fits one 128-lane vreg. ``lane_gather`` splits wider sources into
128-lane slabs, gathers from each and keeps the slab each index points into.
``row_counts`` turns a (TE, W) per-element count matrix into the lane-dense
(1, TE) row the kernels store, so every output block is (1, TE) with TE a
multiple of 128 — the layout the XLA (8, 128) tiling expects.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["LANE", "lane_gather", "row_counts"]

LANE = 128


def lane_gather(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``take_along_axis(x, idx, axis=1)`` for (R, L) ``x``, any L.

    ``idx`` has ``x``'s shape and every entry in [0, L); L is at most 128
    or a multiple of it. Costs (L/128)² in-vreg gathers plus selects.
    """
    width = x.shape[1]
    if width <= LANE:
        return jnp.take_along_axis(x, idx, axis=1, mode="promise_in_bounds")
    slabs = width // LANE
    cols = []
    for k in range(slabs):  # output slab k: idx columns [128k, 128k + 128)
        ik = idx[:, k * LANE:(k + 1) * LANE]
        lo, slab = ik & (LANE - 1), ik >> 7
        got = jnp.zeros(ik.shape, x.dtype)
        for c in range(slabs):  # source slab c
            part = x[:, c * LANE:(c + 1) * LANE]
            got = jnp.where(slab == c, jnp.take_along_axis(
                part, lo, axis=1, mode="promise_in_bounds"), got)
        cols.append(got)
    return jnp.concatenate(cols, axis=1)


def row_counts(m: jnp.ndarray) -> jnp.ndarray:
    """Row sums of a (TE, W) int32 matrix as a lane-dense (1, TE) row."""
    return jnp.sum(m.T, axis=0, keepdims=True)
