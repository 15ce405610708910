"""Broadcast-compare set-intersection core (the ``broadcast`` strategy).

Pallas TPU kernel for batched sorted-list intersection — the TC hot loop, and
the strategy the ``auto`` cost model keeps for narrow degree buckets where
the O(W²) compare is pure gather-free VPU work (see ops.py for the dispatch
and probe.py / bitmap.py for the other cores).

TPU adaptation of the paper's 2-kernel (TwoSmall/TwoLarge) strategy:

* Load balancing is static: callers bucket edges by max endpoint degree
  (``graphs.formats.bucket_edges_by_degree``), so every row in one launch has
  the same padded width W and every grid step does identical work — the MXU/VPU
  equivalent of the paper's "process intersections with same level of workload
  together".
* Each grid step loads a (TE, W) tile of u-lists and v-lists into VMEM,
  transposes both so edges run along the 128 lanes, and walks the W
  positions of v: step j compares the whole (W, TE) u tile with v's row j
  broadcast over sublanes and accumulates matches. Membership tests run at
  full VPU width with zero divergence — the role merge-path played on the
  GPU — and the per-edge counts leave as one lane-dense (1, TE) row.
* Padding uses disjoint sentinels so no equality fires on padding; the kernel
  needs no masks.

VMEM budget: 2 · 2 · TE·W·4B (double-buffered inputs) + 3 · TE·W·4B (the
transposed u tile, the transposed v scratch and the (W, TE) match
accumulator; the per-step compare is one more (W, TE) intermediate) +
2 · TE·4B (out). With TE=256, W=512 that is ~4.5 MB — under the 16 MB
scoped VMEM default of a v5e core.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["intersect_counts_pallas"]


def _intersect_kernel(u_ref, v_ref, out_ref, vt_ref):
    ut = u_ref[...].T  # (W, TE) int32: edges along lanes
    vt_ref[...] = v_ref[...].T

    def step(j, acc):
        return acc + (ut == vt_ref[pl.ds(j, 1), :]).astype(jnp.int32)

    acc = jax.lax.fori_loop(0, ut.shape[0], step,
                            jnp.zeros(ut.shape, jnp.int32))
    out_ref[...] = jnp.sum(acc, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("tile_edges", "interpret"))
def intersect_counts_pallas(
    u_lists: jnp.ndarray,
    v_lists: jnp.ndarray,
    *,
    tile_edges: int = 256,
    interpret: bool = True,
) -> jnp.ndarray:
    """Pallas broadcast-compare kernel: per-edge |N(u) ∩ N(v)|.

    Args:
      u_lists: (E, W) int32; sorted rows padded with a sentinel disjoint from
        v's; E must be a multiple of ``tile_edges`` (callers pad with
        sentinel rows — see ops.py).
      v_lists: (E, W) int32, same layout, disjoint padding sentinel.
      tile_edges: rows per grid step; a multiple of 128 (the lane width of
        the (1, TE) output block).
      interpret: run the kernel body on CPU for validation; pass False on a
        real TPU.

    Returns:
      (E,) int32 per-edge intersection sizes.
    """
    e, w = u_lists.shape
    assert e % tile_edges == 0, (e, tile_edges)
    out = pl.pallas_call(
        _intersect_kernel,
        grid=(e // tile_edges,),
        in_specs=[
            pl.BlockSpec((tile_edges, w), lambda i: (i, 0)),
            pl.BlockSpec((tile_edges, w), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_edges), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, e), jnp.int32),
        scratch_shapes=[pltpu.VMEM((w, tile_edges), jnp.int32)],
        interpret=interpret,
    )(u_lists, v_lists)
    return out.reshape(e)
