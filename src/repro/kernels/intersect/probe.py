"""Binary-probe set-intersection core (the ``probe`` strategy).

Scan the u-list, binary-search each element in the sorted v-list — the TPU
analogue of the paper's proposed third GPU kernel ("scan the smaller list,
search the larger") and of Wang & Owens' BFS-based follow-up (arXiv:1909.02127)
where binary probing wins on wide, skewed neighborhoods. O(W·log W) work per
edge vs the broadcast core's O(W²).

Two implementations of the same semantics:

* ``intersect_counts_probe``        — vmapped ``jnp.searchsorted`` (the
                                      production CPU path; GSPMD-shardable).
* ``intersect_counts_probe_pallas`` — a Pallas kernel running a branchless
                                      fixed-iteration lower-bound search per
                                      lane: every u element in a (TE, W) tile
                                      searches its v row in ``bit_length(W)``
                                      compare/select rounds, each a lane
                                      gather (128-lane slabs, see
                                      ``repro.kernels.lanes``) + VPU select
                                      at full vector width.

Both require rows sorted ascending. Padding follows the repo-wide sentinel
rule: u rows pad with one value, v rows with a *different* value, so padding
never probes successfully.

VMEM budget (pallas): 2 · 2 · TE·W·4B double-buffered inputs + about
6 · TE·W·4B search state (lo, hi, mid, the gathered v values, the slab
select and the found mask) + 2 · TE·4B out; with TE=256, W=512 that is
~5 MB — under the 16 MB scoped VMEM default of a v5e core.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lanes import lane_gather, row_counts

__all__ = ["intersect_counts_probe", "intersect_counts_probe_pallas"]


@jax.jit
def intersect_counts_probe(u_lists: jnp.ndarray, v_lists: jnp.ndarray) -> jnp.ndarray:
    """Binary-search each element of u in the sorted v list.

    Args:
      u_lists: (E, W) int32, each row sorted ascending (neighbor list +
        trailing sentinel padding).
      v_lists: (E, W) int32, same layout, padded with a sentinel disjoint
        from u's so padding never matches.

    Returns:
      (E,) int32 — per-edge |N(u) ∩ N(v)|. O(W log W) per row.
    """

    def one(u, v):
        pos = jnp.searchsorted(v, u)
        pos = jnp.clip(pos, 0, v.shape[0] - 1)
        return (v[pos] == u).sum(dtype=jnp.int32)

    return jax.vmap(one)(u_lists, v_lists)


def _probe_kernel(u_ref, v_ref, out_ref, *, width: int):
    u = u_ref[...]  # (TE, W) int32, rows sorted
    v = v_ref[...]  # (TE, W) int32, rows sorted
    # Branchless lower-bound binary search, all TE·W lanes in lockstep.
    # Fixed iteration count bit_length(W) ≥ ceil(log2(W+1)) covers the
    # [0, W] search range; converged lanes are frozen by the `active` mask.
    lo = jnp.zeros(u.shape, jnp.int32)
    hi = jnp.full(u.shape, width, jnp.int32)
    for _ in range(max(1, int(width).bit_length())):
        active = lo < hi
        mid = (lo + hi) // 2  # active lanes have mid ∈ [lo, hi) ⊂ [0, W)
        v_mid = lane_gather(v, jnp.clip(mid, 0, width - 1))
        go_right = active & (v_mid < u)
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
    pos = jnp.clip(lo, 0, width - 1)
    found = (lane_gather(v, pos) == u) & (lo < width)
    out_ref[...] = row_counts(found.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("tile_edges", "interpret"))
def intersect_counts_probe_pallas(
    u_lists: jnp.ndarray,
    v_lists: jnp.ndarray,
    *,
    tile_edges: int = 256,
    interpret: bool = True,
) -> jnp.ndarray:
    """Pallas binary-probe kernel: per-edge |N(u) ∩ N(v)| for (E, W) lists.

    Args:
      u_lists: (E, W) int32 sorted rows; E must be a multiple of
        ``tile_edges`` (callers pad with sentinel rows — see ops.py).
      v_lists: (E, W) int32 sorted rows, disjoint padding sentinel.
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
        functools.partial(_probe_kernel, width=w),
        grid=(e // tile_edges,),
        in_specs=[
            pl.BlockSpec((tile_edges, w), lambda i: (i, 0)),
            pl.BlockSpec((tile_edges, w), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_edges), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, e), jnp.int32),
        interpret=interpret,
    )(u_lists, v_lists)
    return out.reshape(e)
