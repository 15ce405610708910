"""Pallas TPU kernel: fused masked block-SpGEMM for triangle counting.

This kernel implements, in one fused pass, all three TC-specific SpGEMM
optimizations the paper identifies but leaves as future work (§5):

  (1) compute only the upper-triangular part  — the host scheduler emits
      triples only for A's strict-upper tiles;
  (2) avoid multiplications where A is known zero — only nonzero (A, L, U)
      tile triples are scheduled at all (block-level masking), and the
      elementwise mask inside the tile kills the rest;
  (3) never write B = L·U to global memory    — the tile product lives only
      in VMEM/registers; the kernel emits one f32 partial count per triple.

TPU mapping: each grid step processes TT triples. The B×B×B tile product runs
on the MXU (B = 128 → one native systolic pass); mask + reduce run on the VPU.
Arithmetic intensity per triple: 2·B³ FLOPs over 3·B²·4 bytes ≈ 21 FLOP/byte
(B=128), comfortably compute-bound against TPU v5e's ~240 FLOP/byte ridge only
at low B — which is exactly why the tile schedule (not this kernel) is where
hillclimbing happens; see EXPERIMENTS.md §Perf.

VMEM: 3 · TT·B²·4B + TT·4B. TT=8, B=128 → ~1.6 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["masked_spgemm_pallas"]


def _masked_spgemm_kernel(l_ref, u_ref, a_ref, out_ref):
    l = l_ref[...]  # (TT, B, B)
    u = u_ref[...]
    a = a_ref[...]
    prod = jax.lax.dot_general(
        l,
        u,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),  # batched (B,B)@(B,B)
        preferred_element_type=jnp.float32,
    )
    rows = (prod * a).sum(axis=2)  # (TT, B)
    out_ref[...] = jnp.sum(rows.T, axis=0, keepdims=True)  # (1, TT)


@functools.partial(jax.jit, static_argnames=("tile_triples", "interpret"))
def masked_spgemm_pallas(
    l_tiles: jnp.ndarray,
    u_tiles: jnp.ndarray,
    a_tiles: jnp.ndarray,
    *,
    tile_triples: int = 8,
    interpret: bool = True,
) -> jnp.ndarray:
    """Pallas fused masked block-SpGEMM: per-triple ``sum(A ∘ (L @ U))``.

    Args:
      l_tiles: (T, B, B) float32/bf16 dense L tiles; T must be a multiple of
        ``tile_triples`` (callers pad with zero tiles, which contribute
        exactly 0 to the count).
      u_tiles: (T, B, B) U tiles, same dtype.
      a_tiles: (T, B, B) mask tiles (strict upper triangle of A).
      tile_triples: triples per grid step (VMEM tile depth).
      interpret: run the kernel body on CPU for validation; pass False on a
        real TPU.

    Returns:
      (T,) float32 per-triple masked partial wedge counts.
    """
    t, b, b2 = l_tiles.shape
    assert b == b2 and t % tile_triples == 0, (t, b, b2, tile_triples)
    groups = t // tile_triples
    # one (1, TT) row per grid step: a block whose last two dims equal the
    # array's, so any TT satisfies the (8, 128) tiling rule
    out = pl.pallas_call(
        _masked_spgemm_kernel,
        grid=(groups,),
        in_specs=[
            pl.BlockSpec((tile_triples, b, b), lambda i: (i, 0, 0)),
            pl.BlockSpec((tile_triples, b, b), lambda i: (i, 0, 0)),
            pl.BlockSpec((tile_triples, b, b), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, tile_triples), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((groups, 1, tile_triples), jnp.float32),
        interpret=interpret,
    )(l_tiles, u_tiles, a_tiles)
    return out.reshape(t)
