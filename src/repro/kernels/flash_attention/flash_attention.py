"""Pallas TPU flash attention (forward) — the MXU realization of the chunked
online-softmax schedule in repro.models.layers.attention.

Tiling: grid = (batch·kv_heads, S/BLOCK_Q). Each program owns a BLOCK_Q tile
of queries (all G grouped q-heads at once) and streams the full K/V sequence
through VMEM in BLOCK_K slabs via `jax.lax.fori_loop`, maintaining running
(max, sumexp, out) — O(BLOCK_Q·BLOCK_K) live memory, never S×T.

The q tile arrives as (BLOCK_Q, G·hd) so the q@kᵀ and p@v products are plain
2-D MXU matmuls (G folds into the N dimension). Causal/window masking is
positional arithmetic on the fly; softcap (gemma2) is a tanh on the tile.

VMEM at BLOCK_Q=256, BLOCK_K=512, hd=256, G=8: q 1 MB + k/v 0.5 MB each +
out 2 MB (f32) — ~4.5 MB, double-buffer safe.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flash_attention_pallas"]


def _flash_kernel(q_ref, k_ref, v_ref, out_ref, *, block_k: int, seq_k: int,
                  causal: bool, window: Optional[int], cap: Optional[float],
                  g: int, hd: int, block_q: int):
    qi = pl.program_id(1)
    q = q_ref[...]  # (BQ, G*hd)
    scale = 1.0 / (hd ** 0.5)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    nblocks = seq_k // block_k

    def body(kb, carry):
        m_run, l_run, acc = carry
        k_tile = k_ref[pl.ds(kb * block_k, block_k), :]  # (BK, hd)
        v_tile = v_ref[pl.ds(kb * block_k, block_k), :]
        # logits: (BQ*G, BK) via 2-D matmul on the MXU
        qf = q.astype(jnp.float32).reshape(block_q * g, hd)
        logits = jax.lax.dot_general(
            qf, k_tile.astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if cap is not None:
            logits = jnp.tanh(logits / cap) * cap
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        qk = jnp.repeat(q_pos, g, axis=0) - k_pos  # (BQ*G, BK)
        valid = jnp.ones_like(qk, dtype=jnp.bool_)
        if causal:
            valid &= qk >= 0
        if window is not None:
            valid &= qk < window
        logits = jnp.where(valid, logits, -1e30)
        m_new = jnp.maximum(m_run, logits.max(axis=1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(logits - m_new[:, None])
        l_new = l_run * alpha + p.sum(axis=1)
        pv = jax.lax.dot_general(
            p, v_tile.astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # (BQ*G, hd)
        acc = acc * alpha[:, None] + pv
        return m_new, l_new, acc

    m0 = jnp.full((block_q * g,), -1e30, jnp.float32)
    l0 = jnp.zeros((block_q * g,), jnp.float32)
    a0 = jnp.zeros((block_q * g, hd), jnp.float32)
    m_f, l_f, acc = jax.lax.fori_loop(0, nblocks, body, (m0, l0, a0))
    out = acc / jnp.maximum(l_f, 1e-30)[:, None]
    out_ref[...] = out.reshape(block_q, g * hd).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "cap", "block_q", "block_k",
                     "interpret"))
def flash_attention_pallas(
    q: jnp.ndarray,  # (B, S, Hq, hd)
    k: jnp.ndarray,  # (B, T, Hkv, hd)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    cap: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    b, s, hq, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    # fold GQA: (B·Hkv, S, G·hd) queries against (B·Hkv, T, hd) keys
    qr = q.reshape(b, s, hkv, g * hd).transpose(0, 2, 1, 3).reshape(
        b * hkv, s, g * hd)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, t, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, t, hd)
    grid = (b * hkv, s // block_q)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, block_k=block_k, seq_k=t, causal=causal,
            window=window, cap=cap, g=g, hd=hd, block_q=block_q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, g * hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, t, hd), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, t, hd), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, g * hd), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, s, g * hd), q.dtype),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hkv, s, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        b, s, hq, hd)
