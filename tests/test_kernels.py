"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs the jnp oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.intersect import (
    intersect_counts, intersect_counts_bitmap_pallas, intersect_counts_pallas,
    intersect_counts_probe_pallas, intersect_counts_ref,
)
from repro.kernels.masked_spgemm import masked_spgemm_pallas, masked_spgemm_ref


# ------------------------------------------------------------- intersect

@pytest.mark.parametrize("e,w,dtype", [
    (64, 8, jnp.int32), (256, 32, jnp.int32), (512, 16, jnp.int32),
    (128, 128, jnp.int32),
])
def test_intersect_pallas_matches_ref(e, w, dtype):
    rng = np.random.default_rng(e * w)
    n = 1000
    u = np.sort(rng.integers(0, n, size=(e, w)), axis=1).astype(np.int32)
    v = np.sort(rng.integers(0, n, size=(e, w)), axis=1).astype(np.int32)
    # dedup within rows (sorted lists must be strictly increasing to model
    # neighbor lists); replace dups with unique sentinels
    for arr, base in ((u, n), (v, 2 * n)):
        dup = np.zeros_like(arr, dtype=bool)
        dup[:, 1:] = arr[:, 1:] == arr[:, :-1]
        arr[dup] = base + np.arange(dup.sum())
        arr.sort(axis=1)
    ref = intersect_counts_ref(jnp.asarray(u), jnp.asarray(v))
    pal = intersect_counts_pallas(jnp.asarray(u), jnp.asarray(v),
                                  tile_edges=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))
    probe = intersect_counts(jnp.asarray(u), jnp.asarray(v), backend="jnp")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(probe))
    # the other two strategy kernels compute the same counts
    probe_pal = intersect_counts_probe_pallas(
        jnp.asarray(u), jnp.asarray(v), tile_edges=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(probe_pal))
    bits = ((2 * n + e * w) + 31) // 32 * 32  # cover the dedup sentinels too
    bm_pal = intersect_counts_bitmap_pallas(
        jnp.asarray(u), jnp.asarray(v), num_bits=bits, tile_edges=64,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(bm_pal))


def test_intersect_padding_rows():
    """Non-multiple edge counts pad with disjoint sentinels — zero matches."""
    u = jnp.asarray(np.arange(10 * 4).reshape(10, 4), dtype=jnp.int32)
    v = jnp.asarray(np.arange(10 * 4).reshape(10, 4), dtype=jnp.int32)
    out = intersect_counts(u, v, backend="pallas", tile_edges=8)
    np.testing.assert_array_equal(np.asarray(out), np.full(10, 4))


# ---------------------------------------------------------- masked spgemm

@pytest.mark.parametrize("t,b,dtype", [
    (8, 16, jnp.float32), (16, 32, jnp.float32), (24, 8, jnp.float32),
    (8, 128, jnp.bfloat16),
])
def test_masked_spgemm_pallas_matches_ref(t, b, dtype):
    rng = np.random.default_rng(t * b)
    mk = lambda: (rng.random((t, b, b)) < 0.2).astype(np.float32)
    l, u, a = mk(), mk(), mk()
    ref = masked_spgemm_ref(jnp.asarray(l), jnp.asarray(u), jnp.asarray(a))
    pal = masked_spgemm_pallas(
        jnp.asarray(l, dtype), jnp.asarray(u, dtype), jnp.asarray(a, dtype),
        tile_triples=8, interpret=True)
    rtol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal), rtol=rtol,
                               atol=1e-3)
