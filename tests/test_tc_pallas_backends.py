"""The public TC API through the Pallas kernels (interpret mode), the
set-intersection strategy × width sweep against the ref oracle, and the
multi-host-device sharded intersection path."""

import json
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from repro.graphs import grid_graph, rmat_graph
from repro.core import (
    triangle_count_intersection, triangle_count_matrix, triangle_count_scipy,
)
from repro.kernels.intersect import (
    BITMAP_MAX_BITS,
    STRATEGIES,
    choose_strategy,
    intersect_counts,
    intersect_counts_bitmap,
    intersect_counts_bitmap_ref,
    intersect_counts_probe_ref,
    intersect_counts_ref,
    packed_bits,
    resolve_strategy,
)

# ------------------------------------------------------- end-to-end graphs

GRAPHS = [rmat_graph(8, 6, seed=11), grid_graph(9, seed=2)]


@pytest.mark.parametrize("g", GRAPHS, ids=lambda g: g.name)
@pytest.mark.parametrize("strategy", ("auto",) + STRATEGIES)
def test_pallas_intersection_end_to_end(g, strategy):
    truth = triangle_count_scipy(g)
    assert triangle_count_intersection(g, backend="pallas", interpret=True,
                                       strategy=strategy) == truth


@pytest.mark.parametrize("block", [16, 32])
def test_pallas_matrix_end_to_end(block):
    g = rmat_graph(8, 6, seed=12)
    truth = triangle_count_scipy(g)
    assert triangle_count_matrix(g, block=block, backend="pallas",
                                 interpret=True) == truth


# -------------------------------------------- strategy × width oracle sweep

def _padded_lists(e, w, n, seed):
    """Synthetic degree-bucket rows following the engine's sentinel rules:
    sorted neighbor lists padded in-row with n (u) / n+1 (v), plus one pair
    of fully-padded sentinel rows at the end."""
    rng = np.random.default_rng(seed)

    def make(fill):
        rows = []
        for _ in range(e - 1):
            k = int(rng.integers(0, min(w, n) + 1))
            vals = np.sort(rng.choice(n, size=k, replace=False))
            rows.append(np.concatenate([vals, np.full(w - k, fill)]))
        rows.append(np.full(w, fill))  # fully-padded sentinel row
        return np.asarray(rows, dtype=np.int32)

    return make(n), make(n + 1)


@pytest.mark.parametrize("width", [8, 32, 128])
@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_strategy_matches_ref_oracle(width, strategy, backend):
    n = 100  # id range (n + 2 sentinels) fits every bitmap capacity below
    u, v = _padded_lists(50, width, n, seed=width * 7 + len(strategy))
    uj, vj = jnp.asarray(u), jnp.asarray(v)
    ref = np.asarray(intersect_counts_ref(uj, vj))
    out = intersect_counts(uj, vj, strategy=strategy, backend=backend,
                           tile_edges=16, interpret=True,
                           bitmap_bits=128)
    np.testing.assert_array_equal(np.asarray(out), ref, err_msg=f"{strategy}/{backend}")
    # the independent numpy refs agree too
    np.testing.assert_array_equal(intersect_counts_probe_ref(u, v), ref)
    np.testing.assert_array_equal(
        intersect_counts_bitmap_ref(u, v, num_bits=128), ref)


def test_bitmap_id_range_boundary():
    """Ids at num_bits-1 are counted; ids ≥ num_bits are masked out (and the
    auto cost model never hands such a bucket to bitmap)."""
    bits = 64
    u = jnp.asarray([[5, bits - 1, bits, bits + 7]], dtype=jnp.int32)
    v = jnp.asarray([[5, bits - 1, bits, bits + 7]], dtype=jnp.int32)
    # oracle counts all four matches; bitmap must count only the in-range two
    assert int(intersect_counts_ref(u, v)[0]) == 4
    for backend in ("jnp", "pallas"):
        got = intersect_counts(u, v, strategy="bitmap", backend=backend,
                               bitmap_bits=bits, tile_edges=1)
        assert int(got[0]) == 2, backend
    assert int(intersect_counts_bitmap_ref(u, v, num_bits=bits)[0]) == 2
    # cost model: bitmap only when the id range fits the packed width
    assert choose_strategy(64, bits) == "bitmap"
    assert choose_strategy(64, bits + 9) != "bitmap"
    assert packed_bits(64) == 64
    # forced bitmap beyond the packed width widens the bitmap to cover it
    strat, forced_bits = resolve_strategy(64, bits + 9, strategy="bitmap")
    assert (strat, forced_bits) == ("bitmap", 96)
    got = intersect_counts(u, v, strategy="bitmap", backend="jnp",
                           bitmap_bits=forced_bits)
    assert int(got[0]) == 4  # all ids < 96 are in range again


def test_forced_bitmap_over_huge_id_range_refuses():
    """The packer unrolls num_bits/32 iterations, so a forced bitmap over a
    huge id range raises instead of tracing an unbounded graph — and the
    auto selector never picks bitmap past the cap either."""
    with pytest.raises(ValueError, match="BITMAP_MAX_BITS"):
        resolve_strategy(8, 10**7, strategy="bitmap")
    huge_width = 1 << 20  # packed width over the cap: auto must not bitmap
    assert choose_strategy(huge_width, 1000) != "bitmap"
    assert resolve_strategy(512, BITMAP_MAX_BITS, "bitmap")[1] == BITMAP_MAX_BITS


def test_auto_never_selects_undersized_bitmap():
    """Regression: a caller-supplied bitmap_bits is a capacity for
    strategy="bitmap" only — the auto selector derives the id range from the
    data and must not mask out-of-capacity ids by picking bitmap anyway."""
    row = jnp.asarray([[10, 200, 300, 301]], dtype=jnp.int32)
    assert int(intersect_counts_ref(row, row)[0]) == 4
    got = intersect_counts(row, row, strategy="auto", backend="jnp",
                           bitmap_bits=64)
    assert int(got[0]) == 4


def test_bitmap_counts_trailing_padding_as_zero():
    """The v-row padding run (n+1 repeated) sets its bit once and u never
    queries it; the u-row padding (n) queries an unset bit."""
    n = 40
    u = jnp.asarray([[1, 2, n, n]], dtype=jnp.int32)
    v = jnp.asarray([[2, 3, n + 1, n + 1]], dtype=jnp.int32)
    out = intersect_counts_bitmap(u, v, num_bits=64)
    assert int(out[0]) == 1


# -------------------------------------- multi-host-device sharded dispatch

_DIST_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
from repro.launch.mesh import make_mesh
from repro.graphs import rmat_graph, complete_graph
from repro.core import (triangle_count_intersection_distributed,
                        triangle_count_scipy)

out = {"devices": jax.device_count() == 4}
mesh = make_mesh((4,), ("data",))
g = rmat_graph(8, 8, seed=41)
truth = triangle_count_scipy(g)
for s in ("auto", "broadcast", "probe", "bitmap"):
    out[s] = triangle_count_intersection_distributed(g, mesh, strategy=s) == truth
# dense graph whose id range fits the packed width => auto shards the bitmap core
k = complete_graph(100)
out["bitmap_auto_dense"] = (
    triangle_count_intersection_distributed(k, mesh) == triangle_count_scipy(k))
print("RESULT:" + json.dumps(out))
"""


def test_distributed_intersection_strategies():
    """Sharded intersection agrees with the oracle for every strategy on a
    4-host-device mesh (subprocess so the XLA device-count flag never leaks)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _DIST_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stdout
    out = json.loads(line[0][len("RESULT:"):])
    assert all(out.values()), out


@pytest.mark.parametrize("width,id_range,backend,platform,want", [
    (512, None, "jnp", "cpu", "probe"),
    (512, None, "pallas", "tpu", "probe"),
    (512, None, "jnp", "tpu", "broadcast"),  # gather-bound jnp probe
    (128, 10**6, "jnp", "tpu", "broadcast"),
    (32, 30, "jnp", "tpu", "bitmap"),
    (32, None, "pallas", "tpu", "broadcast"),
])
def test_auto_strategy_follows_backend_and_platform(width, id_range, backend,
                                                    platform, want):
    assert choose_strategy(width, id_range, backend, platform) == want
