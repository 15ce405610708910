"""Multi-device semantics on 8 placeholder CPU devices.

Runs in a SUBPROCESS so the XLA device-count flag never leaks into the other
tests (jax locks device count at first init)."""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_mesh
from repro.graphs import rmat_graph, grid_graph
from repro.core import (triangle_count_matrix_distributed,
                        triangle_count_intersection_distributed,
                        triangle_count_scipy)

import warnings
from repro.core import TriangleCounter, CountOptions
from repro.core.engine import (plan_triangle_count, plan_edge_support,
                               executable_cache_info)
from repro.core.registry import choose_algorithm
from repro.core.calibrate import choose_measured

out = {}
mesh = make_mesh((4, 2), ("data", "model"))
mesh1 = make_mesh((8,), ("data",))
g = rmat_graph(9, 8, seed=5)
truth = triangle_count_scipy(g)
g2 = grid_graph(12, seed=2)
t2 = triangle_count_scipy(g2)

# --- parity sweep: lane x strategy x prep_backend vs the scipy oracle -----
for lane in ("intersection_distributed", "matrix_distributed"):
    for strat in ("auto", "probe", "broadcast"):
        for prep in ("device", "host"):
            opts = CountOptions(algorithm=lane, strategy=strat,
                                prep_backend=prep, block=32)
            r = TriangleCounter(g, opts, mesh=mesh1).count()
            out["%s_%s_%s" % (lane, strat, prep)] = r.count == truth

# 2D mesh + the deprecated shims (one DeprecationWarning, bit-identical)
out["matrix_2d"] = (plan_triangle_count(g, "matrix_distributed", mesh=mesh,
                                        block=32).count() == truth)
with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter("always")
    a = triangle_count_matrix_distributed(g2, mesh1, block=16)
    b = triangle_count_intersection_distributed(g2, mesh)
deps = [x for x in w if issubclass(x.category, DeprecationWarning)]
out["shim_warns"] = len(deps) == 2
out["shim_parity"] = a == t2 and b == t2

# --- zero-recompile steady state ------------------------------------------
p = plan_triangle_count(g, "intersection_distributed", mesh=mesh1)
p.count()
m0 = executable_cache_info()["misses"]
for _ in range(3):
    p.count()
p_again = plan_triangle_count(g, "intersection_distributed", mesh=mesh1)
out["warm_parity"] = p_again.count() == truth
out["steady_recompiles"] = executable_cache_info()["misses"] - m0 == 0

# --- exactly one compile on a shard-shape change --------------------------
# matrix lane = single stage; (8,) and (4,2) have equal device counts so the
# per-shard shapes match and ONLY the mesh cache-key component differs.
# block=64 keeps this pair's cache keys disjoint from every earlier check
# AND makes the tile deal non-divisible (8 shards over a tile count that is
# not a multiple of 8) for the padding regression below.
pm1 = plan_triangle_count(g, "matrix_distributed", mesh=mesh1, block=64)
pm1.count()
m0 = executable_cache_info()["misses"]
pm2 = plan_triangle_count(g, "matrix_distributed", mesh=mesh, block=64)
out["reshard_parity"] = pm2.count() == truth
out["reshard_compiles"] = executable_cache_info()["misses"] - m0 == 1

# --- shard balance: max/min per-shard padded work <= 2x -------------------
work = p.meta["shard_work"]
out["balance"] = (min(work) > 0 and max(work) / min(work) <= 2.0
                  and len(work) == 8)

# --- padding is length-gated: non-divisible deals + poisoned padding ------
# the deal is non-divisible (some shard has fewer real rows than dealt),
# and overwriting the padding with adversarial values must not change the
# count: the executables gate on the per-shard valid length, they do not
# rely on sentinel fill values surviving.
import jax.numpy as jnp
st = next(s for s in p.stages
          if (np.asarray(s.args[2]) < s.args[0].shape[1]).any())
u, v, valid = (np.asarray(x).copy() for x in st.args)
base = int(st.executable(*st.args))
for s in range(u.shape[0]):
    u[s, valid[s]:, :] = 7    # real vertex ids: u n v would "match"
    v[s, valid[s]:, :] = 7
poisoned = int(st.executable(jnp.asarray(u), jnp.asarray(v), st.args[2]))
out["poison_intersect"] = poisoned == base

stm = pm1.stages[0]
l, uu, aa, vv = (np.asarray(x).copy() for x in stm.args)
basem = float(stm.executable(*stm.args))
out["matrix_nondivisible"] = (np.asarray(vv) < l.shape[1]).any()
for s in range(l.shape[0]):
    l[s, vv[s]:] = np.nan     # NaN-poison: any touch would propagate
    uu[s, vv[s]:] = np.nan
    aa[s, vv[s]:] = np.nan
poim = float(stm.executable(jnp.asarray(l), jnp.asarray(uu),
                            jnp.asarray(aa), stm.args[3]))
out["poison_matrix"] = poim == basem

# --- chooser promotion: auto lands on a distributed lane under a mesh -----
out["auto_promote"] = choose_algorithm(g, mesh=mesh1).endswith("_distributed")
out["auto_measured"] = choose_measured(g, mesh=mesh1).endswith("_distributed")
out["auto_single"] = not choose_algorithm(g).endswith("_distributed")
ra = TriangleCounter(g, CountOptions(algorithm="auto", chooser="measured"),
                     mesh=mesh1).count()
out["auto_parity"] = (ra.count == truth
                      and ra.algorithm.endswith("_distributed"))

# --- distributed edge-support parity --------------------------------------
sup_d = np.asarray(plan_edge_support(g2, mesh=mesh1).support())
sup_1 = np.asarray(plan_edge_support(g2).support())
out["edge_parity"] = sup_d.shape == sup_1.shape and (sup_d == sup_1).all()

# gradient parity: sharded train step == single-device reference
from repro.models.registry import get_model, get_reduced_config
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import init_train_state, make_train_step
from repro.train.sharding import param_shardings, batch_sharding
from repro.train.data import SyntheticDataConfig, make_batch
from repro.models.meshctx import activation_mesh

cfg = get_reduced_config("gemma2-2b")
model = get_model(cfg)
opt_cfg = AdamWConfig(peak_lr=1e-3, warmup_steps=1, moment_dtype=jnp.float32)
params, opt = init_train_state(model, cfg, opt_cfg, jax.random.key(0),
                               dtype=jnp.float32)
batch = {k: jnp.asarray(v) for k, v in make_batch(
    cfg, SyntheticDataConfig(8, 17), 0).items()}
step = make_train_step(model, cfg, opt_cfg, microbatches=2)
p_ref, _, m_ref = jax.jit(step)(params, opt, batch)

p_sh = param_shardings(params, mesh)
b_sh = {k: batch_sharding(mesh, v) for k, v in batch.items()}
with activation_mesh(mesh):
    sharded = jax.jit(step, in_shardings=(p_sh, None, b_sh)).lower(
        params, opt, batch).compile()
p_dist, _, m_dist = sharded(jax.device_put(params, p_sh), opt,
                            jax.tree.map(lambda x, s: jax.device_put(x, s),
                                         batch, b_sh))
out["loss_parity"] = bool(np.isclose(float(m_ref["loss"]),
                                     float(m_dist["loss"]), rtol=1e-4))
flat_r = jax.tree.leaves(p_ref)
flat_d = jax.tree.leaves(p_dist)
out["param_parity"] = all(
    np.allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)
    for a, b in zip(flat_r, flat_d))

# compressed psum on a real mesh axis
from repro.train.compression import ef_psum, ef_init
from jax.sharding import PartitionSpec as P
from jax import shard_map

def worker(g):
    deq, _ = ef_psum({"w": g}, ef_init({"w": g}), "data")
    return deq["w"]

gs = jnp.arange(32, dtype=jnp.float32).reshape(8, 4) * 1e-3
got = jax.jit(shard_map(worker, mesh=mesh1, in_specs=P("data"),
                        out_specs=P("data")))(gs)
want = gs.sum(axis=0, keepdims=True)
out["ef_psum"] = bool(np.allclose(np.asarray(got[0]), np.asarray(want[0]),
                                  atol=2e-3))
print("RESULT:" + json.dumps({k: bool(v) for k, v in out.items()}))
"""


def test_distributed_semantics():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stdout
    out = json.loads(line[0][len("RESULT:"):])
    assert all(out.values()), out
