"""Multi-device integration: one subprocess with 8 virtual CPU devices runs
the full distributed battery (ring == all_gather, AMPED vs equal-nnz vs
oracle, r>1 merges, ALS convergence, gradient-compression psum). Subprocess
keeps the main test env at 1 device per the dry-run isolation rule."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from repro.compat import shard_map
from repro.core.coo import random_sparse, to_dense
from repro.core.partition import build_plan
from repro.core import mttkrp as M
from repro.core import exchange
from repro.kernels.ref import mttkrp_dense_ref
from jax.sharding import Mesh, PartitionSpec as P

results = {}
assert jax.device_count() == 8, jax.device_count()

# --- ring all-gather == lax.all_gather over a 2D (4,2) mesh -------------
mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("group", "sub"))
x = jnp.arange(8 * 3 * 5, dtype=jnp.float32).reshape(24, 5)

def ring_fn(x):
    return exchange.ring_all_gather(x, ("group", "sub"))

def ag_fn(x):
    return exchange.all_gather_axes(x, ("group", "sub"), ring=False)

ring = jax.jit(shard_map(ring_fn, mesh=mesh, in_specs=P(("group", "sub")),
                             out_specs=P(None)))(x)
ag = jax.jit(shard_map(ag_fn, mesh=mesh, in_specs=P(("group", "sub")),
                           out_specs=P(None)))(x)
results["ring_equals_allgather"] = bool(np.allclose(ring, ag))
results["ring_equals_input"] = bool(np.allclose(ring, x))

# --- distributed MTTKRP across strategies vs dense oracle ---------------
t = random_sparse((50, 37, 24), 800, seed=1, distribution="zipf")
dense = to_dense(t)
R = 16
ok = True
for strategy, repl in (("amped_cdf", None), ("amped_cdf", 4),
                       ("equal_nnz", None), ("amped_lpt", None)):
    plan = build_plan(t, 8, strategy=strategy, replication=repl)
    for mode in range(3):
        part = plan.modes[mode]
        cmesh = M.cp_mesh(8, part.r)
        rng = np.random.default_rng(0)
        factors = []
        for w in range(3):
            f = np.zeros((plan.modes[w].padded_rows, R), np.float32)
            f[plan.global_to_padded[w]] = rng.normal(
                size=(t.shape[w], R)).astype(np.float32)
            factors.append(jnp.asarray(f))
        dev = M.shard_plan_mode(part, cmesh)
        out = M.distributed_mttkrp(plan, mode, cmesh, dev, factors,
                                   use_kernel=False, ring=True)
        f_glob = [jnp.asarray(np.asarray(f)[plan.global_to_padded[w]])
                  for w, f in enumerate(factors)]
        ref = np.asarray(mttkrp_dense_ref(jnp.asarray(dense), f_glob, mode))
        got = np.asarray(out)[plan.global_to_padded[mode]]
        err = np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6)
        ok = ok and err < 5e-4
results["mttkrp_all_strategies"] = bool(ok)

# --- kernel path on 8 devices -------------------------------------------
plan = build_plan(t, 8)
part = plan.modes[0]
cmesh = M.cp_mesh(8, part.r)
rng = np.random.default_rng(0)
factors = []
for w in range(3):
    f = np.zeros((plan.modes[w].padded_rows, R), np.float32)
    f[plan.global_to_padded[w]] = rng.normal(size=(t.shape[w], R)).astype(np.float32)
    factors.append(jnp.asarray(f))
dev = M.shard_plan_mode(part, cmesh)
k_out = M.distributed_mttkrp(plan, 0, cmesh, dev, factors, use_kernel=True)
j_out = M.distributed_mttkrp(plan, 0, cmesh, dev, factors, use_kernel=False)
results["kernel_matches_jnp_8dev"] = bool(
    np.allclose(np.asarray(k_out), np.asarray(j_out), atol=2e-3))
f_out = M.distributed_mttkrp(plan, 0, cmesh, dev, factors, variant="fused")
results["fused_matches_jnp_8dev"] = bool(
    np.allclose(np.asarray(f_out), np.asarray(j_out), atol=2e-3))

# --- ALS converges on 8 devices ------------------------------------------
from repro.core.decompose import cp_decompose
res = cp_decompose(t, rank=8, num_devices=8, iters=4, tol=0)
results["als_fits"] = res.fits
results["als_monotone"] = bool(all(
    b >= a - 1e-4 for a, b in zip(res.fits, res.fits[1:])))

# --- elastic restart: 4 devices -> checkpoint -> resume on 8 --------------
import tempfile
ck = tempfile.mkdtemp()
r4 = cp_decompose(t, rank=6, num_devices=4, iters=3, tol=0, seed=5,
                  checkpoint_dir=ck)
r8 = cp_decompose(t, rank=6, num_devices=8, iters=6, tol=0, seed=5,
                  checkpoint_dir=ck, resume=True)
results["elastic_fits"] = r4.fits + r8.fits[len(r4.fits):]
results["elastic_resumed"] = bool(len(r8.fits) == 6 and
                                  r8.fits[3] >= r4.fits[-1] - 1e-3)

# --- compressed psum across 8 devices ------------------------------------
from repro.training.compression import compressed_psum_tree
dmesh = Mesh(np.asarray(jax.devices()), ("data",))
gs = jnp.asarray(np.random.default_rng(3).normal(size=(8, 128)).astype(np.float32))

def comp(g, r):
    out, res = compressed_psum_tree({"w": g.reshape(128)},
                                    {"w": r.reshape(128)}, "data")
    return out["w"], res["w"]

out, _ = jax.jit(shard_map(comp, mesh=dmesh,
                               in_specs=(P("data"), P("data")),
                               out_specs=(P(), P("data"))))(gs, jnp.zeros_like(gs))
true_mean = np.asarray(gs).mean(0)
rel = np.abs(np.asarray(out) - true_mean).max() / np.abs(true_mean).max()
results["compressed_psum_rel_err"] = float(rel)
results["compressed_psum_ok"] = bool(rel < 0.08)

print("RESULTS_JSON:" + json.dumps(results))
"""


@pytest.mark.slow
def test_multidevice_battery():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1500)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("RESULTS_JSON:"))
    results = json.loads(line[len("RESULTS_JSON:"):])
    assert results["ring_equals_allgather"]
    assert results["ring_equals_input"]
    assert results["mttkrp_all_strategies"]
    assert results["kernel_matches_jnp_8dev"]
    assert results["fused_matches_jnp_8dev"]
    assert results["als_monotone"], results["als_fits"]
    assert results["elastic_resumed"], results["elastic_fits"]
    assert results["compressed_psum_ok"], results["compressed_psum_rel_err"]


# -- packed ownership layout on 4 devices -------------------------------------

PACKED_SCRIPT = r"""
import json
import sys
import numpy as np
import jax
assert jax.device_count() == 4, jax.device_count()

import repro.api as api
from repro.core import mttkrp as M
from repro.core.coo import random_sparse, to_dense
from repro.store import TensorStore, write_store_from_coo
from repro.store.plan import resident_shard_nbytes

t = random_sparse((160, 60, 40), 4000, seed=5, distribution="zipf")
dense = np.asarray(to_dense(t), np.float64)
R = 8
SUBS = ("ir", "jr", "kr")


def mttkrp64(f, d):
    ins = [w for w in range(3) if w != d]
    return np.einsum("ijk," + ",".join(SUBS[w] for w in ins) + "->"
                     + SUBS[d], dense, *[f[w] for w in ins])


def sweep64(f):
    # one ALS sweep in float64 from global-layout factors
    f = [np.asarray(x, np.float64) for x in f]
    grams = [x.T @ x for x in f]
    for d in range(3):
        v = np.prod([grams[w] for w in range(3) if w != d], axis=0)
        fd = mttkrp64(f, d) @ np.linalg.pinv(v)
        lam = np.linalg.norm(fd, axis=0)
        lam = np.where(lam > 0, lam, 1.0)
        f[d] = fd / lam
        grams[d] = f[d].T @ f[d]
    return f, lam


def rel(a, b):
    return float(np.abs(np.asarray(a) - b).max() / max(np.abs(b).max(), 1e-12))


out = {"mttkrp": {}, "sweep": {}}
resident = None
for variant, repl in (("ring", 1), ("ring", 2), ("allgather", 1),
                      ("overlap", 1), ("overlap", 2)):
    cfg = api.paper({"rank": R, "runtime.num_devices": 4,
                     "runtime.tol": 0.0, "exchange.variant": variant,
                     "partition.replication": repl})
    plan = api.plan(t, cfg)
    with api.compile(plan, cfg) as s:
        f0 = s.result().factors
        errs, packed = [], True
        for d in range(3):
            part = plan.modes[d]
            m = np.asarray(M.distributed_mttkrp(
                plan, d, s.mesh, s.dev_arrays[d], s.state.factors,
                use_kernel=False, exchange_spec=s.exchange_spec))
            g2p = plan.global_to_padded[d]
            packed &= (m.shape[0] == part.padded_rows
                       < part.n_groups * part.rows_max)
            packed &= bool((np.delete(m, g2p, axis=0) == 0).all())
            errs.append(rel(m[g2p], mttkrp64(f0, d)))
        s.sweep()
        res = s.result()
    f1, lam1 = sweep64(f0)
    key = f"{variant}-r{repl}"
    out["mttkrp"][key] = {"err": max(errs), "packed": bool(packed)}
    out["sweep"][key] = max([rel(a, b) for a, b in zip(res.factors, f1)]
                            + [rel(res.lam, lam1)])
    if variant == "ring" and repl == 1:
        resident = (f0, f1, lam1, res)

# the streamed finish: a store-backed plan whose budget splits every mode
# into super-shards, against the resident ring update above
path = sys.argv[1]
write_store_from_coo(t, path, chunk_nnz=512)
st = TensorStore(path)
cfg = api.paper({"rank": R, "runtime.num_devices": 4, "runtime.tol": 0.0,
                 "exchange.variant": "ring", "partition.replication": 1})
lazy = api.plan(st, cfg)
nmodes = 3
total = sum(resident_shard_nbytes(p, nmodes) for p in lazy.modes)
floors = [st.chunk_nnz * (8 * nmodes + 4)]
for p in lazy.modes:
    dense_tile = int(p._dev_tc_pad.max())
    floors.append(2 * int(max(dense_tile, p.block_p)
                          * (4 * nmodes + 8 + 4 / p.block_p)
                          + p.layout.n_tiles * 4 + 1))
scfg = cfg.with_overrides({"runtime.streaming": True,
                           "runtime.memory_budget":
                               max(int(total * 0.3), *floors)})
with api.compile(api.plan(st, scfg), scfg) as s:
    multi = max(sp.num_shards for sp in s.stream_plans) > 1
    s.sweep()
    res = s.result()
f0, f1, lam1, rres = resident
out["sweep"]["streamed"] = max([rel(a, b) for a, b in zip(res.factors, f1)]
                               + [rel(res.lam, lam1)])
out["streamed_bitwise"] = bool(
    multi and res.fits == rres.fits
    and all((np.asarray(a) == np.asarray(b)).all()
            for a, b in zip(res.factors, rres.factors))
    and (np.asarray(res.lam) == np.asarray(rres.lam)).all())
print("RESULTS_JSON:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def packed_runs(tmp_path_factory):
    """One child with 4 virtual CPU devices runs every packed-layout path:
    a distributed MTTKRP per mode and one ALS sweep under each exchange
    (r = 1: 4 groups; r = 2, for the ring and the overlap: 2 groups), and
    the streamed finish."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    store = str(tmp_path_factory.mktemp("packed") / "t.store")
    proc = subprocess.run([sys.executable, "-c", PACKED_SCRIPT, store],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("RESULTS_JSON:"))
    return json.loads(line[len("RESULTS_JSON:"):])


@pytest.mark.parametrize("variant", ["ring", "allgather", "overlap"])
def test_packed_mttkrp_matches_the_oracle(packed_runs, variant):
    """Each mode's distributed MTTKRP returns the packed factor (fewer rows
    than the gathered slabs, zero pad rows) and matches the float64
    MTTKRP of the dense tensor."""
    rows = {k: v for k, v in packed_runs["mttkrp"].items()
            if k.startswith(variant + "-")}
    assert rows
    for key, row in rows.items():
        assert row["packed"], (key, row)
        assert row["err"] < 1e-5, (key, row)


@pytest.mark.parametrize("case", ["ring", "allgather", "overlap",
                                  "streamed"])
def test_packed_sweep_matches_the_oracle(packed_runs, case):
    """One ALS sweep on the packed layout: factors and λ match a float64
    sweep from the same initial factors."""
    errs = {k: v for k, v in packed_runs["sweep"].items()
            if k == case or k.startswith(case + "-")}
    assert errs
    for key, err in errs.items():
        assert err < 1e-4, (key, err)


def test_streamed_finish_equals_the_resident_update(packed_runs):
    """The streamed finish (the exchange run once on the accumulated
    super-shard partials) packs the same slabs: fits, factors and λ equal
    the resident update's bit for bit."""
    assert packed_runs["streamed_bitwise"]
