"""Exchange subsystem (repro.comm) under 4 forced host devices.

The multi-device battery runs in one subprocess (the dry-run isolation
rule: the main test process must keep a single device) and covers the
ISSUE-4 acceptance surface:

* ``ring_all_gather`` vs ``lax.all_gather`` bit-equality,
* the ``overlap`` (chunked double-buffered) variant bit-equal to the
  blocking paths at fp32 — both at the collective level and end-to-end
  through 10 ALS sweeps,
* ``ring_rs`` vs ``psum_scatter`` merge agreement,
* bf16-wire ALS fit within tolerance of the fp32 run over 10 sweeps,
* the non-divisible merge raising a clear ``ValueError`` (satellite
  bugfix) instead of corrupting row ownership.

In-process tests (single device) cover the pure-python surface: variant /
merge resolution precedence, ``ExchangeSpec`` validation, the volume model,
and the chunk-size defaults.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import comm
from repro.api import ExchangeConfig

SCRIPT = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.compat import shard_map
from repro import comm
import repro.api as api
from repro.core.coo import random_sparse

results = {}
assert jax.device_count() == 4, jax.device_count()
mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2), ("group", "sub"))
axes = ("group", "sub")
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(24, 5)).astype(np.float32))

def gather(variant, **kw):
    fn = lambda v: comm.all_gather_axes(v, axes, variant=variant, **kw)
    return np.asarray(jax.jit(shard_map(
        fn, mesh=mesh, in_specs=P(axes), out_specs=P(None)))(x))

ag = gather("allgather")
results["allgather_roundtrip"] = bool((ag == np.asarray(x)).all())
results["ring_bitwise"] = bool((gather("ring") == ag).all())
# overlap: even chunking, uneven tail chunk, and degenerate single chunk
results["overlap_bitwise"] = bool(
    (gather("overlap", chunk_rows=2) == ag).all()
    and (gather("overlap", chunk_rows=4) == ag).all()   # 6 = 4 + 2 tail
    and (gather("overlap", chunk_rows=6) == ag).all())

# --- merge variants over the sub axis (r=2) ------------------------------
y = jnp.asarray(rng.normal(size=(2, 8, 3)).astype(np.float32))

# each device returns its (4, 3) row slice of the merged (8, 3) partial
def merge(**kw):
    fn = lambda v: comm.merge_partials(v.reshape(8, 3), "sub", **kw)
    return np.asarray(jax.jit(shard_map(
        fn, mesh=mesh, in_specs=P("group", None, None),
        out_specs=P(("group", "sub"), None)))(y))

ps = merge(merge="psum_scatter")
results["ring_rs_matches_psum_scatter"] = bool(
    np.allclose(merge(merge="ring_rs"), ps, atol=1e-6))
results["bf16_merge_close"] = bool(
    np.allclose(merge(merge="ring_rs", wire_dtype=jnp.bfloat16), ps,
                atol=5e-2))

# --- non-divisible merge raises at trace time (satellite bugfix) ----------
try:
    merge_bad = lambda v: comm.merge_partials(v.reshape(8, 3)[:7], "sub")
    jax.jit(shard_map(merge_bad, mesh=mesh,
                      in_specs=P("group", None, None),
                      out_specs=P(("group", "sub"), None)))(y)
    results["nondivisible_raises"] = False
except ValueError as e:
    results["nondivisible_raises"] = "not divisible" in str(e)

# --- end-to-end: 10 ALS sweeps per exchange variant ----------------------
t = random_sparse((40, 30, 20), 1500, seed=7, distribution="zipf")
base = api.paper({"rank": 8, "runtime.tol": 0.0,
                  "partition.replication": 2})
plan = api.plan(t, base)

def run(overrides):
    cfg = base.with_overrides(overrides)
    with api.compile(plan, cfg) as solver:
        return solver.run(10)

r_ag = run({"exchange.variant": "allgather"})
fp32_variants_bitwise = True
for ov in ({"exchange.variant": "ring"},
           {"exchange.variant": "overlap"},
           {"exchange.variant": "overlap", "exchange.chunk_rows": 4},
           {"exchange.variant": "overlap", "exchange.merge": "ring_rs"}):
    r = run(ov)
    fp32_variants_bitwise = fp32_variants_bitwise and all(
        (a == b).all() for a, b in zip(r.factors, r_ag.factors))
results["fp32_variants_bitwise"] = bool(fp32_variants_bitwise)

r_bf16 = run({"exchange.variant": "overlap",
              "exchange.wire_dtype": "bfloat16"})
results["fit_fp32"] = float(r_ag.fits[-1])
results["fit_bf16"] = float(r_bf16.fits[-1])
results["bf16_fit_within_tol"] = bool(
    abs(r_bf16.fits[-1] - r_ag.fits[-1]) < 0.08)

# --- modelled vs measured exchange volume --------------------------------
cfg = base.with_overrides({"exchange.variant": "overlap",
                           "exchange.chunk_rows": 4})
with api.compile(plan, cfg) as solver:
    solver.sweep()
    rep = solver.exchange_report()
results["modelled_bytes"] = rep["modelled"]["sweep_total_bytes"]
results["measured_bytes"] = rep["measured"]["sweep_total_bytes"]
results["volume_model_matches"] = bool(
    rep["modelled"]["sweep_total_bytes"] > 0 and
    abs(rep["measured"]["sweep_total_bytes"] -
        rep["modelled"]["sweep_total_bytes"])
    <= 0.25 * rep["modelled"]["sweep_total_bytes"])
bf16_model = comm.modelled_exchange_bytes(plan, 8, wire_dtype="bfloat16")
results["bf16_half_volume"] = bool(
    bf16_model["sweep_total_bytes"] * 2
    == rep["modelled"]["sweep_total_bytes"])

print("RESULTS_JSON:" + json.dumps(results))
"""


@pytest.mark.slow
def test_exchange_battery_4dev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1500)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("RESULTS_JSON:"))
    results = json.loads(line[len("RESULTS_JSON:"):])
    assert results["allgather_roundtrip"]
    assert results["ring_bitwise"]
    assert results["overlap_bitwise"]
    assert results["ring_rs_matches_psum_scatter"]
    assert results["bf16_merge_close"]
    assert results["nondivisible_raises"]
    assert results["fp32_variants_bitwise"]
    assert results["bf16_fit_within_tol"], (
        results["fit_fp32"], results["fit_bf16"])
    assert results["volume_model_matches"], results
    assert results["bf16_half_volume"]


# --- in-process (single device): resolution, validation, volume model -----

def test_variant_resolution_precedence(monkeypatch):
    monkeypatch.delenv(comm.ENV_VARIANT, raising=False)
    # legacy ring flag maps onto the registry
    assert comm.resolve_variant(None, True) == "ring"
    assert comm.resolve_variant(None, False) == "allgather"
    assert comm.resolve_variant(None, None) == comm.DEFAULT_VARIANT
    # env beats the legacy flag, explicit argument beats env
    monkeypatch.setenv(comm.ENV_VARIANT, "overlap")
    assert comm.resolve_variant(None, True) == "overlap"
    assert comm.resolve_variant("ring", True) == "ring"
    with pytest.raises(ValueError, match="unknown exchange variant"):
        comm.resolve_variant("nope")


def test_merge_resolution(monkeypatch):
    monkeypatch.delenv(comm.ENV_MERGE, raising=False)
    assert comm.resolve_merge(None) == "psum_scatter"
    monkeypatch.setenv(comm.ENV_MERGE, "ring_rs")
    assert comm.resolve_merge(None) == "ring_rs"
    with pytest.raises(ValueError, match="unknown exchange merge"):
        comm.resolve_merge("nope")


def test_exchange_config_validation():
    assert ExchangeConfig().resolved_variant() == "ring"
    assert ExchangeConfig(ring=False).resolved_variant() == "allgather"
    assert ExchangeConfig(variant="overlap").resolved_variant() == "overlap"
    with pytest.raises(ValueError, match="exchange.variant"):
        ExchangeConfig(variant="bogus")
    with pytest.raises(ValueError, match="exchange.merge"):
        ExchangeConfig(merge="bogus")
    with pytest.raises(ValueError, match="wire_dtype"):
        ExchangeConfig(wire_dtype="float16")
    with pytest.raises(ValueError, match="chunk_rows"):
        ExchangeConfig(chunk_rows=0)


def test_exchange_spec_resolution(monkeypatch):
    monkeypatch.delenv(comm.ENV_VARIANT, raising=False)
    monkeypatch.delenv(comm.ENV_MERGE, raising=False)
    spec = comm.resolve_exchange_spec(ExchangeConfig(
        variant="overlap", merge="ring_rs", chunk_rows=16,
        wire_dtype="bfloat16"))
    assert (spec.variant, spec.merge, spec.chunk_rows) == \
        ("overlap", "ring_rs", 16)
    assert spec.reduced_wire and str(spec.wire) == "bfloat16"
    # full-precision spec emits no casts at all
    assert comm.resolve_exchange_spec(ExchangeConfig()).wire is None
    with pytest.raises(ValueError):
        comm.ExchangeSpec(variant="bogus")


def test_bf16_wire_merge_normalization(monkeypatch):
    """A bf16 wire always runs the ring_rs merge: the DEFAULT merge is
    normalized so the spec (and every report built from it) names the
    schedule that actually executes, while an EXPLICIT psum_scatter
    request is a contradiction and raises."""
    monkeypatch.delenv(comm.ENV_MERGE, raising=False)
    spec = comm.resolve_exchange_spec(
        ExchangeConfig(wire_dtype="bfloat16"))
    assert spec.merge == "ring_rs"
    with pytest.raises(ValueError, match="psum_scatter"):
        comm.resolve_exchange_spec(ExchangeConfig(
            wire_dtype="bfloat16", merge="psum_scatter"))
    monkeypatch.setenv(comm.ENV_MERGE, "psum_scatter")
    with pytest.raises(ValueError, match="psum_scatter"):
        comm.resolve_exchange_spec(ExchangeConfig(wire_dtype="bfloat16"))
    with pytest.raises(ValueError, match="psum_scatter"):
        comm.ExchangeSpec(wire_dtype="bfloat16", merge="psum_scatter")


def test_core_exchange_shim_keeps_historical_default(monkeypatch):
    """repro.core.exchange.all_gather_axes pre-dates the variant registry:
    its ring flag must keep defaulting to False (native all_gather) and
    must NOT be swayed by AMPED_EXCHANGE_VARIANT."""
    import inspect

    from repro.core import exchange as core_exchange

    sig = inspect.signature(core_exchange.all_gather_axes)
    assert sig.parameters["ring"].default is False
    # behavioral: under env=ring, the shim still lowers the default path
    # to a plain all-gather (no collective-permute ring)
    monkeypatch.setenv(comm.ENV_VARIANT, "ring")
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.compat import shard_map

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("group", "sub"))
    fn = shard_map(
        lambda v: core_exchange.all_gather_axes(v, ("group", "sub")),
        mesh=mesh, in_specs=P(("group", "sub")), out_specs=P(None))
    txt = jax.jit(fn).lower(jnp.ones((4, 3))).as_text()
    assert "collective_permute" not in txt and "ppermute" not in txt


def test_volume_model(small_tensor):
    from repro.core.partition import build_plan
    plan = build_plan(small_tensor, 1)
    rank = 8
    # single device: no exchange at all
    assert comm.modelled_exchange_bytes(plan, rank)["sweep_total_bytes"] == 0
    # the m-device ring model: (m-1) * rows/r * R * 4 per device and mode
    plan4 = build_plan(small_tensor, 4, replication=2)
    model = comm.modelled_exchange_bytes(plan4, rank)
    for part, row in zip(plan4.modes, model["per_mode"]):
        gather_rows = part.rows_max // part.r
        assert row["gather_bytes"] == 3 * gather_rows * rank * 4
        assert row["merge_bytes"] == (part.rows_max // 2) * rank * 4
    half = comm.modelled_exchange_bytes(plan4, rank, wire_dtype="bfloat16")
    assert half["sweep_total_bytes"] * 2 == model["sweep_total_bytes"]


def test_default_chunk_rows():
    assert comm.default_chunk_rows(24) == 12
    assert comm.default_chunk_rows(1) == 1
    assert comm.default_chunk_rows(3) == 2
