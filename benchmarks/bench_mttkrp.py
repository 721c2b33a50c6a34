"""EC kernel-variant microbenchmark: ref vs blocked vs fused vs sorted.

    PYTHONPATH=src python -m benchmarks.bench_mttkrp [--quick]

For every (nmodes, rank, nnz) grid point the four EC variants run on the
same partitioned shard (``sorted`` on its row-sorted layout of the same
tensor and geometry); the report carries, per variant:

  * wall time (best of ``repeats``) and GFLOP/s
    (flops = nnz · R · nin Hadamard multiplies + nnz · R accumulates),
  * modelled FLOPs: the one-hot variants (blocked/fused) commit each block
    through a ``(tile, block_p) @ (block_p, R)`` matmul — ``2·nnz·tile·R``
    pure scatter FLOPs the segmented-reduction variants (ref/sorted) do not
    spend (asserted: no one-hot term in their model),
  * *modelled* HBM bytes moved and the resulting effective GB/s — the
    gather-traffic analysis of EXPERIMENTS.md §Perf. The blocked variant
    both writes and re-reads an (nnz, R) gathered intermediate per input
    mode (2·nnz·nin·R·4 bytes); fused and sorted stream each factor row
    exactly once (nnz·nin·R·4). Sorted additionally replaces the per-slot
    row array (nnz·4) with per-block segment descriptors
    (nblocks·(2·tile+3)·4 ≪ nnz·4) and writes each output row once instead
    of rewriting the output tile per block — so
    ``modelled_hbm_bytes(sorted) < modelled_hbm_bytes(fused)`` strictly,
    asserted at every point and recorded machine-readably,
  * an HLO check: ``gather_free`` is True iff the lowered computation
    contains no XLA gather op (no materialized intermediate exists).

Each point also times the slot-order oracle (``kernels/ref.py``) on the
sorted shard with and without the ``segment_sum(indices_are_sorted=True)``
hint — bit-identical by construction (asserted), and real XLA CPU wall
time, so hint parity or better is the one wall-clock claim this container
can honestly make
(``ref_sorted_hint.parity``); the Pallas variants run in interpret mode
off-TPU, where absolute times are meaningless.

A second scenario exercises the *scheduler*: on a synthetic hot-index
(skewed) tensor with 4 forced host devices, CP-ALS runs with the dynamic
rebalancer off vs on, and the report carries the per-sweep max/mean
per-device EC-time ratio plus the idle fraction (1 - 1/ratio) of the
parallel makespan — the quantity AMPED's dynamic load balancing minimizes.

A third scenario exercises the *exchange* (repro.comm): on 4 forced host
devices with replication r=2, CP-ALS runs under the blocking ring exchange
vs the chunked double-buffered ``overlap`` schedule (bit-identical factors
asserted), and the report carries per-sweep wall time for both, modelled vs
HLO-measured exchange volume, and the bf16-wire run's volume (≈ half fp32)
and final-fit delta vs fp32 — the quantities the multidevice CI job gates
on.

A fourth scenario exercises the *ingest path* (repro.store): a paper-profile
tensor written as text is planned twice — once through the in-memory COO
path, once through the streaming store converter + plan-from-stats — each in
its own subprocess; the report carries converter throughput (Mnnz/s),
store-vs-text on-disk size, and the peak-RSS delta of each planning path
(the store path reads zero chunks, asserted).

A fifth scenario exercises *epoch streaming* (runtime.streaming): the same
store-backed tensor decomposes resident vs streamed under a memory budget
several times smaller than its total shard bytes, each in its own
subprocess; the report carries the fit-trajectory equality (bitwise, the
hard invariant), the overlap fraction (transfer time hidden behind compute
by the double-buffered prefetch), exposed transfer ms/sweep, peak streamed
device bytes vs the budget, and each path's peak-RSS delta (the streamed
run must stay below the resident one — the point of the mode).

A sixth scenario exercises the *serving path* (repro.serve): an exactly
low-rank store-backed tensor is fitted, checkpointed, and booted as a
``CPService``; the report carries the batched jitted query throughput vs a
per-request ``reconstruct_at`` loop at equal results (the >= 50x speedup
flag), client-side p50/p99 latency before and during a concurrent
background incremental refit (the bounded-p99 flag), and the
appended-chunk incremental-refresh fit vs a from-scratch refit of the
grown store (the < 1e-3 agreement flag).

Output: ``experiments/bench/BENCH_mttkrp.json`` (benchmarks/common.py's
standard location) plus a copy at the repo root (``BENCH_mttkrp.json``) so
the perf trajectory is tracked across PRs. On this CPU-only container the
Pallas variants run in interpret mode, so *absolute* times are meaningless
for the kernel paths — the modelled-traffic numbers, the gather-free
property and the rebalance ratios are the machine-readable perf trajectory;
on TPU the same script reports real GFLOP/s.
"""
from __future__ import annotations

import argparse
import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import run_subprocess_bench, save_result, timeit
from repro.obs import trace as obs_trace

VARIANTS = ("ref", "blocked", "fused", "sorted")

SKEW_SCRIPT = r"""
import json
import numpy as np
import jax
assert jax.device_count() == 4, jax.device_count()

import repro.api as api
from repro.core.coo import SparseTensor
from repro.obs import clock
from repro.obs import export as obs_export
from repro.obs import trace as obs_trace

NNZ = {nnz}
rng = np.random.default_rng(0)
hot = NNZ * 6 // 10
i0 = np.concatenate([rng.integers(0, 3, hot),
                     rng.integers(3, 4096, NNZ - hot)])
t = SparseTensor(
    np.stack([i0, rng.integers(0, 64, NNZ), rng.integers(0, 64, NNZ)], 1
             ).astype(np.int32),
    rng.standard_normal(NNZ).astype(np.float32), (4096, 64, 64)
).deduplicated()

base = api.paper({{"rank": 8, "runtime.tol": 0.0,
                   "partition.strategy": "equal_nnz"}})
out = {{"nnz": t.nnz, "devices": 4}}
for label, rebalance in (("off", "measure"), ("on", "on")):
    cfg = base.with_overrides({{
        "schedule.rebalance": rebalance, "schedule.cadence": 1,
        "schedule.imbalance_threshold": 1.1,
        "schedule.migration_budget": 0.4}})
    solver = api.compile(api.plan(t, cfg), cfg)
    res = solver.run({sweeps})
    worst = [max(e["imbalance"].values()) for e in solver.schedule_events]
    out[label] = {{
        "fit": float(res.fits[-1]),
        "imbalance_per_point": worst,
        "idle_frac_per_point": [1.0 - 1.0 / w for w in worst],
        "moved_nnz": int(sum(e["moved_nnz"]
                             for e in solver.schedule_events)),
        "rebalance_epoch": int(solver.plan.rebalance_epoch),
    }}

# sorted-variant A/B on the same skewed tensor: ref (XLA segment_sum with
# the sorted hint) vs the ec_sorted Pallas kernel, SAME row-sorted plan —
# factors must match bit-for-bit; wall times ride along (off-TPU the Pallas
# kernel runs in interpret mode, so only the bit-equality is gated there).
ab_base = api.paper({{"rank": 8, "runtime.tol": 0.0,
                      "partition.strategy": "equal_nnz",
                      "partition.layout": "sorted"}})
ab, facs = {{}}, {{}}
for name, cfg in (
        ("ref", ab_base),
        ("sorted", ab_base.with_overrides({{"kernel.use_kernel": True,
                                            "kernel.variant": "sorted"}}))):
    solver = api.compile(api.plan(t, cfg), cfg)
    solver.run(1)                       # compile + warm every mode
    solver.reset()
    t0 = clock.now()
    res = solver.run({ab_sweeps})
    ab[name] = {{"per_sweep_s": (clock.now() - t0) / {ab_sweeps},
                 "fit": float(res.fits[-1])}}
    facs[name] = [np.asarray(f) for f in res.factors]
ab["factors_bitwise_equal"] = bool(all(
    (a == b).all() for a, b in zip(facs["ref"], facs["sorted"])))
out["sorted_ab"] = ab

# --- observability rider: traced mini-run + disabled-span overhead gate --
# (a) tracing ON: 2 sweeps through the traced resident path must produce a
# schema-valid span tree (sweep -> mode_update -> ec/exchange) covering
# >= 95% of the run span — the deterministic span counts land in the
# artifact and check_trajectory gates them;
obs_trace.reset()
obs_trace.enable()
tr_cfg = base.with_overrides({{"schedule.rebalance": "off"}})
tr_solver = api.compile(api.plan(t, tr_cfg), tr_cfg)
tr_solver.run(2)
obs_trace.disable()
trace = obs_export.chrome_trace(obs_trace.get_tracer().records())
val = obs_export.validate_trace(trace, min_coverage=0.95)

# (b) tracing OFF: per-call cost of a disabled span over the span calls
# one traced sweep would make, as a fraction of the measured ref sweep —
# the <= 2% acceptance gate for instrumentation left in the hot path
N = 200000
t0 = clock.now()
for _ in range(N):
    with obs_trace.span("x", mode=0):
        pass
span_cost = (clock.now() - t0) / N
nmodes = 3
spans_per_sweep = 1 + 3 * nmodes          # sweep + per-mode {{mode,ec,exch}}
per_sweep_s = ab["ref"]["per_sweep_s"]
overhead_frac = spans_per_sweep * span_cost / per_sweep_s
out["obs"] = {{
    "trace_valid": bool(val["ok"]),
    "coverage": float(val["coverage"]),
    "span_counts": val["span_counts"],
    "traced_sweeps": 2,
    "disabled_span_ns": span_cost * 1e9,
    "spans_per_sweep": spans_per_sweep,
    "overhead_frac_disabled": overhead_frac,
    "overhead_ok": bool(overhead_frac <= 0.02),
}}
print("RESULT_JSON:" + json.dumps(out))
"""


EXCHANGE_SCRIPT = r"""
import json
import numpy as np
import jax
assert jax.device_count() == 4, jax.device_count()

import repro.api as api
from repro import comm
from repro.core.coo import random_sparse
from repro.obs import clock

t = random_sparse((512, 96, 64), {nnz}, seed=3, distribution="zipf")
base = api.paper({{"rank": 16, "runtime.tol": 0.0,
                   "partition.replication": 2}})
plan = api.plan(t, base)
out = {{"nnz": t.nnz, "devices": 4, "rank": 16}}

def timed_run(overrides, sweeps={sweeps}, repeats={repeats}):
    cfg = base.with_overrides(overrides)
    with api.compile(plan, cfg) as solver:
        solver.run(1)                       # compile + warm every mode
        best = float("inf")
        for _ in range(repeats):
            solver.reset()
            t0 = clock.now()
            for _ in range(sweeps):
                solver.sweep()
            fit = float(solver.state.fits[-1])   # sync point
            best = min(best, (clock.now() - t0) / sweeps)
        rep = solver.exchange_report()
        factors = solver.result().factors
    return best, fit, rep, factors

blk_t, blk_fit, blk_rep, blk_f = timed_run({{"exchange.variant": "ring"}})
ov_t, ov_fit, ov_rep, ov_f = timed_run({{"exchange.variant": "overlap"}})
bf_t, bf_fit, bf_rep, _ = timed_run({{"exchange.variant": "overlap",
                                      "exchange.wire_dtype": "bfloat16"}})

assert all((a == b).all() for a, b in zip(blk_f, ov_f)), \
    "overlap diverged from blocking at fp32"

out["blocking"] = {{"per_sweep_s": blk_t, "fit": blk_fit,
                    "modelled_bytes": blk_rep["modelled"]["sweep_total_bytes"],
                    "measured_bytes": blk_rep["measured"]["sweep_total_bytes"]}}
out["overlap"] = {{"per_sweep_s": ov_t, "fit": ov_fit,
                   "chunk_rows": ov_rep["spec"]["chunk_rows"],
                   "modelled_bytes": ov_rep["modelled"]["sweep_total_bytes"],
                   "measured_bytes": ov_rep["measured"]["sweep_total_bytes"]}}
out["bf16_wire"] = {{"per_sweep_s": bf_t, "fit": bf_fit,
                     "modelled_bytes": bf_rep["modelled"]["sweep_total_bytes"],
                     "measured_bytes": bf_rep["measured"]["sweep_total_bytes"]}}
print("RESULT_JSON:" + json.dumps(out))
"""


def bench_exchange_overlap(*, nnz: int = 40000, sweeps: int = 6,
                           repeats: int = 3) -> dict:
    """Exchange A/B (blocking ring vs chunked overlap, plus bf16 wire) on 4
    forced host devices in its own subprocess. Derived fields are recorded,
    not asserted (a noisy wall-clock must not lose the artifact): CI gates
    on ``overlap_not_slower`` / ``bf16_*``; the deterministic bit-equality
    assertions live in tests/test_exchange.py."""
    result = run_subprocess_bench(
        EXCHANGE_SCRIPT.format(nnz=nnz, sweeps=sweeps, repeats=repeats),
        devices=4)
    blk, ov, bf = result["blocking"], result["overlap"], result["bf16_wire"]
    result["overlap_speedup"] = blk["per_sweep_s"] / ov["per_sweep_s"]
    # "not slower" with a 5% wall-clock noise margin: on a single-core CPU
    # host the chunks serialize, so parity is the honest expectation; on
    # real hardware the overlap hides wire time and the speedup is > 1.
    result["overlap_not_slower"] = (
        ov["per_sweep_s"] <= blk["per_sweep_s"] * 1.05)
    result["volume_model_error"] = (
        abs(ov["measured_bytes"] - ov["modelled_bytes"])
        / max(ov["modelled_bytes"], 1))
    result["bf16_volume_ratio"] = (bf["modelled_bytes"]
                                   / max(ov["modelled_bytes"], 1))
    result["bf16_fit_delta"] = abs(bf["fit"] - blk["fit"])
    return result


INGEST_COO_SCRIPT = r"""
import json, resource, tracemalloc
import repro.api as api
from repro.obs import clock
from repro.sparse.io import read_tns
base_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
tracemalloc.start()
t0 = clock.now()
t = read_tns({tns!r})
cfg = api.paper({{"runtime.num_devices": 1}})
plan = api.plan(t, cfg)
dt = clock.now() - t0
_, alloc_peak = tracemalloc.get_traced_memory()
tracemalloc.stop()
peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
print("RESULT_JSON:" + json.dumps({{
    "nnz": t.nnz, "plan_s": dt, "rss_base_kb": base_kb,
    "rss_peak_kb": peak_kb, "rss_delta_kb": peak_kb - base_kb,
    "alloc_peak_kb": alloc_peak // 1024}}))
"""

INGEST_STORE_SCRIPT = r"""
import json, os, resource, tracemalloc
import repro.api as api
from repro.obs import clock
from repro.store import TensorStore, convert_tns
report = convert_tns({tns!r}, {store!r}, chunk_nnz={chunk_nnz})
store_bytes = sum(os.path.getsize(os.path.join({store!r}, f))
                  for f in os.listdir({store!r}))
base_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
tracemalloc.start()
t0 = clock.now()
st = TensorStore({store!r})
cfg = api.paper({{"runtime.num_devices": 1}})
plan = api.plan(st, cfg)
dt = clock.now() - t0
_, alloc_peak = tracemalloc.get_traced_memory()
tracemalloc.stop()
peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
print("RESULT_JSON:" + json.dumps({{
    "nnz": st.nnz, "plan_s": dt, "rss_base_kb": base_kb,
    "rss_peak_kb": peak_kb, "rss_delta_kb": peak_kb - base_kb,
    "alloc_peak_kb": alloc_peak // 1024,
    "convert_s": report["elapsed_s"], "nnz_per_s": report["nnz_per_s"],
    "store_bytes": store_bytes, "chunks": len(report["chunks"]),
    "plan_chunk_reads": plan.modes[0].store.access_stats["chunk_reads"]}}))
"""


def bench_ingest(*, profile: str = "amazon", scale: float = 1e-3,
                 chunk_nnz: int = 1 << 17, workdir: str = "/tmp") -> dict:
    """Ingest A/B: text .tns -> in-memory COO planning vs streaming store
    conversion + plan-from-stats. Records converter throughput (Mnnz/s),
    peak memory of each planning path — both process ru_maxrss (meaningful
    once the working set clears the ~0.4 GB jax import baseline, i.e. at
    Mnnz+ scale) and the tracemalloc allocation peak (scale-independent; at
    quick scale this is the memory signal) — and store-vs-text on-disk
    size. The store path plans from manifest histograms with zero chunk
    reads (the one hard assertion here). Each path runs in its own
    subprocess so peaks don't contaminate each other."""
    import os

    from repro.sparse.io import make_profile_tensor, write_tns

    tns = os.path.join(workdir, f"bench_ingest_{profile}.tns")
    store = os.path.join(workdir, f"bench_ingest_{profile}.store")
    t = make_profile_tensor(profile, scale=scale, seed=0)
    write_tns(tns, t)
    tns_bytes = os.path.getsize(tns)
    del t

    coo = run_subprocess_bench(INGEST_COO_SCRIPT.format(tns=tns), devices=1)
    st = run_subprocess_bench(
        INGEST_STORE_SCRIPT.format(tns=tns, store=store,
                                   chunk_nnz=chunk_nnz), devices=1)
    assert st["plan_chunk_reads"] == 0, st  # plan-from-stats, always
    result = {
        "profile": profile, "scale": scale, "nnz": st["nnz"],
        "chunk_nnz": chunk_nnz, "tns_bytes": tns_bytes,
        "store_bytes": st["store_bytes"],
        "store_to_text_ratio": st["store_bytes"] / max(tns_bytes, 1),
        "convert_s": st["convert_s"],
        "convert_mnnz_per_s": st["nnz_per_s"] / 1e6,
        "coo_plan": coo, "store_plan": st,
        # recorded, not asserted here (memory noise must not lose the
        # artifact); CI gates on them
        "store_alloc_below_coo": (st["alloc_peak_kb"]
                                  < coo["alloc_peak_kb"]),
        "alloc_peak_ratio": (coo["alloc_peak_kb"]
                             / max(st["alloc_peak_kb"], 1)),
        "rss_delta_ratio": (coo["rss_delta_kb"]
                            / max(st["rss_delta_kb"], 1)),
    }
    return result


STREAM_RESIDENT_SCRIPT = r"""
import json, resource
import repro.api as api
from repro.store import TensorStore

st = TensorStore({store!r})
base_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
cfg = api.paper({{"rank": 32, "runtime.tol": 0.0,
                  "runtime.num_devices": 1}})
with api.compile(api.plan(st, cfg), cfg) as solver:
    res = solver.run({sweeps})
peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
print("RESULT_JSON:" + json.dumps({{
    "fits": res.fits, "rss_base_kb": base_kb, "rss_peak_kb": peak_kb,
    "rss_delta_kb": peak_kb - base_kb}}))
"""

STREAM_STREAMING_SCRIPT = r"""
import json, resource
import repro.api as api
from repro.store import TensorStore, resident_shard_nbytes

st = TensorStore({store!r})
base_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
cfg = api.paper({{"rank": 32, "runtime.tol": 0.0,
                  "runtime.num_devices": 1}})
plan = api.plan(st, cfg)
total = sum(resident_shard_nbytes(p, plan.nmodes) for p in plan.modes)
floors = []
for p in plan.modes:
    per_slot = 4 * plan.nmodes + 8 + 4 / p.block_p
    dense = int(p._dev_tc_pad.max()) if p._dev_tc_pad.size else 0
    floors.append(2 * int(max(dense, p.block_p) * per_slot
                          + p.layout.n_tiles * 4 + 1))
    floors.append(p.store.chunk_nnz * (8 * plan.nmodes + 4))
budget = max(total // 6, *floors)
scfg = cfg.with_overrides({{"runtime.streaming": True,
                           "runtime.memory_budget": budget}})
with api.compile(api.plan(st, scfg), scfg) as solver:
    res = solver.run({sweeps})
    rep = solver.overlap_report()
peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
rep["per_sweep"] = rep["per_sweep"][-1:]   # keep the artifact small
print("RESULT_JSON:" + json.dumps({{
    "fits": res.fits, "budget_bytes": budget, "total_shard_bytes": total,
    "report": rep, "rss_base_kb": base_kb, "rss_peak_kb": peak_kb,
    "rss_delta_kb": peak_kb - base_kb}}))
"""


def bench_stream_overlap(*, nnz: int = 1_200_000, sweeps: int = 3,
                         workdir: str = "/tmp") -> dict:
    """Epoch-streaming A/B on one store-backed tensor: resident vs streamed
    under a budget ~6x smaller than the total shard bytes, each in its own
    subprocess (so peak RSS is attributable). Fit equality is bitwise by
    construction (asserted in tests/test_streaming.py); here it is recorded
    along with the overlap/budget accounting CI gates on. A flat index
    distribution keeps the densest-tile budget floor low, letting the split
    produce genuinely small super-shards.

    ``overlap_fraction`` is the steady-state number (sweep 1 excluded):
    sweep 1 pays the one-time chunk-scan preprocessing that the window
    spill then caches, so sweeps 2+ replay sequential reads and are the
    per-iteration figure comparable across PRs. The cumulative number —
    preprocessing included — rides along as ``overlap_fraction_total``."""
    import os

    from repro.core.coo import random_sparse
    from repro.store import write_store_from_coo

    store = os.path.join(workdir, "bench_stream.store")
    t = random_sparse((4096, 2048, 1024), nnz, seed=7, dedup=False)
    write_store_from_coo(t, store, chunk_nnz=1 << 16)
    real_nnz = t.nnz
    del t

    res = run_subprocess_bench(
        STREAM_RESIDENT_SCRIPT.format(store=store, sweeps=sweeps), devices=1)
    strm = run_subprocess_bench(
        STREAM_STREAMING_SCRIPT.format(store=store, sweeps=sweeps),
        devices=1)
    rep = strm["report"]
    result = {
        "nnz": real_nnz, "sweeps": sweeps,
        "budget_bytes": strm["budget_bytes"],
        "total_shard_bytes": strm["total_shard_bytes"],
        "budget_ratio": strm["total_shard_bytes"] / strm["budget_bytes"],
        "shards_per_mode": rep["shards_per_mode"],
        "fits_equal": res["fits"] == strm["fits"],
        "final_fit": strm["fits"][-1],
        "overlap_fraction": rep["overlap_fraction_steady"],
        "overlap_fraction_total": rep["overlap_fraction"],
        "spill_hits": rep["spill_hits"], "spill_saves": rep["spill_saves"],
        "transfer_ms_per_sweep": rep["transfer_s"] / sweeps * 1e3,
        "exposed_ms_per_sweep": rep["exposed_s"] / sweeps * 1e3,
        "peak_resident_bytes": rep["peak_resident_bytes"],
        "peak_within_budget":
            rep["peak_resident_bytes"] <= strm["budget_bytes"],
        "bytes_streamed": rep["bytes_streamed"],
        "resident_rss_delta_kb": res["rss_delta_kb"],
        "streaming_rss_delta_kb": strm["rss_delta_kb"],
        # recorded, not asserted (memory noise must not lose the artifact);
        # the streaming-smoke CI job gates on it
        "rss_streaming_below_resident":
            strm["rss_delta_kb"] < res["rss_delta_kb"],
    }
    return result


SERVE_SCRIPT = r"""
import json, os
import numpy as np
import repro.api as api
from repro.api.config import DecomposeConfig, RuntimeConfig
from repro.obs import clock
from repro.core.coo import SparseTensor
from repro.serve import CPService, store_fit
from repro.sparse.io import make_lowrank_tensor
from repro.store import TensorStore, append_to_store, write_store_from_coo

WORK = {work!r}
SHAPE = (48, 40, 32)
RANK = 4
ROWS = {rows}
QUERIES = {queries}
BATCH = 16

# exactly rank-R tensor: base store is the first 85%, the remaining 15%
# is appended later, so warm and scratch refits of the grown store both
# converge to fit ~ 1 and their agreement is a real invariant
t = make_lowrank_tensor(SHAPE, RANK, {nnz}, seed=5)
base_n = int(t.nnz * 0.85)
store_path = os.path.join(WORK, "bench_serve.store")
write_store_from_coo(SparseTensor(t.indices[:base_n], t.values[:base_n],
                                  SHAPE), store_path, chunk_nnz=1024)
ckpt = os.path.join(WORK, "bench_serve_ckpt")

def _cfg(ckpt_dir=None):
    return DecomposeConfig(rank=RANK, runtime=RuntimeConfig(
        num_devices=1, tol=0.0, seed=0, checkpoint_dir=ckpt_dir))

cfg = _cfg(ckpt)
with api.compile(api.plan(TensorStore(store_path), cfg), cfg) as solver:
    fitted = solver.run(10)
    solver.checkpoint()

out = {{"shape": list(SHAPE), "rank": RANK, "nnz": int(t.nnz),
        "base_nnz": base_n, "rows": ROWS, "queries": QUERIES,
        "batch": BATCH}}
rng = np.random.default_rng(11)
store = TensorStore(store_path)

with CPService.boot(ckpt, store=store, config=_cfg()) as svc:
    # --- throughput: batched jitted engine vs per-request loop ----------
    coords = np.stack([rng.integers(0, s, size=ROWS) for s in SHAPE], 1)
    fitted.reconstruct_at(coords[:1])                  # warm the loop path
    t0 = clock.now()
    loop_vals = np.concatenate([fitted.reconstruct_at(coords[i:i + 1])
                                for i in range(ROWS)])
    loop_s = clock.now() - t0
    svc.engine.reconstruct_batch(coords)               # compile the bucket
    best = float("inf")
    for _ in range(3):
        t0 = clock.now()
        batched = svc.engine.reconstruct_batch(coords)
        best = min(best, clock.now() - t0)
    out["per_request_loop_s"] = loop_s
    out["batched_s"] = best
    out["batched_qps_rows"] = ROWS / best
    out["batched_speedup"] = loop_s / best
    out["parity_max_abs_err"] = float(
        np.max(np.abs(batched.astype(np.float64) - loop_vals)))

    def probe(n):
        lat = []
        for _ in range(n):
            c = np.stack([rng.integers(0, s, size=BATCH) for s in SHAPE], 1)
            t0 = clock.now()
            svc.reconstruct(c)
            lat.append(clock.now() - t0)
        return np.asarray(lat)

    # --- latency floor, then the same probe during a background refit ---
    base_lat = probe(QUERIES)
    append_to_store(store_path, t.indices[base_n:].astype(np.int64),
                    t.values[base_n:])
    svc.refresh(sweeps=6, wait=False)
    refit_lat = probe(QUERIES)
    event = svc.wait_refresh()
    out["p50_base_ms"] = float(np.percentile(base_lat, 50) * 1e3)
    out["p99_base_ms"] = float(np.percentile(base_lat, 99) * 1e3)
    out["p50_refit_ms"] = float(np.percentile(refit_lat, 50) * 1e3)
    out["p99_refit_ms"] = float(np.percentile(refit_lat, 99) * 1e3)
    out["refresh_published"] = bool(event.get("published"))
    out["snapshot_version"] = int(svc.engine.version)
    out["warm_fit"] = float(svc.engine.snapshot.fit)
    out["metrics"] = svc.metrics_report()

# --- from-scratch refit of the grown store, same fit functional ---------
store.refresh()
cfg = _cfg()
with api.compile(api.plan(store, cfg), cfg) as solver:
    scratch = solver.run(12)
out["scratch_fit"] = store_fit(scratch.factors, scratch.lam, store)
out["refresh_fit_delta"] = abs(out["warm_fit"] - out["scratch_fit"])
print("RESULT_JSON:" + json.dumps(out))
"""


def bench_serve_load(*, nnz: int = 6000, rows: int = 8192,
                     queries: int = 200, workdir: str = "/tmp") -> dict:
    """Serving-path load test in its own subprocess (single device, like
    production query serving). Flags are recorded, not asserted — a noisy
    run must not lose the artifact; check_trajectory refuses True -> False
    flips and tests/test_serve.py holds the deterministic invariants:

    * ``speedup_50x`` — one jitted shape-bucketed ``reconstruct_batch``
      call vs ``rows`` individual ``reconstruct_at`` calls, equal results
      (``parity_ok``, fp32 tolerance);
    * ``p99_bounded`` — client-side p99 while a background incremental
      refit (plan + compile + 6 ALS sweeps) shares the process stays under
      max(50x the idle p50, 500 ms);
    * ``refresh_fit_ok`` — warm-start refresh of the appended store lands
      within 1e-3 of a from-scratch refit, both scored by ``store_fit``.
    """
    result = run_subprocess_bench(
        SERVE_SCRIPT.format(work=workdir, nnz=nnz, rows=rows,
                            queries=queries), devices=1)
    result["parity_ok"] = result["parity_max_abs_err"] < 1e-4
    result["speedup_50x"] = result["batched_speedup"] >= 50.0
    result["p99_bounded"] = (result["p99_refit_ms"]
                             <= max(50.0 * result["p50_base_ms"], 500.0))
    result["refresh_fit_ok"] = (result["refresh_published"]
                                and result["snapshot_version"] == 2
                                and result["refresh_fit_delta"] < 1e-3)
    return result


def bench_skew_rebalance(*, nnz: int = 40000, sweeps: int = 6,
                         ab_sweeps: int = 2) -> dict:
    """Rebalancer A/B on a hot-index tensor, 4 forced host devices (its own
    subprocess — the main process must keep a single device). The same
    subprocess also runs the sorted-variant A/B (ref vs ec_sorted on one
    row-sorted plan, bit-identical factors gated by CI)."""
    result = run_subprocess_bench(
        SKEW_SCRIPT.format(nnz=nnz, sweeps=sweeps, ab_sweeps=ab_sweeps),
        devices=4)
    off, on = result["off"], result["on"]
    result["final_imbalance_off"] = off["imbalance_per_point"][-1]
    result["final_imbalance_on"] = on["imbalance_per_point"][-1]
    result["idle_frac_reduction"] = (off["idle_frac_per_point"][-1]
                                     - on["idle_frac_per_point"][-1])
    # Recorded, not asserted: a noisy wall-clock run must not lose the whole
    # benchmark artifact. CI gates on these fields; the deterministic
    # assertion lives in tests/test_schedule_multidevice.py.
    result["imbalance_reduced"] = (result["final_imbalance_on"]
                                   < result["final_imbalance_off"])
    result["fit_delta"] = abs(off["fit"] - on["fit"])
    return result


def _flops(nnz: int, rank: int, nin: int) -> int:
    # nin multiplies (val·row_1·…·row_nin) + 1 accumulate, per (nz, r) lane
    return nnz * rank * (nin + 1)


def modelled_flops(variant: str, nnz: int, rank: int, nin: int,
                   tile: int) -> int:
    """Per-variant FLOP model. All variants spend the useful
    ``nnz·R·(nin+1)`` (Hadamard products + accumulate). The one-hot
    variants (blocked/fused) additionally commit every block through a
    ``(tile, block_p) @ (block_p, R)`` matmul — ``2·nnz·tile·R`` pure
    scatter FLOPs. The segmented-reduction variants (ref's ``segment_sum``,
    sorted's in-register accumulation) carry NO one-hot scatter term."""
    useful = _flops(nnz, rank, nin)
    if variant in ("blocked", "fused"):
        return useful + 2 * nnz * tile * rank
    return useful


def modelled_hbm_bytes(variant: str, nnz: int, rank: int, nin: int,
                       num_rows: int, num_buffers: int = 2, *,
                       tile: int, block_p: int) -> int:
    """HBM traffic model for one EC call (f32=4B, i32=4B).

    Common terms: values read (nnz·4). Index reads: nnz·nin·4, except the
    in-kernel-gather variants' (fused/sorted) lookahead BlockSpecs stream
    each index slab ``num_buffers`` times (blocks 0..L-1's slices transit
    once per lookahead view). Factor-row traffic:
      ref/blocked    gather writes (nnz·nin·R·4) + kernel re-reads them
      fused/sorted   each row read from HBM exactly once, streamed
    Row-targeting metadata:
      ref/blocked/fused  one i32 per slot (local_rows / row_in_tile): nnz·4
      sorted             per-block segment descriptors only:
                         nblocks·(2·tile+3)·4 — (tile+2) seg starts +
                         (tile+1) seg rows per block, ≪ nnz·4
    Output commits:
      ref      segment_sum writes each row once: num_rows·R·4
      blocked/fused  the one-hot matmul rewrites (reads + writes) the
               output tile once per BLOCK: 2·nblocks·tile·R·4
      sorted   each row written exactly once, plus one accumulator row
               re-read per cross-block segment (≤ 1/block):
               num_rows·R·4 + nblocks·R·4
    Sorted stays strictly below fused: the descriptor read is smaller than
    the per-slot row array whenever block_p > 2·tile+3 (always, for the
    supported geometries), and single-write output beats per-block tile
    rewrite whenever num_rows < nblocks·(2·tile−1).
    """
    nblocks = nnz // block_p
    vals_bytes = nnz * 4
    idx_bytes = nnz * nin * 4
    row_bytes = nnz * nin * rank * 4
    slot_rows_bytes = nnz * 4
    seg_bytes = nblocks * (2 * tile + 3) * 4
    out_once = num_rows * rank * 4
    out_per_block = 2 * nblocks * tile * rank * 4
    if variant == "sorted":
        return (vals_bytes + seg_bytes + num_buffers * idx_bytes + row_bytes
                + out_once + nblocks * rank * 4)
    if variant == "fused":
        return (vals_bytes + slot_rows_bytes + num_buffers * idx_bytes
                + row_bytes + out_per_block)
    if variant == "blocked":
        return (vals_bytes + slot_rows_bytes + idx_bytes + 2 * row_bytes
                + out_per_block)
    return (vals_bytes + slot_rows_bytes + idx_bytes + 2 * row_bytes
            + out_once)


def _gather_free(run, args) -> bool:
    from repro.analysis.hlo_audit import gather_free
    return gather_free(jax.jit(run).lower(*args).as_text())


KERNEL_GRID_SCRIPT = r"""
import json
import jax
from benchmarks.bench_mttkrp import bench_point

d = jax.devices()
out = {{"device": {{"platform": d[0].platform,
                   "device_kind": d[0].device_kind,
                   "device_count": len(d)}},
       "points": [bench_point(n, r, z, repeats={repeats})
                  for n, r, z in {grid!r}]}}
print("RESULT_JSON:" + json.dumps(out, default=str))
"""


def bench_kernel_grid(grid, *, repeats: int = 3) -> dict:
    """:func:`bench_point` over ``grid`` in a one-device child process;
    returns ``{"device": {platform, device_kind, device_count},
    "points": [...]}`` as reported by ``jax.devices()`` there."""
    return run_subprocess_bench(
        KERNEL_GRID_SCRIPT.format(grid=[tuple(g) for g in grid],
                                  repeats=repeats), devices=1)


def bench_point(nmodes: int, rank: int, nnz: int, *, repeats: int = 3,
                seed: int = 0) -> dict:
    from repro.api import KernelConfig
    from repro.core.partition import block_segment_descriptors
    from repro.kernels import ops as kops
    from repro.kernels.autotune import representative_shard
    from repro.kernels.ref import mttkrp_local_ref

    t, part = representative_shard(nmodes, nnz, seed=seed)
    # same tensor, same blocking geometry, row-sorted pad placement
    _, part_s = representative_shard(nmodes, nnz, seed=seed, layout="sorted")
    assert (part_s.tile, part_s.block_p) == (part.tile, part.block_p)
    rng = np.random.default_rng(seed + 1)
    factors = [jnp.asarray(rng.normal(size=(s, rank)).astype(np.float32))
               for s in t.shape]

    def shard_args(p):
        return (jnp.asarray(p.indices[0]), jnp.asarray(p.values[0]),
                jnp.asarray(p.local_rows[0]),
                jnp.asarray(p.block_to_tile[0]), factors)

    args = shard_args(part)
    args_s = shard_args(part_s)
    mask = jnp.asarray(part.tile_visited[0])
    ss, sr = block_segment_descriptors(part_s.local_rows[0], tile=part.tile,
                                       block_p=part.block_p)
    seg_kw = dict(seg_starts=jnp.asarray(ss), seg_rows=jnp.asarray(sr))
    nin = nmodes - 1
    nnz_pad = part.nnz_max  # post-padding nonzeros actually streamed
    flops = _flops(nnz_pad, rank, nin)

    point = {"nmodes": nmodes, "rank": rank, "nnz": nnz,
             "nnz_padded": nnz_pad, "tile": part.tile,
             "block_p": part.block_p, "variants": {}}
    outs = {}
    for variant in VARIANTS:
        # resolve variant + ring depth the way the public API does
        kernel_kw = KernelConfig(use_kernel=True, variant=variant
                                 ).mttkrp_kwargs(nmodes=nmodes, rank=rank)
        if variant == "sorted":
            kernel_kw = {**kernel_kw, **seg_kw}
        vargs = args_s if variant == "sorted" else args

        def run(indices, values, local_rows, block_to_tile, facs,
                _kw=kernel_kw):
            return kops.mttkrp_local(
                indices, values, local_rows, block_to_tile, facs,
                mode=0, num_rows=part.rows_max, tile=part.tile,
                block_p=part.block_p, tile_mask=mask, **_kw)

        jitted = jax.jit(run)
        outs[variant] = np.asarray(jitted(*vargs))
        dt = timeit(lambda: jitted(*vargs).block_until_ready(),
                    repeats=repeats, label=f"ec:{variant}")
        hbm = modelled_hbm_bytes(variant, nnz_pad, rank, nin, part.rows_max,
                                 num_buffers=kernel_kw["num_buffers"],
                                 tile=part.tile, block_p=part.block_p)
        point["variants"][variant] = {
            "time_s": dt,
            "gflops_per_s": flops / dt / 1e9,
            "modelled_flops": modelled_flops(variant, nnz_pad, rank, nin,
                                             part.tile),
            "modelled_hbm_bytes": hbm,
            "effective_hbm_gb_per_s": hbm / dt / 1e9,
            "gather_free": _gather_free(run, vargs),
        }

    # the slot-order oracle on the sorted shard, with vs without the
    # segment_sum hint: real XLA CPU wall time (no interpret mode),
    # bit-identical by construction
    def run_ref(indices, values, local_rows, block_to_tile, facs, *,
                hint):
        del block_to_tile
        return mttkrp_local_ref(indices, values, local_rows, facs, 0,
                                part.rows_max, sorted_rows=hint)

    j_plain = jax.jit(lambda *a: run_ref(*a, hint=False))
    j_hint = jax.jit(lambda *a: run_ref(*a, hint=True))
    assert np.array_equal(np.asarray(j_plain(*args_s)),
                          np.asarray(j_hint(*args_s)))
    t_plain = timeit(lambda: j_plain(*args_s).block_until_ready(),
                     repeats=max(repeats, 3), label="ref_sorted_unhinted")
    t_hint = timeit(lambda: j_hint(*args_s).block_until_ready(),
                    repeats=max(repeats, 3), label="ref_sorted_hinted")
    point["ref_sorted_hint"] = {
        "time_unhinted_s": t_plain,
        "time_hinted_s": t_hint,
        "speedup": t_plain / t_hint,
        # parity or better, with a 15% wall-clock noise margin
        "parity": t_hint <= t_plain * 1.15,
        "bit_identical": True,  # asserted above
    }

    v = point["variants"]
    assert v["fused"]["modelled_hbm_bytes"] < v["blocked"]["modelled_hbm_bytes"]
    assert v["sorted"]["modelled_hbm_bytes"] < v["fused"]["modelled_hbm_bytes"]
    # segmented reduction carries no one-hot scatter FLOPs
    assert v["sorted"]["modelled_flops"] == v["ref"]["modelled_flops"]
    assert v["sorted"]["modelled_flops"] < v["fused"]["modelled_flops"]
    assert v["fused"]["gather_free"] and not v["blocked"]["gather_free"]
    assert v["sorted"]["gather_free"]
    # the kernels compute the same EC bit-for-bit (sorted on its layout
    # produces the same per-row sums as ref on that layout; ref is
    # layout-invariant up to fp addition order, checked exactly in tests)
    assert np.array_equal(outs["sorted"], np.asarray(j_plain(*args_s)))
    return point


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--skip-skew", action="store_true",
                    help="skip the 4-device rebalancer scenario")
    ap.add_argument("--skip-exchange", action="store_true",
                    help="skip the 4-device exchange-overlap scenario")
    ap.add_argument("--skip-ingest", action="store_true",
                    help="skip the out-of-core ingest scenario")
    ap.add_argument("--skip-stream", action="store_true",
                    help="skip the epoch-streaming overlap scenario")
    ap.add_argument("--skip-serve", action="store_true",
                    help="skip the serving-path load-test scenario")
    args = ap.parse_args()

    # span tracing over the whole bench: every scenario runs inside a span,
    # and the artifact carries a per-scenario span summary (counts are
    # deterministic; times informational) instead of hand-rolled timers
    tracer = obs_trace.get_tracer()
    obs_trace.enable()
    per_scenario: dict[str, dict] = {}

    @contextlib.contextmanager
    def scenario(name: str):
        before = tracer.summary()
        with tracer.span(name):
            yield
        after = tracer.summary()
        per_scenario[name] = {
            k: {"count": v["count"]
                - before.get(k, {"count": 0})["count"],
                "total_s": v["total_s"]
                - before.get(k, {"total_s": 0.0})["total_s"]}
            for k, v in after.items()
            if v["count"] > before.get(k, {"count": 0})["count"]}

    if args.quick:
        grid = [(3, 8, 1024)]
    else:
        grid = [(nmodes, rank, nnz)
                for nmodes in (3, 4)
                for rank in (8, 32)
                for nnz in (2048, 8192)]

    # Every scenario that touches a device runs in its own child process,
    # one after another, and this process stays off JAX until they are all
    # done: a process that has touched JAX holds the chip, and a child that
    # needs it then fails or hangs.
    with scenario("kernel_grid"):
        grid_res = bench_kernel_grid(grid, repeats=args.repeats)
    device = grid_res["device"]
    points = grid_res["points"]
    print(f"device: {device['platform']} {device['device_kind']} "
          f"x{device['device_count']}")
    for pt in points:
        f, b = pt["variants"]["fused"], pt["variants"]["blocked"]
        s, h = pt["variants"]["sorted"], pt["ref_sorted_hint"]
        print(f"nmodes={pt['nmodes']} R={pt['rank']} nnz={pt['nnz']}: "
              f"fused {f['time_s']*1e3:.2f}ms "
              f"(model {f['modelled_hbm_bytes']/1e6:.2f}MB) vs blocked "
              f"{b['time_s']*1e3:.2f}ms "
              f"(model {b['modelled_hbm_bytes']/1e6:.2f}MB); sorted "
              f"model {s['modelled_hbm_bytes']/1e6:.2f}MB "
              f"({s['modelled_flops']/1e6:.2f}MF vs fused "
              f"{f['modelled_flops']/1e6:.2f}MF); ref sorted-hint "
              f"{h['speedup']:.3f}x")

    skew = None
    if not args.skip_skew:
        with scenario("skew_rebalance"):
            skew = bench_skew_rebalance(
                nnz=12000 if args.quick else 40000,
                sweeps=4 if args.quick else 6)
        print(f"skew rebalance (4 dev, nnz={skew['nnz']}): max/mean "
              f"{skew['final_imbalance_off']:.3f} -> "
              f"{skew['final_imbalance_on']:.3f}, idle frac reduced by "
              f"{skew['idle_frac_reduction']:.3f}, "
              f"{skew['on']['moved_nnz']} nnz moved; sorted A/B "
              f"bit-equal={skew['sorted_ab']['factors_bitwise_equal']} "
              f"(ref {skew['sorted_ab']['ref']['per_sweep_s']*1e3:.0f}ms vs "
              f"sorted "
              f"{skew['sorted_ab']['sorted']['per_sweep_s']*1e3:.0f}ms"
              f"/sweep)")

    xchg = None
    if not args.skip_exchange:
        with scenario("exchange_overlap"):
            xchg = bench_exchange_overlap(
                nnz=12000 if args.quick else 40000,
                sweeps=3 if args.quick else 6,
                repeats=2 if args.quick else 3)
        print(f"exchange overlap (4 dev, nnz={xchg['nnz']}): blocking "
              f"{xchg['blocking']['per_sweep_s'] * 1e3:.1f}ms/sweep vs "
              f"overlap {xchg['overlap']['per_sweep_s'] * 1e3:.1f}ms "
              f"(speedup {xchg['overlap_speedup']:.3f}); volume modelled "
              f"{xchg['overlap']['modelled_bytes']} B measured "
              f"{xchg['overlap']['measured_bytes']:.0f} B; bf16 wire "
              f"ratio {xchg['bf16_volume_ratio']:.2f}, fit delta "
              f"{xchg['bf16_fit_delta']:.4f}")

    ingest = None
    if not args.skip_ingest:
        with scenario("ingest"):
            ingest = bench_ingest(
                scale=2e-4 if args.quick else 1e-3,
                chunk_nnz=(1 << 14) if args.quick else (1 << 17))
        print(f"ingest ({ingest['profile']}, nnz={ingest['nnz']}): convert "
              f"{ingest['convert_mnnz_per_s']:.2f} Mnnz/s; store "
              f"{ingest['store_bytes'] / 1e6:.1f} MB vs text "
              f"{ingest['tns_bytes'] / 1e6:.1f} MB (ratio "
              f"{ingest['store_to_text_ratio']:.2f}); plan alloc peak "
              f"COO {ingest['coo_plan']['alloc_peak_kb'] / 1024:.1f} MB vs "
              f"store {ingest['store_plan']['alloc_peak_kb'] / 1024:.1f} MB "
              f"(ratio {ingest['alloc_peak_ratio']:.1f}x, chunk reads "
              f"{ingest['store_plan']['plan_chunk_reads']})")

    stream = None
    if not args.skip_stream:
        with scenario("stream_overlap"):
            stream = bench_stream_overlap(
                nnz=400_000 if args.quick else 1_200_000,
                sweeps=2 if args.quick else 3)
        print(f"stream overlap (nnz={stream['nnz']}): budget "
              f"{stream['budget_bytes'] / 2**20:.1f} MiB "
              f"({stream['budget_ratio']:.1f}x under shard bytes), shards "
              f"{stream['shards_per_mode']}; overlap "
              f"{stream['overlap_fraction']:.1%} steady "
              f"({stream['overlap_fraction_total']:.1%} with sweep-1 "
              f"preprocessing), exposed "
              f"{stream['exposed_ms_per_sweep']:.1f} ms/sweep; peak "
              f"{stream['peak_resident_bytes'] / 2**20:.2f} MiB "
              f"(within budget: {stream['peak_within_budget']}); RSS delta "
              f"streamed {stream['streaming_rss_delta_kb'] / 1024:.0f} MB "
              f"vs resident {stream['resident_rss_delta_kb'] / 1024:.0f} MB")

    serve = None
    if not args.skip_serve:
        with scenario("serve_load"):
            serve = bench_serve_load(
                nnz=3000 if args.quick else 6000,
                rows=2048 if args.quick else 8192,
                queries=80 if args.quick else 200)
        print(f"serve load (rows={serve['rows']}): batched "
              f"{serve['batched_s'] * 1e3:.2f}ms "
              f"({serve['batched_qps_rows']:.0f} rows/s) vs per-request "
              f"loop {serve['per_request_loop_s'] * 1e3:.0f}ms (speedup "
              f"{serve['batched_speedup']:.0f}x, parity err "
              f"{serve['parity_max_abs_err']:.1e}); p50/p99 "
              f"{serve['p50_base_ms']:.2f}/{serve['p99_base_ms']:.2f}ms "
              f"idle, p99 {serve['p99_refit_ms']:.2f}ms during refit; "
              f"refresh fit delta {serve['refresh_fit_delta']:.2e} "
              f"(snapshot v{serve['snapshot_version']})")

    # static-analysis gate: concurrency lint + configs allowlist + autotune
    # cache hygiene + plan rules on one small sorted plan; the artifact
    # records the count and check_trajectory fails any nonzero value
    import repro.api as rapi
    from repro.analysis import (check_autotune_cache, check_config_modules,
                                check_plan, lint_default_targets)
    from repro.sparse.io import make_profile_tensor
    acfg = rapi.preset("sorted", {"rank": 8})
    afindings = (lint_default_targets() + check_config_modules()
                 + check_autotune_cache()
                 + check_plan(rapi.plan(
                     make_profile_tensor("amazon", scale=2e-5, seed=0),
                     acfg), acfg, deep=True))
    for f in afindings:
        print(f"analysis: {f}")
    print(f"analysis findings: {len(afindings)}")

    save_result("BENCH_mttkrp", {
        "platform": device["platform"],
        "device_kind": device["device_kind"],
        "device_count": device["device_count"],
        "interpret_mode": device["platform"] != "tpu",
        "analysis_findings": len(afindings),
        "notes": ("interpret-mode times are not hardware-meaningful; "
                  "modelled_hbm_bytes + modelled_flops + gather_free + the "
                  "ref_sorted_hint segment_sum wall times + the "
                  "skew_rebalance ratios + the exchange volume model carry "
                  "the perf claim off-TPU"),
        "points": points,
        "skew_rebalance": skew,
        "exchange_overlap": xchg,
        "ingest": ingest,
        "stream_overlap": stream,
        "serve_load": serve,
        "obs": {"per_scenario": per_scenario},
    }, also_root=True)


if __name__ == "__main__":
    main()
