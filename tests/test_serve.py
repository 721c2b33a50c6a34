"""Decomposition-as-a-service subsystem (repro.serve).

Covers the ISSUE-7 battery: engine query parity with the float64 reference
across shape buckets, top-k vs dense argsort, admission-control rejection
under overload, concurrent queries during a background refit against a
bitwise-stable snapshot, rolling-deploy rollback on an injected fit
regression, and incremental-refresh fit agreement with a from-scratch
refit on a grown store — plus the store append/refresh primitives and the
bounds/rank validation satellites they ride on.
"""
import os
import shutil
import threading

import numpy as np
import pytest

import repro.api as api
from repro.api.config import DecomposeConfig, RuntimeConfig
from repro.core.coo import SparseTensor
from repro.core.decompose import validate_coords
from repro.serve import (CPService, FactorSnapshot, MicroBatcher,
                         RejectedError, ServiceMetrics, ServingEngine,
                         store_fit)
from repro.serve.metrics import LatencyHistogram
from repro.sparse.io import make_lowrank_tensor
from repro.store import TensorStore, append_to_store, write_store_from_coo
from repro.store.format import StoreFormatError
from repro.api.checkpoint import CheckpointManager

RANK = 4
SHAPE = (48, 40, 32)
CHUNK = 512


def _config(ckpt_dir=None, seed=0):
    return DecomposeConfig(rank=RANK, runtime=RuntimeConfig(
        num_devices=1, tol=0.0, seed=seed, checkpoint_dir=ckpt_dir))


@pytest.fixture(scope="module")
def lowrank():
    """An exactly rank-RANK sparse tensor plus its base/append split."""
    t = make_lowrank_tensor(SHAPE, RANK, 3000, seed=0)
    base_n = int(t.nnz * 0.85)
    return t, base_n


@pytest.fixture(scope="module")
def fitted(lowrank, tmp_path_factory):
    """Base store + 10-sweep fit + checkpoint directory (shared,
    read-only — tests that append copy the store first)."""
    t, base_n = lowrank
    root = tmp_path_factory.mktemp("serve_fit")
    store_path = str(root / "base.store")
    base = SparseTensor(t.indices[:base_n], t.values[:base_n], t.shape)
    write_store_from_coo(base, store_path, chunk_nnz=CHUNK)
    ckpt = str(root / "ckpts")
    cfg = _config(ckpt_dir=ckpt)
    with api.compile(api.plan(TensorStore(store_path), cfg), cfg) as solver:
        result = solver.run(10)
    return {"store_path": store_path, "ckpt": ckpt, "result": result}


def _copy_store(fitted, tmp_path):
    dst = str(tmp_path / "grow.store")
    shutil.copytree(fitted["store_path"], dst)
    return dst


# -- engine ---------------------------------------------------------------

def test_reconstruct_parity_across_buckets(fitted):
    """Batched fp32 engine values match float64 reconstruct_at for every
    request size across several shape buckets, while the engine traces at
    most one kernel per bucket (never one per request size)."""
    res = fitted["result"]
    engine = ServingEngine(FactorSnapshot.from_result(res))
    rng = np.random.default_rng(1)
    sizes = [1, 2, 3, 7, 8, 9, 17, 33, 100, 257]
    for n in sizes:
        coords = np.stack([rng.integers(0, s, size=n) for s in SHAPE],
                          axis=1)
        got = engine.reconstruct_batch(coords)
        want = res.reconstruct_at(coords)
        assert got.shape == (n,) and got.dtype == np.float32
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    buckets = {max(8, 1 << (int(n) - 1).bit_length()) for n in sizes}
    assert engine.metrics.gauge("reconstruct_buckets") <= len(buckets)


def test_reconstruct_batch_chunks_beyond_max_batch(fitted):
    res = fitted["result"]
    engine = ServingEngine(FactorSnapshot.from_result(res), max_batch=64)
    rng = np.random.default_rng(2)
    coords = np.stack([rng.integers(0, s, size=300) for s in SHAPE], axis=1)
    np.testing.assert_allclose(engine.reconstruct_batch(coords),
                               res.reconstruct_at(coords),
                               rtol=1e-4, atol=1e-5)


def test_topk_matches_dense_argsort():
    """Engine top-k over the free mode == numpy dense scoring + argsort,
    on a random (tie-free) snapshot, for single and batched queries."""
    rng = np.random.default_rng(3)
    shape, rank, k = (12, 37, 9), 5, 6
    factors = [rng.standard_normal((s, rank)).astype(np.float32)
               for s in shape]
    lam = rng.uniform(0.5, 2.0, rank).astype(np.float32)
    engine = ServingEngine(
        FactorSnapshot.from_arrays(factors, lam, version=1))
    fixed = np.array([4, 0, 7])
    scores, idx = engine.topk_slice(fixed, mode=1, k=k)
    dense = np.zeros(shape[1])
    for j in range(shape[1]):
        acc = lam.astype(np.float64).copy()
        acc *= factors[0][4].astype(np.float64)
        acc *= factors[1][j].astype(np.float64)
        acc *= factors[2][7].astype(np.float64)
        dense[j] = acc.sum()
    order = np.argsort(-dense)[:k]
    np.testing.assert_array_equal(idx, order)
    np.testing.assert_allclose(scores, dense[order], rtol=1e-4, atol=1e-5)
    # batched: each row independently correct, free-mode column ignored
    batch = np.array([[4, 999, 7], [0, 0, 0], [11, 3, 8]])
    bs, bi = engine.topk_slice(batch, mode=1, k=k)
    np.testing.assert_array_equal(bi[0], order)
    np.testing.assert_allclose(bs[0], scores, rtol=1e-6)


def test_topk_validation():
    rng = np.random.default_rng(4)
    factors = [rng.standard_normal((8, 3)).astype(np.float32)
               for _ in range(3)]
    engine = ServingEngine(FactorSnapshot.from_arrays(
        factors, np.ones(3, np.float32), version=1))
    with pytest.raises(ValueError, match="mode 5"):
        engine.topk_slice(np.zeros(3, np.int64), mode=5, k=2)
    with pytest.raises(ValueError, match="k="):
        engine.topk_slice(np.zeros(3, np.int64), mode=1, k=99)
    with pytest.raises(IndexError, match="mode 0"):
        engine.topk_slice(np.array([88, 0, 0]), mode=1, k=2)


def test_publish_swap_and_validation(fitted):
    res = fitted["result"]
    engine = ServingEngine(FactorSnapshot.from_result(res))
    v2 = FactorSnapshot.from_arrays(res.factors, res.lam, version=2)
    engine.publish(v2)
    assert engine.version == 2
    with pytest.raises(ValueError, match="version"):
        engine.publish(FactorSnapshot.from_arrays(res.factors, res.lam,
                                                  version=2))
    bad_rank = [np.zeros((s, RANK + 1), np.float32) for s in SHAPE]
    with pytest.raises(ValueError, match="rank"):
        engine.publish(FactorSnapshot.from_arrays(
            bad_rank, np.ones(RANK + 1, np.float32), version=3))


# -- bounds/rank validation satellites ------------------------------------

def test_reconstruct_at_rejects_out_of_range(fitted):
    res = fitted["result"]
    with pytest.raises(IndexError, match=r"mode 1.*row 1"):
        res.reconstruct_at(np.array([[0, 0, 0], [0, -1, 0]]))
    with pytest.raises(IndexError, match="mode 2"):
        res.reconstruct_at(np.array([[0, 0, SHAPE[2]]]))
    with pytest.raises(ValueError, match=r"\(k, 3\)"):
        res.reconstruct_at(np.zeros((4, 2), np.int64))


def test_validate_coords_passthrough():
    ind = validate_coords(np.array([[0, 1], [3, 2]], np.int32), (4, 3))
    assert ind.dtype == np.int64


def test_engine_rejects_out_of_range(fitted):
    engine = ServingEngine(FactorSnapshot.from_result(fitted["result"]))
    with pytest.raises(IndexError, match="mode 0"):
        engine.reconstruct_batch(np.array([[SHAPE[0], 0, 0]]))


def test_restore_rank_mismatch_names_both_ranks(fitted, tmp_path):
    """A checkpoint written at another rank fails restore with a clear
    ValueError naming both ranks, not a broadcast error."""
    store = TensorStore(fitted["store_path"])
    cfg8 = DecomposeConfig(rank=8, runtime=RuntimeConfig(
        num_devices=1, tol=0.0, seed=0, checkpoint_dir=fitted["ckpt"]))
    with api.compile(api.plan(store, cfg8), cfg8) as solver:
        with pytest.raises(ValueError, match=r"rank 4.*rank 8"):
            solver.restore()


def test_boot_rank_mismatch_names_both_ranks(fitted):
    with pytest.raises(ValueError, match=r"rank 4.*rank 9"):
        CPService.boot(fitted["ckpt"], rank=9)


def test_load_state_validates_mode_shape(fitted):
    store = TensorStore(fitted["store_path"])
    cfg = _config()
    with api.compile(api.plan(store, cfg), cfg) as solver:
        bad = [np.ones((s + 1, RANK), np.float32) for s in SHAPE]
        with pytest.raises(ValueError, match="mode 0"):
            solver.load_state(bad, np.ones(RANK, np.float32))


# -- store append / refresh ----------------------------------------------

def test_append_to_store_matches_full_rewrite(lowrank, fitted, tmp_path):
    """Append-then-read equals writing the concatenated tensor: identical
    data bytes, chunk stats, and histograms."""
    t, base_n = lowrank
    grown = _copy_store(fitted, tmp_path)
    append_to_store(grown, t.indices[base_n:].astype(np.int64),
                    t.values[base_n:])
    ref = str(tmp_path / "ref.store")
    write_store_from_coo(t, ref, chunk_nnz=CHUNK)
    sa, sb = TensorStore(grown), TensorStore(ref)
    assert sa.nnz == sb.nnz == t.nnz
    assert [c["min"] for c in sa.manifest["chunks"]] == \
        [c["min"] for c in sb.manifest["chunks"]]
    assert [c["hist"] for c in sa.manifest["chunks"]] == \
        [c["hist"] for c in sb.manifest["chunks"]]
    assert abs(sa.manifest["values_sumsq"] -
               sb.manifest["values_sumsq"]) < 1e-6
    for d in range(3):
        np.testing.assert_array_equal(sa.mode_histogram(d),
                                      sb.mode_histogram(d))
    for (ia, va), (ib, vb) in zip(sa.iter_chunks(), sb.iter_chunks()):
        np.testing.assert_array_equal(ia, ib)
        np.testing.assert_array_equal(va, vb)


def test_append_validates(fitted, tmp_path):
    grown = _copy_store(fitted, tmp_path)
    with pytest.raises(ValueError, match="out of range"):
        append_to_store(grown, np.array([[SHAPE[0], 0, 0]]),
                        np.ones(1, np.float32))
    with pytest.raises(ValueError, match="negative"):
        append_to_store(grown, np.array([[-1, 0, 0]]),
                        np.ones(1, np.float32))


def test_store_refresh_delta_and_noop(lowrank, fitted, tmp_path):
    t, base_n = lowrank
    grown = _copy_store(fitted, tmp_path)
    store = TensorStore(grown)
    assert store.refresh() is None  # digest unchanged -> no-op
    old_nnz, old_chunks = store.nnz, store.num_chunks
    append_to_store(grown, t.indices[base_n:].astype(np.int64),
                    t.values[base_n:])
    delta = store.refresh()
    assert delta["old_nnz"] == old_nnz and delta["new_nnz"] == t.nnz
    assert delta["appended_nnz"] == t.nnz - base_n
    assert delta["first_changed_chunk"] == old_nnz // CHUNK
    assert store.nnz == t.nnz and store.num_chunks >= old_chunks
    # appended rows readable through the refreshed memmaps
    rows = store.appended_mode_rows(delta["old_nnz"])
    for d in range(3):
        np.testing.assert_array_equal(
            rows[d], np.unique(t.indices[base_n:, d]))


def test_store_refresh_rejects_rewrite(lowrank, fitted, tmp_path):
    t, _ = lowrank
    grown = _copy_store(fitted, tmp_path)
    store = TensorStore(grown)
    shutil.rmtree(grown)
    small = SparseTensor(t.indices[:100], t.values[:100], t.shape)
    write_store_from_coo(small, grown, chunk_nnz=CHUNK)
    with pytest.raises(StoreFormatError, match="shrank"):
        store.refresh()


# -- metrics / batcher ----------------------------------------------------

def test_latency_histogram_percentiles():
    h = LatencyHistogram()
    for ms in range(1, 101):           # 1..100 ms uniform
        h.record(ms * 1e-3)
    assert h.count == 100
    p50, p99 = h.percentile(0.50), h.percentile(0.99)
    assert 0.04 <= p50 <= 0.07         # ~50 ms, one log-bucket slack
    assert 0.08 <= p99 <= 0.14         # ~99 ms
    snap = h.snapshot()
    assert snap["count"] == 100 and snap["p50_ms"] >= 1.0


def test_metrics_report_shape():
    m = ServiceMetrics()
    m.inc("queries_total", 5)
    m.set_gauge("queue_depth", 2)
    with m.time("reconstruct"):
        pass
    rep = m.metrics_report()
    assert rep["counters"]["queries_total"] == 5
    assert rep["gauges"]["queue_depth"] == 2
    assert rep["latency"]["reconstruct"]["count"] == 1
    assert rep["qps"] > 0


def test_batcher_coalesces_and_scatters():
    calls = []

    def handler(ind):
        calls.append(ind.shape[0])
        return ind[:, 0].astype(np.float32) * 2

    with MicroBatcher(handler, max_delay_s=0.2, max_depth=16) as mb:
        results = {}

        def client(i):
            results[i] = mb.submit(np.array([[i, 0], [i + 1, 0]]))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    for i in range(4):
        np.testing.assert_array_equal(results[i], [2 * i, 2 * (i + 1)])
    assert sum(calls) == 8
    assert len(calls) < 4  # at least some coalescing happened


def test_batcher_rejects_when_queue_full():
    """Admission control: with the drain thread wedged in the handler and
    the queue at max_depth, the next submit fails fast with
    RejectedError."""
    gate = threading.Event()
    entered = threading.Event()

    def handler(ind):
        entered.set()
        gate.wait(5)
        return np.zeros(ind.shape[0], np.float32)

    mb = MicroBatcher(handler, max_delay_s=0.0, max_depth=2,
                      default_deadline_s=10.0)
    fillers = []
    req = np.zeros((1, 2), np.int64)
    t0 = threading.Thread(target=lambda: mb.submit(req))
    try:
        t0.start()
        assert entered.wait(5)       # drain thread is inside the handler
        fillers = [threading.Thread(target=lambda: mb.submit(req))
                   for _ in range(2)]
        for th in fillers:
            th.start()
        deadline = 50
        while mb.metrics.gauge("queue_depth", 0) < 2 and deadline:
            threading.Event().wait(0.02)
            deadline -= 1
        assert mb.metrics.gauge("queue_depth", 0) == 2
        with pytest.raises(RejectedError, match="max depth"):
            mb.submit(req)
        assert mb.metrics.counter("rejected_total") == 1
    finally:
        gate.set()
        for th in [t0] + fillers:
            th.join(5)
        mb.close()


def test_batcher_deadline_rejection():
    def handler(ind):
        threading.Event().wait(0.2)  # slower than the deadline
        return np.zeros(ind.shape[0], np.float32)

    with MicroBatcher(handler, max_delay_s=0.0) as mb:
        with pytest.raises(RejectedError, match="deadline"):
            mb.submit(np.zeros((1, 2), np.int64), deadline_s=0.05)


def test_batcher_propagates_handler_errors(fitted):
    engine = ServingEngine(FactorSnapshot.from_result(fitted["result"]))
    with MicroBatcher(engine.reconstruct_batch) as mb:
        with pytest.raises(IndexError, match="mode 0"):
            mb.submit(np.array([[-1, 0, 0]]))


# -- service lifecycle ----------------------------------------------------

def test_boot_serves_checkpoint(fitted):
    res = fitted["result"]
    with CPService.boot(fitted["ckpt"]) as svc:
        assert svc.engine.version == 1
        assert svc.engine.snapshot.rank == RANK
        coords = np.array([[1, 2, 3], [0, 0, 0]])
        np.testing.assert_allclose(svc.reconstruct(coords),
                                   res.reconstruct_at(coords),
                                   rtol=1e-4, atol=1e-5)
        rep = svc.metrics_report()
        assert rep["snapshot"]["version"] == 1
        assert rep["counters"]["queries_total"] >= 1


def test_boot_no_checkpoint_raises(tmp_path):
    with pytest.raises(ValueError, match="no verified checkpoint"):
        CPService.boot(str(tmp_path / "empty"))


def test_incremental_refresh_matches_scratch_refit(lowrank, fitted,
                                                   tmp_path):
    """The acceptance gate: after an append, the frozen-row warm-start
    refit publishes a snapshot whose exact store fit is within 1e-3 of a
    from-scratch refit of the grown store."""
    t, base_n = lowrank
    grown = _copy_store(fitted, tmp_path)
    store = TensorStore(grown)
    with CPService.boot(fitted["ckpt"], store=store,
                        config=_config()) as svc:
        append_to_store(grown, t.indices[base_n:].astype(np.int64),
                        t.values[base_n:])
        event = svc.refresh(sweeps=6)
        assert event["published"], event
        assert svc.engine.version == 2
        warm_fit = event["refit"]["fit"]
        assert event["refit"]["frozen"]
        # at least one mode keeps frozen rows (small modes may have every
        # row touched by a 15% append)
        assert any(f < 1.0 for f in event["refit"]["affected_fraction"])
    cfg = _config(seed=0)
    store2 = TensorStore(grown)
    with api.compile(api.plan(store2, cfg), cfg) as solver:
        scratch = solver.run(12)
    scratch_fit = store_fit(scratch.factors, scratch.lam, store2)
    assert abs(warm_fit - scratch_fit) < 1e-3, (warm_fit, scratch_fit)
    assert warm_fit > 0.99  # both converged on the exactly-low-rank data


def test_refresh_noop_without_growth(fitted, tmp_path):
    grown = _copy_store(fitted, tmp_path)
    with CPService.boot(fitted["ckpt"], store=TensorStore(grown),
                        config=_config()) as svc:
        event = svc.refresh()
        assert event == {"refreshed": False, "reason": "store unchanged"}
        assert svc.engine.version == 1


def test_concurrent_queries_during_background_refit(lowrank, fitted,
                                                    tmp_path):
    """Queries keep flowing during a background refit and every answer is
    bitwise equal to one of the two published snapshots' answers — the
    blue/green swap is atomic, no torn reads, readers never block."""
    t, base_n = lowrank
    grown = _copy_store(fitted, tmp_path)
    store = TensorStore(grown)
    rng = np.random.default_rng(5)
    coords = np.stack([rng.integers(0, s, size=64) for s in SHAPE], axis=1)
    with CPService.boot(fitted["ckpt"], store=store,
                        config=_config()) as svc:
        snap_v1 = svc.engine.snapshot
        want_v1 = svc.reconstruct(coords)
        append_to_store(grown, t.indices[base_n:].astype(np.int64),
                        t.values[base_n:])
        event = svc.refresh(sweeps=4, wait=False)
        assert event["background"]
        answers = []
        while svc.metrics.gauge("refit_in_progress", 0) == 1 or \
                not answers:
            answers.append(svc.reconstruct(coords))
        done = svc.wait_refresh()
        assert done["published"] and svc.engine.version == 2
        assert svc.engine.snapshot is not snap_v1
        want_v2 = svc.reconstruct(coords)
    for a in answers:
        assert np.array_equal(a, want_v1) or np.array_equal(a, want_v2)
    # the model actually moved, so the bitwise check is meaningful
    assert not np.array_equal(want_v1, want_v2)


def test_rolling_deploy_rollback_on_regression(lowrank, fitted, tmp_path):
    """An injected bad checkpoint (random factors) regresses the held-out
    sample fit -> deploy rolls back; the good checkpoint then publishes."""
    t, base_n = lowrank
    grown = _copy_store(fitted, tmp_path)
    ckpt = str(tmp_path / "deploy_ckpts")
    shutil.copytree(fitted["ckpt"], ckpt)
    store = TensorStore(grown)
    with CPService.boot(ckpt, store=store, config=_config()) as svc:
        rng = np.random.default_rng(6)
        bad = {"factors": [rng.standard_normal((s, RANK)).astype(np.float32)
                           for s in SHAPE],
               "lam": np.ones(RANK, np.float32),
               "fits": np.array([0.0])}
        CheckpointManager(ckpt).save(99, bad)
        event = svc.deploy_checkpoint()   # latest == the bad one
        assert event["rolled_back"] and not event["published"]
        assert svc.engine.version == 1    # rollback kept the incumbent
        assert event["sample_fit_candidate"] < event["sample_fit_current"]
        assert svc.metrics.counter("rollbacks_total") == 1
        # promoting the good checkpoint still works
        good_step = fitted["result"].sweeps
        event2 = svc.deploy_checkpoint(step=good_step)
        assert event2["published"] and svc.engine.version == 2


def test_export_snapshot_hook(fitted):
    store = TensorStore(fitted["store_path"])
    cfg = _config()
    with api.compile(api.plan(store, cfg), cfg) as solver:
        solver.run(2)
        snap = solver.export_snapshot(version=7, source="unit test")
    assert isinstance(snap, FactorSnapshot)
    assert snap.version == 7 and snap.shape == SHAPE and snap.rank == RANK
    res = solver.result()
    for f, g in zip(snap.host_factors(), res.factors):
        np.testing.assert_array_equal(f, np.asarray(g, np.float32))
