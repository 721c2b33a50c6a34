"""repro.analysis: plan-rule registry, compiled-HLO audit, concurrency
lint, runtime lock assertions, and the ``python -m repro.analysis`` CLI
contract. Each seeded-defect test names the rule id it regresses."""
import dataclasses
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import repro.api as api
from repro.analysis import (AnalysisError, Finding, LockNotHeldError,
                            apply_baseline, audit_ec_kernel,
                            audit_serving_engine, check_autotune_cache,
                            check_config_modules, check_plan,
                            donation_aliased, gather_free, lint_source,
                            load_baseline, runtime, save_baseline)
from repro.analysis.__main__ import main as analysis_main
from repro.kernels.ops import variant_vmem_bytes


@pytest.fixture(scope="module")
def sorted_cfg():
    return api.preset("sorted", {"rank": 8})


@pytest.fixture(scope="module")
def sorted_plan(small_tensor, sorted_cfg):
    return api.plan(small_tensor, sorted_cfg)


def _swap_mode(plan, part):
    modes = list(plan.modes)
    modes[part.mode] = part
    return dataclasses.replace(plan, modes=tuple(modes))


# -- plan rules (AP-*) -------------------------------------------------------

def test_clean_plan_no_findings(sorted_plan, sorted_cfg):
    assert check_plan(sorted_plan, sorted_cfg) == []


def test_ap_p001_fractional_tile(sorted_plan):
    part = sorted_plan.modes[0]
    assert part.tile > 1
    bad = _swap_mode(sorted_plan,
                     dataclasses.replace(part, rows_max=part.rows_max + 1))
    found = check_plan(bad, rules=["AP-P001"])
    assert found and all(f.rule == "AP-P001" for f in found)
    assert all(f.severity == "error" for f in found)


def test_ap_p002_grid_coverage(sorted_plan):
    part = sorted_plan.modes[0]
    bad = _swap_mode(sorted_plan,
                     dataclasses.replace(part, n_groups=part.n_groups + 1))
    found = check_plan(bad, rules=["AP-P002"])
    assert any("device grid" in f.message for f in found)


def test_ap_p003_nonmonotone_sorted_rows(sorted_plan):
    part = sorted_plan.modes[0]
    assert part.block_layout == "sorted"
    lr = np.array(part.local_rows)
    rows = lr[0]
    inc = np.nonzero(np.diff(rows.astype(np.int64)) > 0)[0]
    assert inc.size, "fixture needs at least one strict increase"
    i = int(inc[0])
    rows[i], rows[i + 1] = rows[i + 1], rows[i]
    bad = _swap_mode(sorted_plan, dataclasses.replace(part, local_rows=lr))
    found = check_plan(bad, rules=["AP-P003"])
    assert any(f.rule == "AP-P003" and "dev=0" in f.location for f in found)


def test_ap_p004_pad_retarget_violation(sorted_plan):
    part = sorted_plan.modes[0]
    n_tiles = part.rows_max // part.tile
    assert n_tiles >= 2
    b2t = np.asarray(part.block_to_tile)
    lr = np.array(part.local_rows)
    # slot 0's row moved into a tile its block does not map to
    wrong_tile = (int(b2t[0, 0]) + 1) % n_tiles
    lr[0, 0] = wrong_tile * part.tile
    bad = _swap_mode(sorted_plan, dataclasses.replace(part, local_rows=lr))
    found = check_plan(bad, rules=["AP-P004"])
    assert any(f.rule == "AP-P004" and "block=0" in f.location
               for f in found)


def test_ap_p005_descriptors_unbuildable(sorted_plan):
    part = sorted_plan.modes[0]
    lr = np.array(part.local_rows)[:, :-1]  # last dim no longer % block_p
    bad = _swap_mode(sorted_plan, dataclasses.replace(part, local_rows=lr))
    found = check_plan(bad, rules=["AP-P005"])
    assert any(f.rule == "AP-P005" and "unbuildable" in f.message
               for f in found)


def test_ap_p006_vmem_budget(sorted_plan, sorted_cfg):
    assert check_plan(sorted_plan, sorted_cfg, rules=["AP-P006"]) == []
    found = check_plan(sorted_plan, sorted_cfg, vmem_budget=1,
                       rules=["AP-P006"])
    assert found and all(f.rule == "AP-P006" for f in found)


def test_ap_p007_streaming_preconditions(sorted_plan, sorted_cfg):
    cfg = sorted_cfg.with_overrides({"runtime.streaming": True})
    found = check_plan(sorted_plan, cfg, rules=["AP-P007"])
    assert any("memory_budget" in f.message for f in found)
    cfg = cfg.with_overrides({"runtime.memory_budget": 2 ** 20})
    found = check_plan(sorted_plan, cfg, rules=["AP-P007"])
    assert any("fully resident" in f.message for f in found)


def test_ap_p008_cache_hygiene(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    path.write_text(json.dumps({
        "_format": 1,
        "cpu|fused|t2048": {"num_buffers": 2},   # pre-v3 key, no device tag
    }))
    monkeypatch.setenv("AMPED_AUTOTUNE_CACHE", str(path))
    found = check_autotune_cache()
    assert any("format" in f.message for f in found)
    assert any("pre-v3" in f.message for f in found)
    assert all(f.severity == "warning" for f in found)
    monkeypatch.setenv("AMPED_AUTOTUNE_CACHE", "")
    assert check_autotune_cache() == []


def test_ap_p009_degenerate_chunk_rows(sorted_plan, sorted_cfg):
    cfg = sorted_cfg.with_overrides({"exchange.variant": "overlap",
                                     "exchange.chunk_rows": 10 ** 6})
    found = check_plan(sorted_plan, cfg, rules=["AP-P009"])
    assert any(f.rule == "AP-P009" and "chunk_rows" in f.message
               for f in found)


def test_ap_c001_config_allowlist(tmp_path):
    (tmp_path / "amped_paper.py").write_text("")
    assert check_config_modules(str(tmp_path)) == []
    (tmp_path / "gemma2_9b.py").write_text("")
    found = check_config_modules(str(tmp_path))
    assert [(f.rule, f.location) for f in found] == [
        ("AP-C001", "configs/gemma2_9b.py")]
    # the clean repo's own configs/ is fully classified
    assert check_config_modules() == []


# -- streaming split validation (AP-P007 deep path) --------------------------

@pytest.fixture(scope="module")
def stream_setup(small_tensor, tmp_path_factory, sorted_cfg):
    from repro.store import TensorStore, write_store_from_coo
    path = str(tmp_path_factory.mktemp("astore") / "t.store")
    write_store_from_coo(small_tensor, path, chunk_nnz=256)
    cfg = sorted_cfg.with_overrides({"runtime.streaming": True,
                                     "runtime.memory_budget": 2 ** 20})
    return api.plan(TensorStore(path), cfg), cfg


def test_ap_p007_clean_split(stream_setup):
    plan, cfg = stream_setup
    assert check_plan(plan, cfg, rules=["AP-P007"]) == []
    assert check_plan(plan, cfg, deep=True) == []


def test_stream_plan_validate_against_tampered(stream_setup):
    from repro.store.plan import split_mode_super_shards
    plan, cfg = stream_setup
    part = plan.modes[0]
    splan = split_mode_super_shards(part, cfg.runtime.memory_budget,
                                    buffers=cfg.runtime.stream_buffers)
    assert splan.validate_against(part, nmodes=plan.nmodes) == []
    bad = dataclasses.replace(splan, shard_bytes=splan.shard_bytes + 4)
    msgs = bad.validate_against(part, nmodes=plan.nmodes)
    assert any("byte model" in m for m in msgs)
    bad = dataclasses.replace(splan, budget_bytes=1)
    msgs = bad.validate_against(part, nmodes=plan.nmodes)
    assert any("exceed the budget" in m for m in msgs)
    wins = tuple(((t0 + 1, t1) if k == 0 and t1 > t0 + 1 else (t0, t1)
                  for k, (t0, t1) in enumerate(dev))
                 for dev in splan.windows)
    bad = dataclasses.replace(splan, windows=wins)
    msgs = bad.validate_against(part, nmodes=plan.nmodes)
    assert any("does not continue coverage" in m for m in msgs)


# -- HLO audit (AH-*) --------------------------------------------------------

def test_gather_free_excludes_collectives():
    assert not gather_free("  %g = f32[8] gather(%a, %b)")
    assert gather_free("  %ag = f32[8] all-gather(%a)")
    assert gather_free("  x = all_gather(y)")
    assert gather_free("no dynamic ops here")


def test_donation_aliased_markers():
    assert donation_aliased("... input_output_alias={ {}: (0, {}) } ...")
    assert not donation_aliased("plain hlo text")


def test_ah_h001_gather_in_fused_path():
    bad = "%r = f32[4] gather(%operand, %indices)"
    found = audit_ec_kernel("fused", nmodes=3, rank=8, lowered_text=bad)
    assert any(f.rule == "AH-H001" for f in found)
    # the rule applies to the gather-free contract paths only
    assert audit_ec_kernel("ref", nmodes=3, rank=8, lowered_text=bad) == []
    clean = "%r = f32[4] all-gather(%operand)"
    assert audit_ec_kernel("sorted", nmodes=3, rank=8,
                           lowered_text=clean) == []


def test_ec_kernel_audit_real_lowerings(sorted_plan, sorted_cfg):
    part = sorted_plan.modes[0]
    for variant in ("ref", "fused", "sorted"):
        found = audit_ec_kernel(variant, nmodes=3, rank=8, tile=part.tile,
                                block_p=part.block_p)
        assert found == [], (variant, found)


def _spec(plan, cfg):
    from repro.comm.spec import resolve_exchange_spec
    return resolve_exchange_spec(cfg.exchange, plan=plan, rank=cfg.rank)


def test_expected_hlo_markers(sorted_plan, sorted_cfg):
    cfg = sorted_cfg.with_overrides({"exchange.variant": "overlap",
                                     "exchange.wire_dtype": "bfloat16"})
    spec = _spec(sorted_plan, cfg)
    assert spec.expected_hlo_markers(multi_device=True) == {
        "collective_permute": True, "wire_bf16": True}
    assert spec.expected_hlo_markers(multi_device=False) == {
        "collective_permute": False, "wire_bf16": False}


def test_ah_h002_to_h005_synthetic_texts(sorted_plan, sorted_cfg):
    from repro.analysis.hlo_audit import audit_update_text
    cfg = sorted_cfg.with_overrides({"exchange.variant": "overlap",
                                     "exchange.wire_dtype": "bfloat16"})
    spec = _spec(sorted_plan, cfg)
    ok_low = "bf16[8] convert(%x) collective-permute(%y)"
    ok_comp = "collective-permute-start input_output_alias={...}"
    rules = {f.rule for f in audit_update_text(
        ok_low, ok_comp, mode=0, exchange_spec=spec, backend="tpu",
        multi_device=True)}
    assert rules == set()
    # host transfer in the sweep
    rules = {f.rule for f in audit_update_text(
        ok_low + " infeed()", ok_comp, mode=0, exchange_spec=spec,
        backend="tpu", multi_device=True)}
    assert "AH-H002" in rules
    # overlap gather with no collective-permute in either text
    rules = {f.rule for f in audit_update_text(
        "bf16[8] convert(%x)", "input_output_alias={}", mode=0,
        exchange_spec=spec, backend="tpu", multi_device=True)}
    assert "AH-H003" in rules
    # donation not aliased (non-CPU backends only)
    rules = {f.rule for f in audit_update_text(
        ok_low, "collective-permute()", mode=0, exchange_spec=spec,
        backend="tpu", multi_device=True)}
    assert "AH-H004" in rules
    assert "AH-H004" not in {f.rule for f in audit_update_text(
        ok_low, "collective-permute()", mode=0, exchange_spec=spec,
        backend="cpu", multi_device=True)}
    # bf16 requested but absent from the lowered module
    rules = {f.rule for f in audit_update_text(
        "f32[8] collective-permute(%y)", ok_comp, mode=0,
        exchange_spec=spec, backend="tpu", multi_device=True)}
    assert "AH-H005" in rules


def test_solver_audit_clean_single_device(small_tensor, sorted_cfg):
    plan = api.plan(small_tensor, sorted_cfg)
    solver = api.compile(plan, sorted_cfg)
    try:
        assert solver.audit() == []
    finally:
        solver.close()


_MD_SCRIPT = r"""
import json
import repro.api as api
from repro.core.coo import random_sparse

t = random_sparse((40, 30, 20), 600, seed=7, distribution="zipf")
cfg = api.preset("sorted", {"rank": 8}).with_overrides({
    "runtime.num_devices": 4,
    "exchange.variant": "overlap",
    "exchange.wire_dtype": "bfloat16",
})
plan = api.plan(t, cfg)
solver = api.compile(plan, cfg)
try:
    findings = solver.audit()
finally:
    solver.close()
print(json.dumps([str(f) for f in findings]))
"""


def test_solver_audit_clean_multi_device():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", _MD_SCRIPT], env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))),
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    findings = json.loads(out.stdout.strip().splitlines()[-1])
    assert findings == []


def test_ah_h006_serving_retrace(small_tensor):
    from repro.serve.engine import FactorSnapshot, ServingEngine
    rng = np.random.default_rng(0)
    snap = FactorSnapshot.from_arrays(
        [rng.normal(size=(s, 4)).astype(np.float32)
         for s in (32, 16, 8)],
        np.ones(4, np.float32), version=1, source="test")
    engine = ServingEngine(snap)
    engine.reconstruct_batch(np.zeros((3, 3), np.int64))
    assert audit_serving_engine(engine) == []
    engine._reconstruct_shapes.add(37)   # a shape outside the bucket grid
    found = audit_serving_engine(engine)
    assert any(f.rule == "AH-H006" for f in found)


# -- concurrency lint (AC-*) -------------------------------------------------

_GUARDED = '''
import threading
class C:
    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0  # guarded-by: _lock
    def bump(self):
        {body}
'''


def test_ac_l001_unguarded_access():
    found = lint_source(_GUARDED.format(body="self.count += 1"), "f.py")
    assert [f.rule for f in found] == ["AC-L001"]
    assert "f.py:8" in found[0].location


def test_ac_l001_with_block_ok():
    src = _GUARDED.format(
        body="with self._lock:\n            self.count += 1")
    assert lint_source(src, "f.py") == []


def test_ac_l001_holds_annotation_ok():
    src = _GUARDED.format(body="self.count += 1").replace(
        "def bump(self):", "def bump(self):  # holds: _lock")
    assert lint_source(src, "f.py") == []


def test_ac_l001_closure_does_not_inherit_lock():
    src = _GUARDED.format(body="""with self._lock:
            def later():
                return self.count
            return later""")
    found = lint_source(src, "f.py")
    assert [f.rule for f in found] == ["AC-L001"]


def test_ac_l002_l003_unknown_locks():
    src = '''
class C:
    def __init__(self):
        self.x = 0  # guarded-by: _missing
    def get(self):  # holds: _also_missing
        return 1
'''
    rules = sorted(f.rule for f in lint_source(src, "f.py"))
    assert rules == ["AC-L002", "AC-L003"]


def test_ac_l000_syntax_error():
    found = lint_source("def broken(:\n", "f.py")
    assert [f.rule for f in found] == ["AC-L000"]


def test_default_targets_lint_clean():
    from repro.analysis import lint_default_targets
    assert lint_default_targets() == []


def test_subclass_inherits_guards():
    src = _GUARDED.format(body="pass") + '''
class D(C):
    def bump2(self):
        self.count -= 1
'''
    found = lint_source(src, "f.py")
    assert [f.rule for f in found] == ["AC-L001"]


# -- runtime lock assertions -------------------------------------------------

def test_assert_holds_disabled_noop(monkeypatch):
    monkeypatch.delenv(runtime.ENV_ASSERT, raising=False)
    runtime.assert_holds(threading.Lock(), "_lock")  # no raise


def test_assert_holds_enabled(monkeypatch):
    monkeypatch.setenv(runtime.ENV_ASSERT, "1")
    lock = threading.Lock()
    with pytest.raises(LockNotHeldError):
        runtime.assert_holds(lock, "_lock")
    with lock:
        runtime.assert_holds(lock, "_lock")
    rlock = threading.RLock()
    with pytest.raises(LockNotHeldError):
        runtime.assert_holds(rlock, "_rlock")
    with rlock:
        runtime.assert_holds(rlock, "_rlock")


def test_streamer_trackers_require_stats_lock(monkeypatch):
    # regression for the AC-L001 defect: _track_add/_track_drop mutated
    # _cur_bytes/stats without _stats_lock
    from repro.sparse.stream import _StreamerBase
    monkeypatch.setenv(runtime.ENV_ASSERT, "1")
    s = _StreamerBase(prefetch=1)
    try:
        with pytest.raises(LockNotHeldError):
            s._track_add("k")
        with s._stats_lock:
            s._track_add("k")
            s._track_drop("k")
    finally:
        s.close()


def test_window_spill_counters(tmp_path):
    from repro.sparse.stream import WindowSpill
    arrs = tuple(np.arange(3, dtype=np.int32) for _ in range(5))
    with WindowSpill(str(tmp_path / "spill")) as sp:
        assert sp.load(0, 0, (0, 0, 2, 6, 2)) is None
        sp.save(0, 0, (0, 0, 2, 6, 2), arrs)
        assert sp.load(0, 0, (0, 0, 2, 6, 2)) is not None
        assert sp.counters() == (1, 1)


def test_batcher_close_rejects_queued():
    # regression for the AC-L001 defect: close() drained _queue outside _cv
    import time
    from repro.serve.batcher import MicroBatcher, RejectedError
    started, release = threading.Event(), threading.Event()

    def handler(idx):
        started.set()
        release.wait(timeout=10)
        return np.zeros(idx.shape[0], np.float32)

    b = MicroBatcher(handler, max_delay_s=0.0)
    errs = []

    def submit():
        try:
            b.submit(np.zeros((1, 3), np.int64), deadline_s=10.0)
        except RejectedError as e:
            errs.append(e)

    t1 = threading.Thread(target=submit)
    t1.start()
    assert started.wait(timeout=5)
    t2 = threading.Thread(target=submit)  # queued behind the blocked batch
    t2.start()
    for _ in range(500):          # wait until t2's request is queued
        with b._cv:
            if b._queue:
                break
        time.sleep(0.01)
    threading.Timer(0.2, release.set).start()
    b.close()
    t1.join(timeout=5)
    t2.join(timeout=5)
    assert errs, "queued request must fail with RejectedError on close"
    with pytest.raises(RejectedError):
        b.submit(np.zeros((1, 3), np.int64))


def test_checkpoint_async_exception_surfaced(tmp_path, monkeypatch):
    # regression for the unguarded _save_exc hand-off (now _exc_lock)
    from repro.api.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    monkeypatch.setattr(mgr, "_save_sync_flat",
                        lambda *a: (_ for _ in ()).throw(IOError("disk")))
    mgr.save(1, {"a": np.zeros(2)}, block=False)
    with pytest.raises(IOError):
        mgr.wait()
    mgr.wait()  # exception consumed exactly once


def test_mode_histogram_owns_its_data(small_tensor, tmp_path):
    # regression for the memmap-lifetime defect: same-dtype asarray
    # returned a view pinning the sidecar handle open
    from repro.store import TensorStore, write_store_from_coo
    path = str(tmp_path / "h.store")
    write_store_from_coo(small_tensor, path, chunk_nnz=256)
    hist = TensorStore(path).mode_histogram(0)
    assert not isinstance(hist, np.memmap)
    assert hist.base is None


# -- api wiring --------------------------------------------------------------

def test_plan_analyze_modes(small_tensor, sorted_cfg, monkeypatch):
    assert api.plan(small_tensor, sorted_cfg, analyze="warn") is not None
    with pytest.raises(ValueError):
        api.plan(small_tensor, sorted_cfg, analyze="nope")
    import repro.analysis as analysis
    monkeypatch.setattr(
        analysis, "check_plan",
        lambda p, c, **kw: [Finding("AP-TEST", "error", "seeded")])
    with pytest.raises(AnalysisError) as ei:
        api.plan(small_tensor, sorted_cfg, analyze="strict")
    assert "AP-TEST" in str(ei.value)
    # warn mode reports but does not raise
    assert api.plan(small_tensor, sorted_cfg, analyze="warn") is not None


def test_variant_vmem_model():
    kw = dict(tile=256, block_p=512, nin=2, num_buffers=2)
    assert variant_vmem_bytes("ref", rank=32, **kw) == 0
    blocked = variant_vmem_bytes("blocked", rank=32, **kw)
    fused = variant_vmem_bytes("fused", rank=32, **kw)
    srt = variant_vmem_bytes("sorted", rank=32, **kw)
    assert 0 < blocked < fused < srt
    assert variant_vmem_bytes("fused", rank=64, **kw) > fused


# -- baseline + CLI contract -------------------------------------------------

def test_baseline_roundtrip(tmp_path):
    f1 = Finding("AC-L001", "error", "msg", "f.py:8")
    f2 = Finding("AP-P001", "error", "msg", "mode=0")
    path = str(tmp_path / "b.json")
    save_baseline(path, [f1])
    kept, suppressed = apply_baseline([f1, f2], load_baseline(path))
    assert kept == [f2] and suppressed == [f1]


def test_cli_usage_error_exits_2():
    with pytest.raises(SystemExit) as ei:
        analysis_main(["--preset", "sorted", "--all-presets"])
    assert ei.value.code == 2


def test_cli_clean_fast_run(capsys, monkeypatch):
    monkeypatch.setenv("AMPED_AUTOTUNE_CACHE", "")
    rc = analysis_main(["--skip-compile", "--preset", "paper",
                        "--scale", "2e-5", "--rank", "8"])
    assert rc == 0
    assert "analysis: clean" in capsys.readouterr().out


def test_cli_seeded_defect_and_baseline(tmp_path, capsys, monkeypatch):
    monkeypatch.setenv("AMPED_AUTOTUNE_CACHE", "")
    bad = tmp_path / "bad.py"
    bad.write_text(_GUARDED.format(body="self.count += 1"))
    args = ["--skip-compile", "--scale", "2e-5", "--rank", "8",
            "--lint-file", str(bad)]
    rc = analysis_main(args)
    out = capsys.readouterr().out
    assert rc == 1 and "AC-L001" in out
    base = str(tmp_path / "base.json")
    assert analysis_main(args + ["--write-baseline", base]) == 0
    rc = analysis_main(args + ["--baseline", base])
    out = capsys.readouterr().out
    assert rc == 0 and "baselined" in out
