"""Unified observability layer (repro.obs).

Covers the ISSUE-10 battery: the shared monotonic clock (and its adoption
by every threaded runtime module), the span tracer (nesting, thread-local
stacks, disabled no-op), Chrome-trace export + schema validation (paired
B/E, non-overlapping siblings, coverage), the metrics registry
(counters/gauges/histograms/providers), the LogHistogram torn-snapshot
concurrency regression, the structured event log and its JSON-lines sink,
StreamMonitor window attribution, overlap_report steady-state fractions
under injected slow/fast transfers, a traced CPSolver run whose span
tree nests run -> sweep -> mode_update at >= 95% coverage, running the
untraced run's programs with bitwise-identical fits, enabled spans as
jax.profiler host events, and the plan's sort/block/translate spans.
"""
import collections
import dataclasses
import glob
import json
import os
import threading
import time

import numpy as np
import pytest

import repro.api as api
from repro import obs
from repro.api.config import DecomposeConfig, RuntimeConfig
from repro.api.solver import CPSolver
from repro.obs import clock
from repro.obs import trace as obs_trace
from repro.obs.export import (chrome_trace, dump_chrome_trace, span_counts,
                              validate_trace)
from repro.obs.metrics import EventLog, LogHistogram, MetricsRegistry
from repro.obs.profiler import StreamMonitor


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Every test starts from a disabled tracer and a clean global
    registry, and cannot leak an enabled tracer into other test files."""
    obs.reset()
    yield
    obs.reset()


# -- clock -------------------------------------------------------------------

def test_clock_monotonic_and_wall():
    ts = [clock.now() for _ in range(100)]
    assert all(b >= a for a, b in zip(ts, ts[1:]))
    assert abs(clock.walltime() - time.time()) < 5.0


def test_threaded_runtime_modules_share_the_obs_clock():
    """Satellite: sparse/stream, serve/batcher, schedule/rebalance and
    api/checkpoint all time against repro.obs.clock — not their own
    perf_counter bindings."""
    from repro.api import checkpoint
    from repro.schedule import rebalance
    from repro.serve import batcher
    from repro.sparse import stream
    for mod in (stream, batcher, rebalance, checkpoint):
        assert mod.clock is clock, mod.__name__


# -- tracer ------------------------------------------------------------------

def test_disabled_span_is_shared_noop():
    tracer = obs_trace.get_tracer()
    assert not tracer.enabled
    s1 = tracer.span("a", mode=1)
    s2 = tracer.span("b")
    assert s1 is s2  # one shared null object, no per-call allocation
    with s1:
        pass
    assert tracer.records() == []


def _profiler_host_events(log_dir):
    import jax
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    pd = jax.profiler.ProfileData.from_file(files[0])
    return [(ev.name, ev.start_ns, ev.duration_ns)
            for plane in pd.planes if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events]


def test_enabled_span_is_a_profiler_host_event(tmp_path):
    """An enabled span enters a jax.profiler annotation of its name, so a
    profiler trace holds it on the device trace's clock; a disabled span
    is the shared no-op and leaves nothing in the trace."""
    import jax
    import jax.numpy as jnp
    off = obs_trace.span("obs_probe_off")
    assert off is obs_trace.span("obs_probe_other")
    on_dir, off_dir = str(tmp_path / "on"), str(tmp_path / "off")
    with jax.profiler.trace(off_dir):
        with off:
            jax.block_until_ready(jnp.arange(4) + 1)
    obs_trace.enable()
    with jax.profiler.trace(on_dir):
        with obs_trace.span("obs_probe_outer", sweep=1):
            with obs_trace.span("obs_probe_inner", mode=2) as inner:
                assert inner is not off
                jax.block_until_ready(jnp.arange(4) + 1)
    events = {name: (t0, dur) for name, t0, dur in
              _profiler_host_events(on_dir)}
    assert {"obs_probe_outer", "obs_probe_inner"} <= set(events)
    (o0, odur), (i0, idur) = (events["obs_probe_outer"],
                              events["obs_probe_inner"])
    assert o0 <= i0 and i0 + idur <= o0 + odur
    assert not [e for e in _profiler_host_events(off_dir)
                if e[0].startswith("obs_probe")]
    assert [r["name"] for r in obs_trace.get_tracer().records()] == [
        "obs_probe_inner", "obs_probe_outer"]


def test_timed_measures_even_when_disabled():
    with obs_trace.timed("work") as t:
        time.sleep(0.01)
    assert t.duration >= 0.009
    assert obs_trace.get_tracer().records() == []


def test_span_nesting_and_attrs():
    obs_trace.enable()
    with obs_trace.span("outer", sweep=1):
        with obs_trace.span("inner", mode=2):
            pass
        with obs_trace.span("inner", mode=3):
            pass
    recs = {}
    for r in obs_trace.get_tracer().records():
        recs.setdefault(r["name"], []).append(r)
    outer, = recs["outer"]
    assert outer["parent"] is None and outer["attrs"] == {"sweep": 1}
    inner = recs["inner"]
    assert [r["parent"] for r in inner] == [outer["id"], outer["id"]]
    assert [r["attrs"]["mode"] for r in inner] == [2, 3]
    # children recorded before the parent (completion order), inside it
    for r in inner:
        assert outer["t0"] <= r["t0"] <= r["t1"] <= outer["t1"]
    summary = obs_trace.get_tracer().summary()
    assert summary["inner"]["count"] == 2
    assert summary["outer"]["count"] == 1


def test_span_stacks_are_thread_local():
    obs_trace.enable()
    started = threading.Event()
    release = threading.Event()

    def worker():
        with obs_trace.span("worker_root"):
            started.set()
            release.wait(5)

    t = threading.Thread(target=worker, name="obs-worker")
    with obs_trace.span("main_root"):
        t.start()
        started.wait(5)
        release.set()
        t.join()
    recs = {r["name"]: r for r in obs_trace.get_tracer().records()}
    # the worker's span roots its own thread's tree — it must not have
    # nested under the main thread's open span
    assert recs["worker_root"]["parent"] is None
    assert recs["worker_root"]["tid"] != recs["main_root"]["tid"]
    assert recs["worker_root"]["thread"] == "obs-worker"


# -- export + validation -----------------------------------------------------

def _demo_records():
    obs_trace.enable()
    with obs_trace.span("run"):
        for k in range(2):
            with obs_trace.span("sweep", sweep=k):
                with obs_trace.span("ec"):
                    pass
    return obs_trace.get_tracer().records()


def test_chrome_trace_pairs_and_nests():
    records = _demo_records()
    trace = chrome_trace(records, pid=1)
    evs = [e for e in trace["traceEvents"] if e["ph"] in "BE"]
    # DFS order: run.B sweep.B ec.B ec.E sweep.E sweep.B ec.B ec.E sweep.E run.E
    assert [(e["ph"], e["name"]) for e in evs] == [
        ("B", "run"), ("B", "sweep"), ("B", "ec"), ("E", "ec"),
        ("E", "sweep"), ("B", "sweep"), ("B", "ec"), ("E", "ec"),
        ("E", "sweep"), ("E", "run")]
    # B events carry the span attrs; one thread_name metadata event
    assert evs[1]["args"] == {"sweep": 0}
    meta = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert len(meta) == 1 and meta[0]["name"] == "thread_name"
    assert span_counts(records) == {"run": 1, "sweep": 2, "ec": 2}


def test_validate_trace_accepts_good_rejects_broken():
    good = chrome_trace(_demo_records(), pid=1)
    res = validate_trace(good)
    assert res["ok"] and res["coverage"] > 0.99, res

    # unpaired B: drop the final E
    broken = {"traceEvents": good["traceEvents"][:-2]}
    res = validate_trace(broken)
    assert not res["ok"]
    assert any("never closed" in p for p in res["problems"])

    # overlapping siblings
    tids = {"pid": 1, "tid": 7}
    res = validate_trace({"traceEvents": [
        {"name": "p", "ph": "B", "ts": 0.0, **tids},
        {"name": "a", "ph": "B", "ts": 1.0, **tids},
        {"name": "a", "ph": "E", "ts": 50.0, **tids},
        {"name": "b", "ph": "B", "ts": 10.0, **tids},
        {"name": "b", "ph": "E", "ts": 60.0, **tids},
        {"name": "p", "ph": "E", "ts": 100.0, **tids},
    ]})
    assert not res["ok"]
    assert any("overlaps the previous sibling" in p for p in res["problems"])

    # top-level coverage below threshold
    res = validate_trace({"traceEvents": [
        {"name": "a", "ph": "B", "ts": 0.0, **tids},
        {"name": "a", "ph": "E", "ts": 10.0, **tids},
        {"name": "b", "ph": "B", "ts": 90.0, **tids},
        {"name": "b", "ph": "E", "ts": 100.0, **tids},
    ]}, min_coverage=0.95)
    assert not res["ok"] and res["coverage"] < 0.25
    assert any("coverage" in p for p in res["problems"])


def test_validator_cli_expectations(tmp_path):
    from repro.obs.__main__ import main
    path = str(tmp_path / "t.json")
    dump_chrome_trace(path, _demo_records())
    assert main([path, "--expect-span", "sweep=2",
                 "--expect-span", "ec"]) == 0
    assert main([path, "--expect-span", "sweep=3"]) == 1
    assert main([path, "--expect-span", "exchange"]) == 1


# -- metrics registry --------------------------------------------------------

def test_registry_counters_gauges_latency():
    reg = MetricsRegistry()
    reg.inc("q"), reg.inc("q", 4)
    reg.set_gauge("depth", 3)
    reg.observe("op", 0.01)
    with reg.time("op"):
        pass
    assert reg.counter("q") == 5
    assert reg.counter("absent") == 0
    assert reg.gauge("depth") == 3
    lat = reg.latency("op")
    assert lat["count"] == 2 and lat["p50_ms"] is not None
    assert reg.latency("absent") is None
    snap = reg.snapshot()
    assert snap["counters"] == {"q": 5} and snap["gauges"] == {"depth": 3}


def test_registry_providers_and_reentrancy():
    """Providers run OUTSIDE the registry lock: a section builder is free
    to call back into the registry (this deadlocks if report() holds the
    lock across provider calls)."""
    reg = MetricsRegistry()

    def section():
        reg.inc("report_calls")  # reentrant mutation
        return {"ok": True}

    reg.register_provider("demo", section)
    rep = reg.report()
    assert rep["sections"] == {"demo": {"ok": True}}
    assert rep["uptime_s"] >= 0
    assert reg.counter("report_calls") == 1
    reg.unregister_provider("demo")
    assert reg.report()["sections"] == {}
    reg.unregister_provider("demo")  # idempotent


def test_log_histogram_percentile_geometry():
    h = LogHistogram()
    for _ in range(99):
        h.record(1e-3)
    h.record(1.0)
    assert h.count == 100
    # upper bucket edge: conservative, within one bucket (~26%) of truth
    assert 1e-3 <= h.percentile(0.5) <= 1.3e-3
    assert 1.0 <= h.percentile(0.995) <= 1.3
    assert LogHistogram().percentile(0.5) is None
    with pytest.raises(ValueError):
        LogHistogram(lo=1.0, hi=0.1)


def test_log_histogram_snapshot_never_torn():
    """Satellite regression: concurrent record() during snapshot() must
    never yield a torn count/bucket view. Writers record a constant, so
    every internally-consistent snapshot has mean exactly that constant
    and count == the histogram's own cumulative bucket mass."""
    h = LogHistogram()
    stop = threading.Event()
    VALUE = 1e-3

    def hammer():
        while not stop.is_set():
            h.record(VALUE)

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(300):
            s = h.snapshot()
            if s["count"] == 0:
                continue
            # count and total_s taken from ONE locked state: their ratio
            # is exact even while writers race
            assert s["mean_ms"] == pytest.approx(VALUE * 1e3, rel=1e-9), s
            assert s["total_s"] == pytest.approx(s["count"] * VALUE,
                                                 rel=1e-9), s
            assert 1e-3 <= s["p50_ms"] / 1e3 <= 1.3e-3, s
    finally:
        stop.set()
        for t in threads:
            t.join()


# -- event log ---------------------------------------------------------------

def test_event_log_stamps_payloads_and_sink(tmp_path):
    log = EventLog()
    log.emit("sweep", sweep=1)
    log.emit("rebalance", sweep=1, migrations=0)
    log.emit("sweep", sweep=2)
    assert len(log) == 3
    for e in log.events():
        assert e["t"] > 0 and e["wall"] > 0 and "kind" in e
    # payloads == exactly what the emitter passed (stamps stripped)
    assert log.payloads("sweep") == [{"sweep": 1}, {"sweep": 2}]
    assert log.payloads("rebalance") == [{"sweep": 1, "migrations": 0}]
    # a sink attached mid-run replays the buffered events, then mirrors
    path = str(tmp_path / "events.jsonl")
    log.set_sink(path)
    log.emit("sweep", sweep=3)
    log.close_sink()
    lines = [json.loads(x) for x in open(path).read().splitlines()]
    assert [e["kind"] for e in lines] == ["sweep", "rebalance", "sweep",
                                         "sweep"]
    assert lines[-1]["sweep"] == 3
    log.emit("sweep", sweep=4)  # post-close emission: memory only
    assert len(open(path).read().splitlines()) == 4


# -- stream monitor + overlap_report fractions -------------------------------

def test_stream_monitor_window_attribution():
    log = EventLog()
    # window A: 100 ms build, consumer blocked 100 ms (fully exposed)
    log.emit("h2d_build", build_s=0.1, bytes=10, mode=0, shard=0)
    log.emit("h2d_wait", wait_s=0.1, cold=True, mode=0, shard=0)
    # window B: 100 ms build, consumer blocked 1 ms (hidden by compute)
    log.emit("h2d_build", build_s=0.1, bytes=10, mode=0, shard=1)
    log.emit("h2d_wait", wait_s=0.001, cold=False, mode=0, shard=1)
    # wait with no recorded build (sink attached mid-run)
    log.emit("h2d_wait", wait_s=0.005, cold=False, mode=1, shard=0)
    rep = StreamMonitor(log).report()
    assert rep["num_windows"] == 3
    a, b, c = rep["windows"]
    assert a["exposed_s"] == pytest.approx(0.1)
    assert a["hidden_s"] == pytest.approx(0.0)
    assert b["hidden_s"] == pytest.approx(0.099)
    assert c["transfer_s"] == 0.0
    assert rep["stalled_windows"] == 1  # only A crossed the 50% threshold
    assert rep["transfer_s"] == pytest.approx(0.2)
    assert rep["exposed_s"] == pytest.approx(0.101)


def _sleep_streamer(build_s, events=None):
    """Minimal _StreamerBase subclass: every build sleeps a fixed time."""
    from repro.sparse.stream import _StreamerBase

    class _SleepStreamer(_StreamerBase):
        def _build(self, key):
            time.sleep(build_s)
            return np.zeros(1)

        def _key_nbytes(self, key):
            return 8

    return _SleepStreamer(prefetch=2, events=events)


def test_streamer_exposed_vs_hidden_under_slow_and_fast_transfers():
    """Injected transfer speeds drive the exposed/hidden split the
    overlap report is built on: a cold (unprefetched) slow load is fully
    exposed; a prefetched load that finishes behind 'compute' is hidden."""
    log = EventLog()
    slow = _sleep_streamer(0.05, events=log)
    try:
        slow._wait("w0")  # cold: consumer blocks for the whole build
        st = slow.stats_snapshot()
        assert st["cold_builds"] == 1
        assert st["exposed_s"] >= 0.9 * st["transfer_s"] > 0
    finally:
        slow.close()
    kinds = [e["kind"] for e in log.events()]
    assert kinds == ["h2d_build", "h2d_wait"]
    assert log.events("h2d_wait")[0]["cold"] is True

    fast = _sleep_streamer(0.05)
    try:
        fast._dispatch("w0")
        time.sleep(0.25)  # "compute" long enough to hide the transfer
        fast._wait("w0")
        st = fast.stats_snapshot()
        assert st["cold_builds"] == 0
        assert st["transfer_s"] >= 0.05
        assert st["exposed_s"] <= 0.5 * st["transfer_s"]
    finally:
        fast.close()


class _FakeStreamSolver:
    """Just enough of CPSolver for overlap_report: injected aggregate
    stats + per-sweep stream_sweep events."""

    streaming = True
    stream_events = CPSolver.stream_events  # the real stamped-view property

    def __init__(self, sweeps, budget=1 << 20):
        from types import SimpleNamespace
        self.events = EventLog()
        total_t = total_e = 0.0
        for i, (transfer, exposed) in enumerate(sweeps):
            total_t += transfer
            total_e += exposed
            self.events.emit("stream_sweep", sweep=i + 1,
                             transfer_s=transfer, exposed_s=exposed,
                             hidden_s=max(transfer - exposed, 0.0),
                             overlap_fraction=(
                                 (transfer - exposed) / transfer
                                 if transfer > 0 else None),
                             shards_streamed=4)
        snap = {"transfer_s": total_t, "exposed_s": total_e,
                "peak_resident_bytes": budget // 2, "bytes_streamed": 1000,
                "builds": 4 * len(sweeps), "cold_builds": 4,
                "spill_hits": 0, "spill_saves": 0}
        self.streamer = SimpleNamespace(stats_snapshot=lambda: dict(snap))
        self.config = SimpleNamespace(runtime=SimpleNamespace(
            memory_budget=budget, stream_buffers=2))
        self.stream_plans = [SimpleNamespace(num_shards=4, shard_bytes=100)]

    overlap_report = CPSolver.overlap_report


def test_overlap_report_steady_state_fractions():
    """Satellite: steady-state overlap drops the cold first sweep. Fast
    steady sweeps (nothing exposed) -> steady fraction 1.0 even though the
    cold sweep drags the cumulative number down; slow steady sweeps
    (every transfer exposed) -> steady fraction 0.0."""
    fast = _FakeStreamSolver([(1.0, 1.0), (1.0, 0.0), (1.0, 0.0)])
    rep = fast.overlap_report()
    assert rep["enabled"]
    assert rep["overlap_fraction_steady"] == pytest.approx(1.0)
    assert rep["overlap_fraction"] == pytest.approx(2.0 / 3.0)
    assert [e["exposed_s"] for e in rep["per_sweep"]] == [1.0, 0.0, 0.0]

    slow = _FakeStreamSolver([(1.0, 1.0), (1.0, 1.0), (1.0, 1.0)])
    rep = slow.overlap_report()
    assert rep["overlap_fraction_steady"] == pytest.approx(0.0)
    assert rep["overlap_fraction"] == pytest.approx(0.0)

    mixed = _FakeStreamSolver([(2.0, 2.0), (1.0, 0.25), (1.0, 0.25)])
    rep = mixed.overlap_report()
    assert rep["overlap_fraction_steady"] == pytest.approx(0.75)
    # one sweep so far: no steady-state number yet
    first = _FakeStreamSolver([(1.0, 0.5)])
    assert first.overlap_report()["overlap_fraction_steady"] is None


# -- traced solver run -------------------------------------------------------

def _solver_cfg(trace):
    return DecomposeConfig(rank=4, runtime=RuntimeConfig(
        num_devices=1, tol=0.0, seed=0, trace=trace))


def _compiled_programs(fn):
    """Run ``fn``; return the names of the programs XLA compiled meanwhile
    (jax.monitoring's backend-compile events), with their counts."""
    import jax
    names = collections.Counter()

    def listener(event, _secs, **kw):
        if event.endswith("backend_compile_duration"):
            names[kw.get("fun_name")] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        out = fn()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    return out, names


def test_traced_run_nests_and_matches_untraced(small_tensor, tmp_path):
    """Acceptance: a traced run's Chrome trace nests run -> sweep ->
    mode_update at >= 95% top-level coverage; tracing changes nothing that
    runs: the fit trajectory and factors are bitwise identical to the
    untraced run's, and the traced run compiles no program the untraced
    run did not."""
    def solve(trace):
        cfg = _solver_cfg(trace)
        with api.compile(api.plan(small_tensor, cfg), cfg) as s:
            assert obs_trace.get_tracer().enabled == trace
            result = s.run(2)
            if not trace:
                return result, None
            path = str(tmp_path / "trace.json")
            trace_doc = s.dump_trace(path)
            assert json.load(open(path)) == trace_doc
            rep = s.report()
            assert s._obs_name in obs.report()["sections"]
        # close() deregistered the solver's section from the global report
        assert s._obs_name not in obs.report()["sections"]
        # the solver report is the registry view over the existing
        # reporters, value-identical to calling them directly
        # (measure=False: a report snapshot must never force an HLO
        # re-lower)
        assert rep["sections"]["overlap"] == {"enabled": False}
        assert rep["sections"]["exchange"] == s.exchange_report(
            measure=False)
        assert "measured" not in rep["sections"]["exchange"]
        assert rep["sections"]["imbalance"] == s.imbalance_report()
        return result, trace_doc

    (r_plain, _), plain_progs = _compiled_programs(lambda: solve(False))
    (r_traced, trace), traced_progs = _compiled_programs(lambda: solve(True))

    assert r_traced.fits == r_plain.fits
    for a, b in zip(r_plain.factors, r_traced.factors):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert plain_progs["jit(update)"] == len(small_tensor.shape)
    assert not traced_progs - plain_progs, (traced_progs, plain_progs)

    res = validate_trace(trace, min_coverage=0.95)
    assert res["ok"], res["problems"]
    assert res["coverage"] >= 0.95
    nmodes = len(small_tensor.shape)
    assert res["span_counts"]["run"] == 1
    assert res["span_counts"]["sweep"] == 2
    assert res["span_counts"]["mode_update"] == 2 * nmodes

    by_id = {r["id"]: r for r in obs_trace.get_tracer().records()}
    parent_names = {"mode_update": "sweep", "sweep": "run"}
    for r in by_id.values():
        want = parent_names.get(r["name"])
        if want is not None:
            assert by_id[r["parent"]]["name"] == want, r
    modes = [r["attrs"]["mode"] for r in by_id.values()
             if r["name"] == "mode_update"]
    assert sorted(modes) == sorted(list(range(nmodes)) * 2)


def test_plan_spans_per_mode_and_identical_plan(small_tensor):
    """The plan's host phases are spans (plan.sort, plan.block,
    plan.translate) once per mode under ``plan``, with the mode and the
    nonzero count; the plan built with the tracer on is byte-identical to
    the one built with it off."""
    cfg = _solver_cfg(False)
    plain = api.plan(small_tensor, cfg)
    obs_trace.enable()
    traced = api.plan(small_tensor, cfg)
    recs = obs_trace.get_tracer().records()
    by_id = {r["id"]: r for r in recs}
    nmodes = len(small_tensor.shape)
    for phase in ("plan.sort", "plan.block", "plan.translate"):
        spans = [r for r in recs if r["name"] == phase]
        assert sorted(r["attrs"]["mode"] for r in spans) == \
            list(range(nmodes))
        assert {r["attrs"]["nnz"] for r in spans} == {small_tensor.nnz}
        assert {by_id[r["parent"]]["name"] for r in spans} == {"plan"}
    assert obs_trace.get_tracer().summary()["plan"]["count"] == 1
    for a, b in zip(plain.modes, traced.modes):
        for f in dataclasses.fields(a):
            x, y = getattr(a, f.name), getattr(b, f.name)
            if isinstance(x, np.ndarray):
                assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), \
                    f.name
            else:
                assert x == y, f.name
    for a, b in zip(plain.global_to_padded + plain.padded_to_global,
                    traced.global_to_padded + traced.padded_to_global):
        assert a.tobytes() == b.tobytes()


def test_solver_events_and_dumps(small_tensor, tmp_path):
    cfg = _solver_cfg(False)
    with api.compile(api.plan(small_tensor, cfg), cfg) as s:
        s.run(2)
        assert [e["sweep"] for e in s.events.payloads("sweep")] == [1, 2]
        assert s.stream_events == []  # resident run: no stream_sweep events
        path = str(tmp_path / "events.jsonl")
        s.dump_events(path)
    lines = [json.loads(x) for x in open(path).read().splitlines()]
    assert [e["kind"] for e in lines].count("sweep") == 2
    # tracer stayed disabled: no spans recorded, hot path untouched
    assert obs_trace.get_tracer().records() == []
