"""Execute layer: ``compile(plan, config) -> CPSolver``.

A :class:`CPSolver` is the session object that owns everything expensive:
the device mesh, the sharded per-mode tensor copies (held through a
:class:`~repro.sparse.stream.ShardStreamer`, which also absorbs rebalanced
shards asynchronously), and the jitted per-mode ALS updates (with donated
factor buffers). Building one pays the device placement and trace/compile
cost once; after that, sweeps are pure enqueued device work:

    solver = api.compile(plan, cfg)
    solver.restore()            # optional: elastic resume from checkpoints
    result = solver.run(iters)  # CPResult — or step with solver.sweep()

When ``config.schedule.rebalance`` is ``"measure"`` or ``"on"`` the solver
also owns a :class:`~repro.schedule.rebalance.Rebalancer`: every
``schedule.cadence`` sweeps it synchronizes, probes per-mode per-device EC
wall time, recalibrates the cost model, and — in ``"on"`` mode — applies
block-granular nnz migrations between replication-group members as an
*incremental* plan update (array shapes are preserved, so the jitted
updates are reused without recompiling; only migrated modes' shards are
re-placed, prefetched in the background by the streamer). Sweeps between
rebalance points remain fully asynchronous.

The solver is deliberately *not* serializable — that's the plan's job
(:mod:`repro.api.planning`) plus the checkpoint manager's
(:mod:`repro.training.checkpoint`). ``checkpoint()``/``restore()`` store
GLOBAL-layout factors, so a checkpoint taken by a solver compiled for m
devices restores into one compiled for m' devices (elastic re-pad into the
new plan's packed ownership layout).
"""
from __future__ import annotations

import itertools
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import comm, obs
from repro.api.config import DecomposeConfig
from repro.core import als as als_mod
from repro.core import mttkrp as dmttkrp
from repro.core.decompose import CPResult
from repro.core.partition import CPPlan
from repro.kernels import ops as kops
from repro.obs import export as obs_export
from repro.obs import trace as obs_trace
from repro.obs.metrics import EventLog, MetricsRegistry
from repro.obs.profiler import StreamMonitor
from repro.sparse.stream import ShardStreamer, SuperShardStreamer

# distinguishes concurrent solvers' sections in the process-wide
# obs.report() — names are never reused within a process
_SOLVER_IDS = itertools.count(1)

__all__ = ["CPSolver", "compile", "partition_report",
           "validate_factor_payload"]


def validate_factor_payload(factors, lam, *, shape, rank,
                            source: str) -> None:
    """Validate GLOBAL-layout factors + lam against an expected geometry.

    Shared by :meth:`CPSolver.restore`/:meth:`CPSolver.load_state` and the
    serving boot path — without it a rank-mismatched checkpoint dies in a
    cryptic broadcast error deep inside the ownership re-pad. Raises
    ``ValueError`` naming the offending mode and BOTH ranks/sizes."""
    nmodes = len(shape)
    if len(factors) != nmodes:
        raise ValueError(
            f"{source} has {len(factors)} factor matrices, but the target "
            f"tensor has {nmodes} modes (shape {tuple(shape)})")
    for w, fg in enumerate(factors):
        fs = tuple(int(s) for s in np.shape(fg))
        if len(fs) != 2:
            raise ValueError(f"{source} factor for mode {w} is not a "
                             f"matrix (shape {fs})")
        if fs[1] != rank:
            raise ValueError(
                f"{source} was written at rank {fs[1]}, but this "
                f"solver/plan is compiled for rank {rank} (mode {w} "
                f"factor is {fs}); re-fit or re-compile at a matching rank")
        if fs[0] != shape[w]:
            raise ValueError(
                f"{source} factor for mode {w} has {fs[0]} rows, but the "
                f"target tensor's mode {w} has {shape[w]} — the "
                f"checkpoint belongs to a different tensor")
    ls = tuple(int(s) for s in np.shape(lam))
    if ls != (rank,):
        raise ValueError(f"{source} lambda has shape {ls}, expected "
                         f"({rank},)")


def partition_report(plan: CPPlan, rank: int) -> dict:
    """What a plan's static partition costs across devices, per mode, from
    the plan alone: true nonzeros and used kernel slots (``blocks_true ·
    block_p``) per device, max over mean; the rows the exchange ships
    (``n_groups · rows_max`` slabs) over the mode's true rows
    (``padded_rows_over_rows``); the packed factor's rows over them
    (``factor_rows_over_rows``); and, for the ``ref`` EC of the mode's
    update at ``rank``, its input factors' lane-padded bytes
    (``ec_input_bytes``) and whether it runs its chunks in the VMEM loop
    (``ec_loop``, :func:`repro.kernels.ops.ref_takes_loop`). One device
    reads 1 and 1, and the layout's tile padding in both row ratios. The
    exchange bytes this layout implies are the ``exchange`` section's
    ``modelled`` entry of the same report."""
    per_mode = {}
    for d, part in enumerate(plan.modes):
        nnz = np.asarray(part.nnz_true, np.float64)
        slots = np.asarray(part.blocks_true, np.float64) * part.block_p
        rows = max(int(plan.shape[d]), 1)
        input_rows = [p.padded_rows for w, p in enumerate(plan.modes)
                      if w != d]
        per_mode[d] = {
            "nnz_max_over_mean": float(nnz.max() / max(nnz.mean(), 1.0)),
            "slots_max_over_mean": float(slots.max() / max(slots.mean(), 1.0)),
            "padded_rows_over_rows": part.n_groups * part.rows_max / rows,
            "factor_rows_over_rows": part.padded_rows / rows,
            "ec_input_bytes": kops.ref_input_bytes(input_rows, rank),
            "ec_loop": kops.ref_takes_loop(input_rows, rank),
        }
    return {"num_devices": int(plan.num_devices), "per_mode": per_mode}


class CPSolver:
    """A compiled CP-ALS session: mesh + sharded tensor copies + jitted
    updates + current :class:`~repro.core.als.ALSState` (+ optional
    :class:`~repro.schedule.rebalance.Rebalancer`)."""

    def __init__(self, plan: CPPlan, config: DecomposeConfig, mesh: Mesh):
        if config.schedule.telemetry_enabled and \
                any(getattr(p, "lazy", False) for p in plan.modes):
            raise ValueError(
                "schedule.rebalance='measure'/'on' needs an in-memory plan: "
                "the rebalancer's probes and migrations address whole-mode "
                "shard arrays, which an out-of-core TensorStore plan "
                "deliberately never materializes. Plan from the in-memory "
                "tensor (store.to_coo()) to use the dynamic scheduler, or "
                "run with schedule.rebalance='off'.")
        self.plan = plan
        self.config = config
        self.mesh = mesh
        self.streaming = config.runtime.streaming
        # unified observability: every report this solver serves is a view
        # over this registry/event log (see repro.obs)
        self.metrics = MetricsRegistry()
        self.events = EventLog()
        if config.runtime.trace:
            obs_trace.enable()
        kernel_kw = config.kernel.mttkrp_kwargs(nmodes=plan.nmodes,
                                                rank=config.rank)
        self.exchange_spec = comm.resolve_exchange_spec(
            config.exchange, plan=plan, rank=config.rank, mesh=mesh)
        if self.streaming:
            if not all(getattr(p, "lazy", False) for p in plan.modes):
                raise ValueError(
                    "runtime.streaming=True needs an out-of-core plan "
                    "(every mode a TensorStore-backed StoreModePartition): "
                    "super-shards are materialized per tile window from "
                    "store chunks. Plan from a TensorStore "
                    "(api.plan(TensorStore(...), cfg)), or turn streaming "
                    "off — an in-memory plan is already fully resident.")
            from repro.store.plan import split_mode_super_shards
            budget = config.runtime.memory_budget
            if budget is None:
                raise ValueError(
                    "runtime.streaming needs runtime.memory_budget "
                    "(per-device bytes for streamed shard arrays); the "
                    "super-shard split is defined by this budget")
            buffers = config.runtime.stream_buffers
            self.stream_plans = [
                split_mode_super_shards(p, budget, buffers=buffers)
                for p in plan.modes]
            spill = None
            if config.runtime.stream_spill:
                from repro.sparse.stream import WindowSpill
                spill = WindowSpill(config.runtime.stream_spill_dir)
            self.streamer = SuperShardStreamer(
                plan, mesh, self.stream_plans, buffers=buffers, spill=spill,
                events=self.events)
            self.updates = als_mod.make_streaming_sweep_updates(
                plan, mesh, rank=config.rank,
                exchange_spec=self.exchange_spec, **kernel_kw)
        else:
            self.stream_plans = None
            # All modes stay resident (prefetch=nmodes): the streamer is
            # here for its async (re)placement, not capacity eviction —
            # out-of-HBM epoch streaming is the runtime.streaming path.
            self.streamer = ShardStreamer(plan, mesh, prefetch=plan.nmodes,
                                          events=self.events)
            self.updates = als_mod.make_sweep_updates(
                plan, mesh, exchange_spec=self.exchange_spec, **kernel_kw)
        self.rebalancer = None
        if config.schedule.telemetry_enabled:
            from repro.schedule.rebalance import Rebalancer
            member_caps = None
            if config.runtime.memory_budget is not None:
                # budget set on a resident plan: keep migrations inside the
                # streamed-slot budget so a later streaming run of the same
                # (rebalanced) layout still fits its super-shard windows
                from repro.store.plan import budget_slot_cap
                member_caps = {
                    d: budget_slot_cap(
                        config.runtime.memory_budget, nmodes=plan.nmodes,
                        n_tiles=p.rows_max // p.tile, block_p=p.block_p,
                        buffers=config.runtime.stream_buffers)
                    for d, p in enumerate(plan.modes)}
            self.rebalancer = Rebalancer(
                imbalance_threshold=config.schedule.imbalance_threshold,
                migration_budget=config.schedule.migration_budget,
                ewma_alpha=config.schedule.ewma_alpha,
                probe_repeats=config.schedule.probe_repeats,
                kernel_kw=kernel_kw,
                migrate=config.schedule.migrations_enabled,
                member_nnz_caps=member_caps)
        self._ckpt_mgr = None
        if config.runtime.checkpoint_dir is not None:
            from repro.training.checkpoint import CheckpointManager
            self._ckpt_mgr = CheckpointManager(config.runtime.checkpoint_dir)
        self.metrics.register_provider("overlap", self.overlap_report)
        self.metrics.register_provider("imbalance", self.imbalance_report)
        self.metrics.register_provider("partition", self.partition_report)
        self.metrics.register_provider(
            "exchange", lambda: self.exchange_report(measure=False))
        self.metrics.register_provider("stream",
                                       self.streamer.stats_snapshot)
        self._obs_name = f"solver.{next(_SOLVER_IDS)}"
        obs.get_registry().register_provider(self._obs_name,
                                             self.metrics.report)
        self.reset()

    @property
    def stream_events(self) -> list[dict]:
        """Per-sweep streaming overlap records (what
        :meth:`overlap_report` aggregates) — a stamp-stripped view over the
        event log's ``stream_sweep`` events, value-identical to the plain
        list this attribute used to be."""
        return self.events.payloads("stream_sweep")

    @property
    def schedule_events(self) -> list[dict]:
        """Rebalance-point event log — a stamp-stripped view over the
        event log's ``rebalance`` events."""
        return self.events.payloads("rebalance")

    @property
    def dev_arrays(self) -> list:
        """Per-mode device shards (kept resident by the streamer)."""
        if self.streaming:
            raise RuntimeError(
                "no whole-mode resident shards in streaming mode: tensor "
                "data cycles through super-shards under the memory budget; "
                "see overlap_report() for what is resident")
        return [self.streamer.get(d) for d in range(self.plan.nmodes)]

    # -- teardown ----------------------------------------------------------
    def close(self) -> None:
        """Release the session's background resources: cancels the
        streamer's pending prefetches and joins its executor so no
        in-flight ``device_put`` outlives the solver (and can touch a freed
        plan). Also deregisters the solver's section from the process-wide
        ``obs.report()`` and closes any event-log sink. Idempotent; the
        solver is unusable afterwards."""
        self.streamer.close()
        obs.get_registry().unregister_provider(self._obs_name)
        self.events.close_sink()

    def __enter__(self) -> "CPSolver":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- state lifecycle ---------------------------------------------------
    def _replicate(self, xs: list) -> list:
        """Place state arrays replicated on the mesh — the sharding the
        jitted updates return — so the first sweep calls the same compiled
        updates as every later one."""
        return jax.device_put(xs, NamedSharding(self.mesh, P()))

    def reset(self) -> None:
        """(Re)initialize factors from the config seed; sweep counter to 0."""
        rank = self.config.rank
        factors = self._replicate(als_mod.init_factors(
            self.plan, rank, seed=self.config.runtime.seed))
        grams = [als_mod.gram(f) for f in factors]
        self.state = als_mod.ALSState(
            factors=factors, lam=self._replicate([jnp.ones(rank)])[0],
            grams=grams)

    def restore(self, step: int | None = None) -> bool:
        """Elastic resume: load the latest (or given) verified checkpoint and
        re-pad its GLOBAL-layout factors into THIS plan's ownership layout —
        the checkpoint may have been written under any device count. Returns
        True iff a checkpoint was restored."""
        if self._ckpt_mgr is None:
            raise ValueError("no checkpoint_dir configured in "
                             "config.runtime; nothing to restore from")
        if step is None:
            restored = self._ckpt_mgr.restore_latest()
        else:
            payload = self._ckpt_mgr.restore(step)
            restored = None if payload is None else (payload, step)
        if restored is None:
            return False
        payload, step = restored
        self.load_state(payload["factors"], payload["lam"],
                        fits=list(payload.get("fits", [])), sweep=step,
                        source=f"checkpoint step {step} in "
                               f"{self._ckpt_mgr.dir!r}")
        return True

    def load_state(self, factors, lam, *, fits=(), sweep: int = 0,
                   source: str = "warm-start state") -> None:
        """Install GLOBAL-layout ``(I_w, rank)`` factors as the solver's
        current state (the warm-start entry: checkpoint restore, serving
        refresh, transfer from another solver). Validates geometry first —
        a mismatched rank or mode size raises a ``ValueError`` naming both
        sides instead of a broadcast error inside the ownership re-pad."""
        rank = self.config.rank
        validate_factor_payload(factors, lam, shape=self.plan.shape,
                                rank=rank, source=source)
        padded = []
        for w, fg in enumerate(factors):
            fp = np.zeros((self.plan.modes[w].padded_rows, rank), np.float32)
            fp[self.plan.global_to_padded[w]] = fg
            padded.append(fp)
        padded = self._replicate(padded)
        grams = [als_mod.gram(f) for f in padded]
        self.state = als_mod.ALSState(
            factors=padded,
            lam=self._replicate([np.asarray(lam, np.float32)])[0],
            grams=grams, sweep=sweep, fits=list(fits))

    def checkpoint(self) -> None:
        """Write the current state (GLOBAL-layout factors) at its sweep."""
        if self._ckpt_mgr is None:
            raise ValueError("no checkpoint_dir configured in config.runtime")
        s = self.state
        self._ckpt_mgr.save(s.sweep, {
            "factors": als_mod.unpad_factors(self.plan, s.factors),
            "lam": np.asarray(s.lam),
            "fits": np.asarray([float(f) for f in s.fits], np.float64),
        })

    # -- execution ---------------------------------------------------------
    def sweep(self) -> als_mod.ALSState:
        """One full ALS sweep (all modes). Enqueues device work only; the
        appended fit is a device scalar (reading it blocks the host).

        In streaming mode each mode iterates its super-shards through the
        double-buffered streamer instead (fits bitwise identical), and the
        sweep's transfer/exposed timings are emitted as ``stream_sweep``
        events (see :attr:`stream_events` / :meth:`overlap_report`).

        The span tracer (``runtime.trace=True`` or ``obs.trace.enable()``)
        only records: a traced sweep runs the same compiled programs, with
        no added sync, and gives bitwise-identical fits. Its ``sweep`` span
        holds one ``mode_update`` span per mode."""
        with obs_trace.span("sweep", sweep=self.state.sweep + 1):
            if self.streaming:
                before = self.streamer.stats_snapshot()
                self.state = als_mod.als_streaming_sweep(
                    self.plan, self.mesh, self.streamer, self.stream_plans,
                    self.state, self.updates)
                after = self.streamer.stats_snapshot()
                transfer = after["transfer_s"] - before["transfer_s"]
                exposed = after["exposed_s"] - before["exposed_s"]
                hidden = max(transfer - exposed, 0.0)
                self.events.emit(
                    "stream_sweep",
                    sweep=self.state.sweep,
                    transfer_s=transfer,
                    exposed_s=exposed,
                    hidden_s=hidden,
                    overlap_fraction=(
                        hidden / transfer if transfer > 0 else None),
                    shards_streamed=after["builds"] - before["builds"],
                )
            else:
                self.state = als_mod.als_sweep(self.plan, self.mesh,
                                               self.dev_arrays, self.state,
                                               self.updates)
        self.events.emit("sweep", sweep=self.state.sweep)
        return self.state

    def rebalance_step(self):
        """One rebalance point: sync, probe per-mode per-device EC times,
        recalibrate the cost model, and (in ``rebalance="on"``) apply any
        triggered migrations incrementally. Returns the
        :class:`~repro.schedule.rebalance.ReplanDecision`, or None when the
        scheduler is off."""
        if self.rebalancer is None:
            return None
        from repro.schedule.rebalance import apply_rebalance
        # Host copies decouple the probes from the solver's committed mesh
        # sharding — this is the one deliberate sync point.
        factors = [jnp.asarray(np.asarray(f)) for f in self.state.factors]
        decision = self.rebalancer.observe(self.plan, factors,
                                           sweep=self.state.sweep)
        event = dict(self.rebalancer.events[-1])
        if decision.triggered:
            self.plan, applied = apply_rebalance(self.plan, decision)
            # Re-place only modes where something actually moved — a
            # skipped migration (no headroom) leaves bit-identical arrays,
            # and re-uploading them every rebalance point would be pure
            # H2D waste.
            moved_modes = sorted({a["mode"] for a in applied
                                  if a.get("moved_nnz", 0) > 0})
            if moved_modes:
                self.streamer.update_plan(self.plan, moved_modes)
            else:
                self.streamer.plan = self.plan  # epoch bump only
            event["applied"] = applied
            event["epoch_after"] = self.plan.rebalance_epoch
        self.events.emit("rebalance", **event)
        return decision

    def run(self, iters: int, *, tol: float | None = None,
            verbose: bool = False) -> CPResult:
        """Sweep until ``iters`` total sweeps or the fit plateaus below
        ``tol`` (default: config.runtime.tol). Checkpoints every sweep when a
        checkpoint_dir is configured; hits a rebalance point every
        ``config.schedule.cadence`` sweeps when the scheduler is enabled.
        Resumes from the current state's sweep counter, so
        ``restore(); run(iters)`` continues where the checkpoint left off."""
        if tol is None:
            tol = self.config.runtime.tol
        cadence = self.config.schedule.cadence
        with obs_trace.span("run", iters=iters):
            for _ in range(self.state.sweep, iters):
                state = self.sweep()
                if verbose:
                    print(f"sweep {state.sweep}: "
                          f"fit={float(state.fits[-1]):.6f}")
                if self._ckpt_mgr is not None:
                    with obs_trace.span("checkpoint", sweep=state.sweep):
                        self.checkpoint()
                if self.rebalancer is not None \
                        and state.sweep % cadence == 0 \
                        and state.sweep < iters:
                    with obs_trace.span("rebalance", sweep=state.sweep):
                        self.rebalance_step()
                if tol > 0 and len(state.fits) >= 2 and \
                        abs(float(state.fits[-1])
                            - float(state.fits[-2])) < tol:
                    break
        return self.result()

    def imbalance_report(self) -> dict:
        """Measured-vs-modelled imbalance per mode plus the rebalance event
        log — what ``launch.decompose`` prints. Empty when the scheduler
        never ran."""
        if self.rebalancer is None or not self.rebalancer.ewma_times:
            return {"enabled": False, "events": []}
        from repro.schedule.rebalance import imbalance_ratio
        per_mode = {}
        for mode, part in enumerate(self.plan.modes):
            measured = self.rebalancer.ewma_times.get(mode)
            per_mode[mode] = {
                "measured_imbalance":
                    imbalance_ratio(measured) if measured is not None else None,
                "modelled_imbalance":
                    imbalance_ratio(self.rebalancer.cost_model.predict(part)),
                "r": int(part.r),
            }
        c = self.rebalancer.cost_model.coeffs
        return {
            "enabled": True,
            "rebalance_epoch": int(self.plan.rebalance_epoch),
            "coefficients": {"sec_per_nnz": c.sec_per_nnz,
                             "sec_per_slot": c.sec_per_slot,
                             "sec_fixed": c.sec_fixed},
            "per_mode": per_mode,
            "events": self.schedule_events,
        }

    def partition_report(self) -> dict:
        """:func:`partition_report` of the live (possibly rebalanced) plan;
        it needs no rebalancer."""
        return partition_report(self.plan, self.config.rank)

    def exchange_report(self, *, measure: bool = True) -> dict:
        """Modelled — and, with ``measure``, HLO-measured — per-device
        exchange bytes for one ALS sweep under the resolved
        :class:`~repro.comm.ExchangeSpec`. Measurement lowers+compiles each
        mode's update once more against the live arrays and parses the
        optimized HLO's collectives (loop-weighted), so it is a deliberate
        sync point — what ``launch.decompose --exchange-report`` prints."""
        spec = self.exchange_spec
        report = {
            "spec": {"variant": spec.variant, "merge": spec.merge,
                     "chunk_rows": spec.chunk_rows,
                     "wire_dtype": spec.wire_dtype},
            "modelled": comm.modelled_exchange_bytes(
                self.plan, self.config.rank, wire_dtype=spec.wire_dtype),
        }
        if measure and self.streaming:
            # the streaming updates split MTTKRP across super-shards; there
            # is no single per-mode HLO whose collectives describe a sweep
            report["measured_skipped"] = (
                "streaming mode: per-mode HLO measurement addresses the "
                "resident single-shard update; modelled bytes above apply "
                "unchanged (the exchange runs once per mode on the "
                "accumulated partials, identical collectives)")
            measure = False
        if measure:
            measured, total = [], 0.0
            s = self.state
            for d in range(self.plan.nmodes):
                others = [s.factors[w] for w in range(self.plan.nmodes)
                          if w != d]
                hlo = self.updates[d].lower(
                    s.factors[d], self.streamer.get(d), others,
                    s.grams).compile().as_text()
                m = comm.measured_exchange_bytes(hlo)
                measured.append(m)
                total += m["total_bytes"]
            report["measured"] = {"per_mode": measured,
                                  "sweep_total_bytes": total}
        return report

    def overlap_report(self) -> dict:
        """Streaming budget accounting + per-sweep transfer overlap — what
        ``launch.decompose --stream`` prints.

        ``transfer_s`` is total host→device build time (chunk reads,
        scatter, ``device_put``); ``exposed_s`` the part the sweep actually
        blocked on (measured at ``get``, i.e. dispatch→ready timestamps);
        their difference is the time double buffering hid behind compute.
        ``peak_resident_bytes`` counts in-flight prefetches and is the
        quantity bounded by ``runtime.memory_budget``.

        ``overlap_fraction`` is cumulative over the whole run, INCLUDING
        the first streamed sweep — whose builds scan and rank store chunks
        for the first time (the one-time preprocessing the window spill
        then caches). ``overlap_fraction_steady`` drops that sweep and is
        the per-iteration number comparable to the paper's timings; None
        until a second streamed sweep exists."""
        if not self.streaming:
            return {"enabled": False}
        snap = self.streamer.stats_snapshot()
        rt = self.config.runtime
        transfer, exposed = snap["transfer_s"], snap["exposed_s"]
        hidden = max(transfer - exposed, 0.0)
        steady = self.stream_events[1:]
        s_transfer = sum(e["transfer_s"] for e in steady)
        s_exposed = sum(e["exposed_s"] for e in steady)
        return {
            "enabled": True,
            "budget_bytes": int(rt.memory_budget),
            "buffers": int(rt.stream_buffers),
            "shards_per_mode": [sp.num_shards for sp in self.stream_plans],
            "shard_bytes_per_mode": [sp.shard_bytes
                                     for sp in self.stream_plans],
            "peak_resident_bytes": int(snap["peak_resident_bytes"]),
            "bytes_streamed": int(snap["bytes_streamed"]),
            "builds": int(snap["builds"]),
            "cold_builds": int(snap["cold_builds"]),
            "transfer_s": transfer,
            "exposed_s": exposed,
            "hidden_s": hidden,
            "overlap_fraction": hidden / transfer if transfer > 0 else None,
            "overlap_fraction_steady":
                (max(s_transfer - s_exposed, 0.0) / s_transfer
                 if s_transfer > 0 else None),
            "spill_hits": int(snap.get("spill_hits", 0)),
            "spill_saves": int(snap.get("spill_saves", 0)),
            "per_sweep": list(self.stream_events),
        }

    def report(self) -> dict:
        """This solver's unified metrics report: counters/gauges/latency
        histograms plus the ``overlap``/``imbalance``/``partition``/
        ``exchange``/``stream`` sections — each a registered provider over
        its report method, value-identical to calling it directly.
        (``exchange`` uses ``measure=False``: a report snapshot must not
        force an HLO re-lower.)"""
        return self.metrics.report()

    def stream_monitor(self) -> StreamMonitor:
        """Per-window exposed-vs-hidden transfer attribution built from
        the streamer's ``h2d_build``/``h2d_wait`` events."""
        return StreamMonitor(self.events)

    def dump_trace(self, path: str) -> dict:
        """Export every span the process tracer recorded as Chrome-trace
        JSON (load in ``chrome://tracing`` or https://ui.perfetto.dev);
        returns the trace dict. Spans nest run → sweep → mode_update
        (streamed sweeps add {h2d_window, ec, exchange} under each
        mode_update), beside plan → {plan.sort, plan.block,
        plan.translate}, compile, checkpoint and rebalance. A traced run
        runs the same programs as an untraced one; each span is also a
        ``jax.profiler`` annotation of its name."""
        return obs_export.dump_chrome_trace(
            path, obs_trace.get_tracer().records())

    def dump_events(self, path: str) -> None:
        """One-shot dump of the solver's structured event log as JSON
        lines (the streaming twin is ``events.set_sink`` — attach early to
        mirror events live)."""
        with open(path, "w") as f:
            for e in self.events.events():
                f.write(json.dumps(e, default=str) + "\n")

    def audit(self, *, modes=None) -> list:
        """Run the :mod:`repro.analysis` passes against THIS compiled
        session: the plan rules over the live (possibly rebalanced) plan
        and the HLO audit over the jitted updates' lowered/compiled text
        (gather-free EC, no host transfers, collective-permute when
        overlapped, donation aliasing, bf16 wire). Lowering each mode's
        update again is a deliberate sync point, like
        :meth:`exchange_report`. Returns the findings (empty == clean)."""
        from repro.analysis import check_plan, hlo_audit
        findings = check_plan(self.plan, self.config)
        findings += hlo_audit.audit_solver(self, modes=modes)
        return findings

    def result(self) -> CPResult:
        """Snapshot the current state as a host-side :class:`CPResult`
        (forces a sync: factors unpadded to global layout, fits to floats)."""
        s = self.state
        return CPResult(
            factors=als_mod.unpad_factors(self.plan, s.factors),
            lam=np.asarray(s.lam),
            fits=[float(f) for f in s.fits],
            plan=self.plan,
            sweeps=s.sweep,
        )

    def export_snapshot(self, *, version: int = 1, source: str = "solver"):
        """Export the current state as an immutable serving
        :class:`~repro.serve.engine.FactorSnapshot` — the hand-off from a
        training/refit session to a :class:`~repro.serve.ServingEngine`
        (forces a sync like :meth:`result`)."""
        from repro.serve.engine import FactorSnapshot
        return FactorSnapshot.from_result(self.result(), version=version,
                                          source=source)


def compile(plan: CPPlan, config: DecomposeConfig, *,
            mesh: Mesh | None = None) -> CPSolver:
    """Build a :class:`CPSolver` for ``plan`` under ``config``: construct the
    (group, sub) mesh (unless one is passed), place every mode's shards, and
    build the jitted per-mode updates. Device-touching but tensor-data-free —
    cheap relative to ``plan()`` at scale."""
    if config.runtime.trace:
        obs_trace.enable()  # before the span below so it is recorded
    with obs_trace.span("compile"):
        from repro.core.partition import validate_plan
        validate_plan(plan)  # fail loudly before any device placement
        if mesh is None:
            mesh = dmttkrp.cp_mesh(plan.num_devices, plan.modes[0].r)
        return CPSolver(plan, config, mesh)
