"""CP decomposition launcher (the paper's workload driver).

    PYTHONPATH=src python -m repro.launch.decompose --preset paper \
        --profile amazon --scale 2e-4            # paper-faithful (§5.1)
    PYTHONPATH=src python -m repro.launch.decompose --preset optimized \
        --profile twitch --scale 2e-4            # auto-r + blocked kernel
    PYTHONPATH=src python -m repro.launch.decompose --preset fused \
        --set kernel.num_buffers=3 --set runtime.tol=0   # dotted overrides
    PYTHONPATH=src python -m repro.launch.decompose --preset paper \
        --set partition.strategy=equal_nnz --rebalance   # dynamic scheduler
    PYTHONPATH=src python -m repro.launch.decompose --preset paper \
        --store tensor.store --plan-cache plans/   # out-of-core ingest path
    PYTHONPATH=src python -m repro.launch.decompose --preset paper \
        --store tensor.store --stream --memory-budget-mb 64   # epoch streaming
    PYTHONPATH=src python -m repro.launch.decompose --preset paper \
        --trace-out trace.json --events-out events.jsonl   # observability

Runs the staged repro.api pipeline and reports preprocessing (plan) time
separately from execution time, the way the paper does — pass --plan-cache
to pay preprocessing once across invocations. With --rebalance (or
--measure-balance) it also prints the scheduler's imbalance report:
per-mode measured vs cost-model-predicted max/mean EC-time ratios, the
calibrated coefficients, and every rebalance event (sweep, migrations,
nonzeros moved). With --exchange-report it prints the exchange subsystem's
volume accounting: per-sweep modelled exchange bytes (ring formulas, §4.9)
against bytes measured from the compiled HLO's collectives, e.g.::

    PYTHONPATH=src python -m repro.launch.decompose --preset paper \
        --set exchange.variant=overlap --set exchange.wire_dtype=bfloat16 \
        --exchange-report

--trace-out enables the repro.obs span tracer for the whole invocation
(plan and its sort/block/translate phases → compile → execute, nested
down to per-mode updates, and per-window EC/exchange/H2D spans when
streamed) and writes a Chrome-trace JSON loadable in chrome://tracing or
Perfetto; a traced run runs the same programs as an untraced one;
--events-out mirrors every structured event (sweeps, rebalance points,
per-window transfer timings) as greppable JSON lines, live.
"""
from __future__ import annotations

import argparse


def main():
    from repro.api.config import PRESETS

    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="paper",
                    choices=sorted(PRESETS),
                    help="named repro.api configuration preset")
    ap.add_argument("--set", dest="set_args", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="dotted config override, e.g. kernel.variant=fused "
                         "or runtime.tol=0 (repeatable)")
    src = ap.add_mutually_exclusive_group()
    src.add_argument("--profile", default="amazon",
                     help="synthetic paper-dataset profile (default)")
    src.add_argument("--tns", default=None, metavar="PATH",
                     help="read an in-memory tensor from a .tns/.tns.gz "
                          "file instead of a synthetic profile")
    src.add_argument("--store", default=None, metavar="DIR",
                     help="run out-of-core from a tensor store directory "
                          "(repro.store.convert); planning reads manifest "
                          "stats only and shards stream per device")
    ap.add_argument("--scale", type=float, default=2e-4)
    ap.add_argument("--rank", type=int, default=32)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--plan-cache", default=None,
                    help="plan cache directory (reuse preprocessing across "
                         "runs with a matching content signature)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--no-resume", action="store_true",
                    help="with --ckpt: start fresh instead of resuming")
    ap.add_argument("--rebalance", action="store_true",
                    help="enable the dynamic load balancer "
                         "(schedule.rebalance=on; tune via --set "
                         "schedule.cadence=... etc.)")
    ap.add_argument("--measure-balance", action="store_true",
                    help="collect per-device EC-time telemetry and report "
                         "imbalance without migrating "
                         "(schedule.rebalance=measure)")
    ap.add_argument("--exchange-report", action="store_true",
                    help="print per-sweep modelled vs HLO-measured exchange "
                         "volume for the resolved exchange spec")
    ap.add_argument("--stream", action="store_true",
                    help="epoch-streaming execution: each mode's sweep "
                         "iterates over budget-sized super-shards with "
                         "double-buffered host-to-device transfer "
                         "(requires --store and --memory-budget-mb)")
    ap.add_argument("--memory-budget-mb", type=float, default=None,
                    metavar="MB",
                    help="per-device memory budget for --stream, in MiB "
                         "(covers all stream buffers of one mode shard)")
    ap.add_argument("--analyze", choices=("off", "warn", "strict"),
                    default="off",
                    help="run the repro.analysis plan rules on the plan "
                         "(strict: abort on any error finding) and, with "
                         "warn/strict, audit the compiled solver's HLO")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable span tracing and write a Chrome-trace "
                         "JSON (chrome://tracing / ui.perfetto.dev) "
                         "covering plan/compile/execute; the traced run "
                         "runs the same programs as an untraced one")
    ap.add_argument("--events-out", default=None, metavar="PATH",
                    help="mirror structured events (sweeps, rebalance "
                         "points, H2D windows) as JSON lines, flushed "
                         "live")
    args = ap.parse_args()

    from repro.launch import compile_cache
    compile_cache.enable()
    from repro.obs import clock
    from repro.obs import trace as obs_trace
    if args.trace_out:
        obs_trace.enable()

    import repro.api as api
    from repro.sparse.io import make_profile_tensor

    cfg = api.preset(args.preset, {"rank": args.rank})
    if args.devices:
        cfg = cfg.with_overrides({"runtime.num_devices": args.devices})
    if args.ckpt:
        cfg = cfg.with_overrides({"runtime.checkpoint_dir": args.ckpt})
    if args.rebalance:
        cfg = cfg.with_overrides({"schedule.rebalance": "on"})
    elif args.measure_balance:
        cfg = cfg.with_overrides({"schedule.rebalance": "measure"})
    if args.stream:
        overrides = {"runtime.streaming": True}
        if args.memory_budget_mb is not None:
            overrides["runtime.memory_budget"] = \
                int(args.memory_budget_mb * 2 ** 20)
        cfg = cfg.with_overrides(overrides)
    cfg = api.apply_set_args(cfg, args.set_args)

    if args.store is not None:
        from repro.store import TensorStore
        t = TensorStore(args.store)
        source = f"store {args.store}"
    elif args.tns is not None:
        from repro.sparse.io import read_tns
        t = read_tns(args.tns)
        source = args.tns
    else:
        t = make_profile_tensor(args.profile, scale=args.scale, seed=0)
        source = f"{args.profile} @ {args.scale}"
    print(f"{source}: shape={t.shape} nnz={t.nnz} "
          f"preset={args.preset} rank={cfg.rank} "
          f"variant={cfg.kernel.resolved_variant()} "
          f"policy={cfg.resolved_policy()} "
          f"rebalance={cfg.schedule.rebalance} "
          f"exchange={cfg.exchange.resolved_variant()}"
          f"/{cfg.exchange.wire_dtype}")

    t0 = clock.now()
    plan = api.plan(t, cfg, cache_dir=args.plan_cache,
                    analyze=args.analyze)
    t_plan = clock.now() - t0
    solver = api.compile(plan, cfg)
    t_compile = clock.now() - t0 - t_plan
    if args.events_out:
        solver.events.set_sink(args.events_out)
    if args.analyze != "off":
        findings = solver.audit()
        for f in findings:
            print(f"analysis: {f}")
        if args.analyze == "strict" and \
                any(f.severity == "error" for f in findings):
            from repro.analysis import AnalysisError, errors
            raise AnalysisError(errors(findings))
    if args.ckpt and not args.no_resume:
        solver.restore()
    t1 = clock.now()
    res = solver.run(args.iters, verbose=True)
    t_exec = clock.now() - t1

    hit = args.plan_cache is not None and api.CACHE_STATS["hits"] > 0
    print(f"plan {t_plan:.1f}s{' (cache hit)' if hit else ''} | "
          f"compile {t_compile:.1f}s | execute {t_exec:.1f}s")
    print(f"{res.sweeps} sweeps; final fit {res.fits[-1]:.5f}")

    report = solver.imbalance_report()
    if report.get("enabled"):
        c = report["coefficients"]
        print(f"schedule: epoch {report['rebalance_epoch']} | calibrated "
              f"sec_per_nnz={c['sec_per_nnz']:.3e} "
              f"sec_per_slot={c['sec_per_slot']:.3e} "
              f"sec_fixed={c['sec_fixed']:.3e}")
        for mode, row in report["per_mode"].items():
            meas = row["measured_imbalance"]
            print(f"  mode {mode} (r={row['r']}): measured max/mean "
                  f"{meas:.3f} | modelled {row['modelled_imbalance']:.3f}")
        for ev in report["events"]:
            worst = max(ev["imbalance"].values())
            line = (f"  sweep {ev['sweep']}: worst imbalance {worst:.3f}, "
                    f"{ev['migrations']} migration(s), "
                    f"{ev['moved_nnz']} nnz moved")
            print(line)

    if args.exchange_report:
        xr = solver.exchange_report()
        spec, model = xr["spec"], xr["modelled"]
        meas = xr["measured"]
        print(f"exchange: {spec['variant']} gather / {spec['merge']} merge "
              f"| wire {spec['wire_dtype']}"
              + (f" | chunk_rows {spec['chunk_rows']}"
                 if spec["chunk_rows"] else ""))
        import jax
        if spec["wire_dtype"] != "float32" and \
                jax.default_backend() != "tpu":
            print("  note: this backend upcasts reduced-precision "
                  "collectives to f32 in the compiled HLO (values are "
                  "still wire-rounded); measured bytes reflect that — "
                  "expect measured ≈ 2× modelled off-TPU")
        print(f"  per-sweep volume/device: modelled "
              f"{model['sweep_total_bytes'] / 1e6:.3f} MB | measured (HLO) "
              f"{meas['sweep_total_bytes'] / 1e6:.3f} MB")
        for mode, row in enumerate(model["per_mode"]):
            m_meas = meas["per_mode"][mode]["total_bytes"]
            print(f"  mode {mode}: modelled {row['total_bytes']} B "
                  f"(gather {row['gather_bytes']} + merge "
                  f"{row['merge_bytes']}) | measured {m_meas:.0f} B")

    ov = solver.overlap_report()
    if ov.get("enabled"):
        print(f"streaming: budget {ov['budget_bytes'] / 2**20:.1f} MiB/dev "
              f"x{ov['buffers']} buffers | shards/mode "
              f"{ov['shards_per_mode']} | peak resident "
              f"{ov['peak_resident_bytes'] / 2**20:.1f} MiB | "
              f"{ov['bytes_streamed'] / 2**20:.1f} MiB streamed "
              f"({ov['builds']} builds, {ov['cold_builds']} cold)")
        steady = ov["overlap_fraction_steady"]
        print(f"  transfer {ov['transfer_s']:.2f}s | hidden "
              f"{ov['hidden_s']:.2f}s | exposed {ov['exposed_s']:.2f}s | "
              f"overlap {ov['overlap_fraction']:.1%}"
              + (f" (steady {steady:.1%})" if steady is not None else ""))
        if ov["spill_saves"] or ov["spill_hits"]:
            print(f"  window spill: {ov['spill_saves']} saved, "
                  f"{ov['spill_hits']} replayed")
    if args.trace_out:
        solver.dump_trace(args.trace_out)
        summary = obs_trace.get_tracer().summary()
        stages = " ".join(f"{k}={v['count']}"
                          for k, v in sorted(summary.items()))
        print(f"trace: {args.trace_out} [{stages}]")
    if args.events_out:
        print(f"events: {args.events_out} ({len(solver.events)} lines)")
    solver.close()


if __name__ == "__main__":
    main()
