"""The program's own spans and scopes under the benchmark's reduction.

A CPU run of the program with its span tracer on, traced inside the
benchmark's annotations (``run._window``): every span is a profiler host
event, and the ``als_solve`` scope reaches the compiled op names. The
reduction reads the trace as it read one without them: idle gaps are
named by the benchmark's annotations alone, and the ``als_solve`` ops are
part of ``other``, which ``solve_ms`` reads.
"""
import glob
import os

import numpy as np

SHAPE, DRAWS, RANK = (240, 90, 90), 4000, 8


def test_program_spans_and_solve_scope_in_a_traced_window(tmp_path):
    import jax
    import gen
    import run
    import tracing
    import repro.api as api
    from repro import obs
    from repro.core import als
    from repro.core.coo import SparseTensor
    from repro.obs import trace as obs_trace
    from repro.obs.profiler import annotation

    obs.reset()
    obs_trace.enable()
    idx, val = gen.generate(1, SHAPE, DRAWS, 1.1, {})
    cfg = api.preset("paper", {"rank": RANK, "runtime.num_devices": 1,
                               "runtime.tol": 0.0})
    plan = api.plan(SparseTensor(np.asarray(idx), np.asarray(val), SHAPE),
                    cfg)
    solver = api.compile(plan, cfg)
    try:
        programs: dict = {}
        run._program_sweep(jax, solver, plan, programs)
        with tracing.capture(str(tmp_path)):
            with annotation("window"):
                sweeps, _, _ = run._window(jax, solver, 0.0, annotation)
        s = solver.state
        fit = als.fit_from_stats.lower(plan.norm, s.factors[-1],
                                       s.factors[-1], s.lam, s.grams)
        texts = [c.as_text() for c in run._compiled(programs)]
        texts.append(fit.compile().as_text())
        records = obs_trace.get_tracer().records()
    finally:
        solver.close()
        obs.reset()

    names = tracing.op_scopes_from_hlo(texts)
    summary = tracing.reduce_dir(str(tmp_path), names)
    assert summary.scope_s["unattributed"] == 0.0
    assert all(name in tracing.HOST_NAMES + ("host",)
               for name, _ in summary.gaps)

    # the als_solve ops are all in "other", and hold most of it on the CPU
    solve = 0.0
    for key, sec in summary.op_s.items():
        op, scope = key[:-1].rsplit(" [", 1)
        if "/als_solve/" in names.get(tuple(op.split("/", 1)), ""):
            assert scope == "other", key
            solve += sec
    assert 0 < solve <= summary.scope_s["other"] * (1 + 1e-9)
    assert any("jit(fit_from_stats)/als_solve/" in v for v in names.values())

    # each sweep's mode_update spans are host events of the trace
    trace, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                       recursive=True)
    pd = jax.profiler.ProfileData.from_file(trace)
    host = [ev.name for plane in pd.planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events]
    assert host.count("mode_update") == sweeps * len(SHAPE)
    by_id = {r["id"]: r for r in records}
    updates = [r for r in records if r["name"] == "mode_update"]
    assert len(updates) == (sweeps + 1) * len(SHAPE)
    assert {by_id[r["parent"]]["name"] for r in updates} == {"sweep"}
