#!/usr/bin/env python3
"""CP-ALS benchmark: runs one cell of ``BENCHMARK.json`` on the chips here.

    python3 bench/run.py --workload amazon-r32.resident --seed 7 \\
        --seconds 10 --trace 0

A run draws the cell's tensor on the device from ``--seed`` (``gen.py``),
plans it with ``repro.api.plan``, compiles it with ``repro.api.compile``
and installs initial factors drawn from the seed. Its first sweep goes
through ``CPSolver.sweep()`` with every mode update's outputs kept; the
set-up (``setup_s``: process start to here) ends with it. The window then
calls ``CPSolver.sweep()`` for ``--seconds`` seconds, reading sweep k−1's
fit after enqueuing sweep k, and ends when the last sweep's fit is ready.
After the window the program is freed and the plain reference
(``reference.py``) runs the first sweep again; ``check.py`` compares.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs the
window under ``jax.profiler`` and prints its per-layer metrics, read from
the trace by ``metrics/<name>.py``. The last stdout line is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` when traced, and ``checks``: each compared number with its
limit); the compared numbers are also the last lines on stderr.

Off a TPU, with fewer chips than the cell asks for, on a device kind the
peaks table lacks, or without the program beside ``bench/``, a run exits
nonzero and prints no result. ``--control 1`` (calibration only) also runs
the bfloat16 control and prints its numbers.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
OUT_DIR = os.path.join(ROOT, ".bench_out")
CHECK_SWEEPS = 1


class BenchError(Exception):
    """A run that cannot produce a result (no chip, unknown device, ...)."""


def log(msg: str) -> None:
    print(msg, flush=True)


def now() -> float:
    return time.perf_counter()


def _environment() -> None:
    """Compile cache at a fixed path in the checkout, unless the caller set
    one; TPU runtime logs off (they would go to a fixed /tmp path)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def _compile_cache(jax) -> str:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    # the cache writes no entry into a directory that does not exist yet
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """XLA backend compilations through ``jax.monitoring``: ``count`` has
    every one, served from the persistent cache or not; ``cache_hits`` the
    ones the cache served."""

    def __init__(self, jax):
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, name, _secs, **_kw):
        if name.endswith("backend_compile_duration"):
            self.count += 1

    def _on_event(self, name, **_kw):
        if name.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def __str__(self):
        return f"{self.count} compilations, {self.cache_hits} from the cache"


def device_info(jax, chips: int, require_tpu: bool):
    """The cell's devices and the peaks of their kind."""
    import peaks as peaks_mod
    devices = jax.devices()
    d0 = devices[0]
    if require_tpu and d0.platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {d0.platform!r})")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    pk = peaks_mod.peaks_for(d0.device_kind) if require_tpu else None
    return devices[:chips], pk


def memory_stat(devices, key: str) -> int | None:
    """The largest ``memory_stats()[key]`` over the cell's devices."""
    vals = [(d.memory_stats() or {}).get(key) for d in devices]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    cell: object
    seed: int
    nnz: int
    shape: tuple
    rank: int
    peaks: dict | None
    setup_s: float
    plan_s: float
    sweeps: int
    window_s: float
    peak_bytes: int | None
    compiles_in_window: int
    trace: object = None             # tracing.Summary of the traced window


# -- the program's side -------------------------------------------------------

def _record_updates(jax, solver, sink: dict, programs: dict):
    """Wrap the solver's jitted mode updates: keep each mode update's
    outputs ``(F, G, M, λ)`` in ``sink`` and each program with the shapes
    it was called with in ``programs``. Returns the original update list."""
    original = list(solver.updates)

    def abstract(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        return x

    def wrap(fn, mode):
        def call(*args):
            programs.setdefault(mode, (fn, jax.tree_util.tree_map(
                abstract, args)))
            out = fn(*args)
            sink[mode] = out
            return out
        return call

    solver.updates = [wrap(u, d) for d, u in enumerate(original)]
    return original


def _program_sweep(jax, solver, plan, programs: dict):
    """One ``solver.sweep()`` with its outputs copied to the host, in the
    global row layout; the programs it ran go into ``programs``."""
    import numpy as np
    import reference
    sink: dict = {}
    original = _record_updates(jax, solver, sink, programs)
    try:
        state = solver.sweep()
        fit = float(state.fits[-1])
    finally:
        solver.updates = original
    m, f, lam = [], [], []
    for d in range(plan.nmodes):
        f_d, _g, m_d, lam_d = sink[d]
        g2p = np.asarray(plan.global_to_padded[d])
        m.append(np.asarray(m_d)[g2p])
        f.append(np.asarray(f_d)[g2p])
        lam.append(np.asarray(lam_d))
    return reference.SweepOut(m, f, lam, fit)


def _compiled(programs: dict) -> list:
    """The recorded programs compiled again from the shapes they ran with
    (the persistent cache serves them): their HLO and memory analysis.
    An update that is not a jitted function (a test's wrapper) has none."""
    return [fn.lower(*args).compile() for fn, args in programs.values()
            if hasattr(fn, "lower")]


def peak_estimate(live: int | None, analyses, allocator_peak: int | None):
    """Peak device bytes of the window, from what the allocator reports and
    what the compiler says each program allocates.

    ``live`` is ``bytes_in_use`` after the window: the resident shards and
    the solver's state, which hold every program's arguments between
    programs. While a program runs it adds its outputs that do not alias
    an argument and its temporaries, which the allocator's
    ``peak_bytes_in_use`` leaves out on the TPU. The estimate is the
    largest of ``live`` plus that, over the programs, or the allocator's
    peak where that is larger (generation, placement)."""
    if live is None:
        return None
    extra = [a.output_size_in_bytes - a.alias_size_in_bytes
             + a.temp_size_in_bytes for a in analyses]
    return max(live + max(extra, default=0), allocator_peak or 0)


def _window(jax, solver, seconds: float, annotate):
    """Sweeps for ``seconds``; returns (sweeps, window seconds, fits)."""
    fits, ends = [], []
    t0 = now()
    prev = None
    while True:
        with annotate("sweep"):
            state = solver.sweep()
        cur = state.fits[-1]
        if prev is not None:
            with annotate("fit_read"):
                fits.append(float(prev))
            ends.append(now() - t0)
            if ends[-1] >= seconds:
                break
        prev = cur
    with annotate("window_wait"):
        jax.block_until_ready(cur)
        fits.append(float(cur))
    window_s = now() - t0
    ends.append(window_s)
    log("sweep ends (s): " + json.dumps([round(e, 6) for e in ends]))
    return len(fits), window_s, fits


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, control: bool = False) -> dict:
    """One run; returns the result line's object."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import check
    import gen
    import reference
    from cell import metric_reader

    _compile_cache(jax)
    devices, pk = device_info(jax, cell.chips, require_tpu)
    counter = CompileCounter(jax)
    import repro.api as api
    from repro.core.coo import SparseTensor
    from repro.obs.profiler import annotation

    cfg_doc = cell.config
    if cell.traffic.get("placement") != "resident":
        raise BenchError(f"placement {cell.traffic.get('placement')!r}: "
                         f"only 'resident' is measured")
    shape = tuple(int(s) for s in cfg_doc["shape"])
    rank = int(cfg_doc["rank"])

    # -- set-up: data, plan, compile, first sweep --------------------------
    t = now()
    gen_t: dict = {}
    indices, values = gen.generate(seed, shape, int(cfg_doc["draws"]),
                                   float(cfg_doc["zipf_a"]), gen_t)
    gen_s = now() - t
    log(f"generation: {gen_t} ({counter})")
    nnz = int(values.shape[0])
    tops = [float(np.bincount(indices[:, w], minlength=s).max() / nnz)
            for w, s in enumerate(shape)]
    log(f"tensor: seed={seed} shape={shape} draws={cfg_doc['draws']} "
        f"nnz={nnz} top-row share per mode={tops} generated in {gen_s:.3f}s"
        f" (allocator peak so far {memory_stat(devices, 'peak_bytes_in_use')}"
        f" B)")
    tensor = SparseTensor(indices, values, shape)
    overrides = {"rank": rank, "runtime.seed": int(seed) & 0x7FFFFFFF,
                 "runtime.tol": 0.0, "runtime.num_devices": cell.chips}
    t = now()
    cfg = api.preset(cfg_doc.get("preset", "paper"), overrides)
    plan = api.plan(tensor, cfg)
    plan_s = now() - t
    t = now()
    solver = api.compile(plan, cfg)
    compile_s = now() - t
    resident = memory_stat(devices, "bytes_in_use")
    log(f"placed: device bytes in use {resident} (the tensor's shards, "
        f"every mode, and the initial state)")
    t = now()
    init = reference.init_factors(seed, shape, rank)
    solver.load_state(init, np.ones(rank, np.float32))
    programs: dict = {}
    prog = _program_sweep(jax, solver, plan, programs)
    first_sweep_s = now() - t
    setup_s = now() - T_START
    log(f"set-up {setup_s:.3f}s: generate {gen_s:.3f}s, plan {plan_s:.3f}s, "
        f"compile (placement) {compile_s:.3f}s, initial factors + first "
        f"sweep {first_sweep_s:.3f}s; first fit {prog.fit!r} ({counter})")

    # -- the window ---------------------------------------------------------
    before = counter.count
    summary = None
    if trace:
        import tracing
        tdir = os.path.join(OUT_DIR, "trace", cell.name)
        shutil.rmtree(tdir, ignore_errors=True)
        with tracing.capture(tdir):
            with annotation("window"):
                sweeps, window_s, fits = _window(jax, solver, seconds,
                                                 annotation)
    else:
        sweeps, window_s, fits = _window(jax, solver, seconds, annotation)
    compiles = counter.count - before
    live = memory_stat(devices, "bytes_in_use")
    compiled = _compiled(programs)
    analyses = [c.memory_analysis() for c in compiled]
    allocator_peak = memory_stat(devices, "peak_bytes_in_use")
    peak = peak_estimate(live, analyses, allocator_peak)
    failed = sum(1 for x in fits if not math.isfinite(x))
    sizes = [(a.argument_size_in_bytes, a.output_size_in_bytes,
              a.alias_size_in_bytes, a.temp_size_in_bytes) for a in analyses]
    log(f"window: {sweeps} sweeps in {window_s:.6f}s, {compiles} "
        f"compilations, last fit {fits[-1]!r}; device bytes live {live}, "
        f"allocator peak {allocator_peak}, per program (arguments, outputs, "
        f"aliased, temporaries) {sizes}, peak {peak}")

    # -- free the program, then the reference -------------------------------
    if trace:
        # the trace's op events carry no op names: take them from the
        # compiled programs' HLO
        summary = tracing.reduce_dir(tdir, tracing.op_scopes_from_hlo(
            [c.as_text() for c in compiled]))
        log(f"device seconds per scope: {summary.scope_s}")
    del compiled
    solver.close()
    del solver, plan
    gc.collect()
    coo = reference.DeviceCOO(indices, values, shape)
    t = now()
    ref = reference.als_sweeps(coo, init, CHECK_SWEEPS)[0]
    log(f"reference sweep in {now() - t:.3f}s; reference fit {ref.fit!r}")
    numbers = check.compare(prog, ref)
    log("per mode: " + json.dumps(check.per_mode(prog, ref)))
    correct, checks = check.judge(numbers, cfg_doc["limits"])
    correct = correct and failed == 0
    if control:
        ctl = reference.als_sweeps(coo, init, CHECK_SWEEPS,
                                   ec_dtype=jnp.bfloat16)[0]
        log("control (bfloat16 EC) numbers: " + json.dumps(
            check.compare(ctl, ref)))
        log("control per mode: " + json.dumps(check.per_mode(ctl, ref)))
    del coo

    ctx = Context(cell=cell, seed=seed, nnz=nnz, shape=shape, rank=rank,
                  peaks=pk, setup_s=setup_s, plan_s=plan_s, sweeps=sweeps,
                  window_s=window_s, peak_bytes=peak,
                  compiles_in_window=compiles, trace=summary)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "device_kind": d0.device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": sweeps, "failed": failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        _environment()
        sys.path.insert(0, BENCH_DIR)
        from cell import load_cell
        cell = load_cell(ROOT, args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          control=bool(args.control))
    except (BenchError, KeyError, ValueError, OSError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
