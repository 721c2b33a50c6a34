"""Benchmark substrate.

This container has ONE physical core, so wall-clock "multi-GPU" timing is
meaningless in-process. We follow the paper's own §5.5 methodology instead:
each device's grid is executed separately and timed; the parallel makespan
is max(per-device EC time) plus a communication model
(bytes / modelled link bandwidth). Figures report the same RATIOS the paper
reports (speedups, balance overheads, breakdowns), not absolute times.

Multi-virtual-device figures run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (never in the main
process — tests/benches must see one device).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Callable

import numpy as np

from repro.obs import trace as obs_trace

HERE = os.path.dirname(__file__)
OUT_DIR = os.path.join(HERE, "..", "experiments", "bench")

# communication model (single-node PCIe-class, as in the paper's platform)
H2D_BW = 64e9          # B/s host→device (paper: PCIe 64 GB/s)
P2P_BW = 50e9          # B/s device↔device


def timeit(fn: Callable, *args, repeats: int = 3, warmup: int = 1,
           label: str = "bench_fn") -> float:
    """Best-of-``repeats`` wall time via :func:`repro.obs.trace.timed` —
    always measured on the obs clock; when the span tracer is enabled each
    repeat additionally records a ``label`` span into the trace."""
    for _ in range(warmup):
        fn(*args)
    best = float("inf")
    for _ in range(repeats):
        with obs_trace.timed(label) as t:
            fn(*args)
        best = min(best, t.duration)
    return best


def run_subprocess_bench(script: str, *, devices: int = 8,
                         timeout: int = 3600) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # src for repro, the repo root for scripts that import benchmarks.*
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(HERE, "..", "src"), os.path.join(HERE, "..")])
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-4000:])
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("RESULT_JSON:"))
    return json.loads(line[len("RESULT_JSON:"):])


def save_result(name: str, payload: dict, *, also_root: bool = False) -> None:
    """Write ``experiments/bench/<name>.json``; with ``also_root`` a
    byte-identical copy also lands at the repo root (``<name>.json``) so the
    perf trajectory is diffable across PRs without digging into
    experiments/.

    The payload is serialized ONCE and both files get the same bytes via an
    atomic tmp + fsync + rename — a crash mid-save can no longer leave the
    two artifacts diverged (checked by benchmarks/check_trajectory.py),
    and double-serialization drift (e.g. a dict mutated between two
    ``json.dump`` calls) is impossible by construction."""
    os.makedirs(OUT_DIR, exist_ok=True)
    data = json.dumps(payload, indent=1, default=str)
    paths = [os.path.join(OUT_DIR, f"{name}.json")]
    if also_root:
        paths.append(os.path.join(HERE, "..", f"{name}.json"))
    for p in paths:
        tmp = p + ".tmp"
        with open(tmp, "w") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, p)


def print_csv(name: str, rows: list[dict]) -> None:
    if not rows:
        print(f"{name}: no rows")
        return
    keys = list(rows[0])
    print(f"# {name}")
    print(",".join(keys))
    for r in rows:
        print(",".join(str(r[k]) for k in keys))
