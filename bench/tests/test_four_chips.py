"""The 4-chip cell on four virtual CPU devices.

``amazon-4chip.resident`` is the one cell whose plan has more than one
group: its rows are owned by CDF ranges across the chips, each mode's
shards run the EC under ``shard_map``, and each mode update all-gathers
the owners' rows (the ``factor_exchange`` ring). Its whole run
(generation, plan, compile, the first sweep, the window, the reference and
the comparison) is driven here at a small scale on four CPU devices,
skipping only the look for a chip: once untraced, once traced, and once
with each chip keeping its own rows and skipping the all-gather, which must
come out not correct. The devices exist only in a child process
(``--xla_force_host_platform_device_count`` must be set before JAX starts),
which is this file run as a script; it prints one JSON line.
"""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

CELL = "amazon-4chip.resident"
SCALE = 2e-4
SEED = 2147483659


def _runs(out_dir: str) -> dict:
    """The child's work: the cell's three runs, and the solver report of a
    small 4-device plan with the rebalancer off."""
    import jax.numpy as jnp
    from jax import lax

    import repro.api as api
    import run
    from conftest import tiny_cell
    from repro import comm
    from repro.core.coo import random_sparse

    run.OUT_DIR = out_dir
    out = {}
    for name, trace in (("untraced", False), ("traced", True)):
        out[name] = run.run_cell(tiny_cell(CELL, SCALE), SEED, 0.5, trace,
                                 require_tpu=False)

    def own_rows_only(x, axis_names, **_kw):
        """Each chip keeps its own rows; the others stay zero."""
        idx = lax.axis_index(axis_names)
        full = jnp.zeros((comm.axis_size(axis_names) * x.shape[0],)
                         + x.shape[1:], x.dtype)
        return lax.dynamic_update_slice_in_dim(full, x, idx * x.shape[0], 0)

    gather = comm.all_gather_axes
    comm.all_gather_axes = own_rows_only
    try:
        out["no_gather"] = run.run_cell(tiny_cell(CELL, SCALE), SEED, 0.5,
                                        False, require_tpu=False)
    finally:
        comm.all_gather_axes = gather

    cfg = api.preset("paper", {"rank": 8, "runtime.num_devices": 4})
    t = random_sparse((300, 120, 90), 6000, seed=0, distribution="zipf")
    with api.compile(api.plan(t, cfg), cfg) as solver:
        sections = solver.report()["sections"]
    out["report"] = sections["partition"]
    out["exchange"] = sections["exchange"]["modelled"]
    return out


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    base = tmp_path_factory.mktemp("four_chips")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(base / "jax_cache"))
    p = subprocess.run([sys.executable, os.path.abspath(__file__),
                        str(base / "out")], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.splitlines()[-1])


def test_untraced_run_is_correct_on_four_devices(runs):
    out = runs["untraced"]
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert {"sweep_s", "setup_s"} <= set(out["metrics"])


def test_traced_run_reads_the_exchange(runs):
    out = runs["traced"]
    assert out["correct"], out["checks"]
    assert out["metrics"]["exchange_ms"]["value"] > 0
    assert out["metrics"]["ec_ms"]["value"] > 0


def test_skipped_all_gather_makes_the_run_incorrect(runs):
    out = runs["no_gather"]
    assert not out["correct"], out["checks"]


def test_partition_report_without_the_rebalancer(runs):
    rep = runs["report"]
    assert rep["num_devices"] == 4
    assert set(rep["per_mode"]) == {"0", "1", "2"}
    for mode in rep["per_mode"].values():
        assert mode["nnz_max_over_mean"] >= 1.0
        assert mode["slots_max_over_mean"] >= 1.0
        assert mode["padded_rows_over_rows"] > 1.0
    # the bytes the padded layout puts on the wire, in the same report
    assert runs["exchange"]["sweep_total_bytes"] > 0


if __name__ == "__main__":
    print(json.dumps(_runs(sys.argv[1])))
