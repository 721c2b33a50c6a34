"""The harness refuses to report where it cannot measure, and finds every
cell's files by name."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT


def _run(cwd, *extra, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"),
         "--workload", "amazon-r32.resident", "--seed", "3",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_off_tpu_run_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_benchmark_alone_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert "no program" in p.stderr
    assert p.stdout.strip() == ""


def test_every_entry_finds_its_files():
    from cell import load_cell, metric_reader
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cfg in bench["configs"]:
        assert cfg["file"].startswith("bench/configs/")
        with open(os.path.join(ROOT, cfg["file"])) as f:
            doc = json.load(f)
        assert doc["name"] == cfg["name"]
        assert {"ec_err", "factor_err", "lam_err"} <= set(doc["limits"])
        assert set(doc["limits"]) <= {"ec_err", "factor_err", "lam_err",
                                      "fit_err"}
    for w in bench["workloads"]:
        cell = load_cell(ROOT, w["name"])
        assert cell.end_to_end and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(metric_reader(m["name"]))


@pytest.mark.parametrize("chips", [4])
def test_too_few_chips_is_refused(chips):
    import jax
    import run
    with pytest.raises(run.BenchError, match="needs 4 chips"):
        run.device_info(jax, chips, require_tpu=False)


def test_peak_counts_each_programs_own_allocations():
    """The peak is what is live between programs plus the most one program
    adds: its outputs that do not alias an argument and its temporaries."""
    import types
    import run

    def analysis(out, alias, temp):
        return types.SimpleNamespace(output_size_in_bytes=out,
                                     alias_size_in_bytes=alias,
                                     temp_size_in_bytes=temp)
    progs = [analysis(100, 40, 1000), analysis(500, 0, 700),
             analysis(10, 10, 0)]
    assert run.peak_estimate(5000, progs, 6000) == 5000 + 1200
    assert run.peak_estimate(5000, progs, 9000) == 9000
    assert run.peak_estimate(5000, [], None) == 5000
    assert run.peak_estimate(None, progs, 9000) is None
