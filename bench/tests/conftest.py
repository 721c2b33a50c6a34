"""Tests of the benchmark, on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

They import the benchmark's modules from ``bench/`` and the program from
``src/``, and keep JAX's compilation cache in a temporary directory.
"""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(autouse=True)
def _compile_cache(tmp_path_factory, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path_factory.getbasetemp() / "jax_cache"))


def tiny_cell(workload: str, scale: float):
    """The cell as ``BENCHMARK.json`` gives it, with its tensor scaled to
    ``scale`` of the published one (the shapes keep their ratios)."""
    from cell import load_cell
    cell = load_cell(ROOT, workload)
    c = cell.config
    c["shape"] = [max(8, int(round(s * scale)))
                  for s in c["published_shape"]]
    c["draws"] = int(round(c["published_nnz"] * scale))
    return cell
