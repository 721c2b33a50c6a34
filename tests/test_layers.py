"""The architecture's arrows as a test: which ``repro`` packages each
package may import.

Each package's ``repro.*`` imports (function-level ones included) are read
with ``ast`` and must lie inside its row of ``ALLOWED``. The table is the
package graph as it stands, cycles and all: each row's upward edges close a
cycle and are named as debts in ROADMAP.md. A change that adds an import
across packages edits this table on purpose.
"""
import ast
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src", "repro")

# package -> the repro packages (and the top-level ``compat`` module) it may
# import, bottom layer first. The second line of a row holds its upward
# edges, each closing a cycle, with the module that makes it.
ALLOWED = {
    "obs": set(),
    "kernels": {"obs",
                "core"},                  # kernels/autotune.py
    "comm": {"compat", "kernels", "obs"},
    "schedule": {"kernels", "obs",
                 "core"},                 # schedule/rebalance.py
    "core": {"comm", "compat", "kernels", "obs", "schedule",
             "api"},                      # core/decompose.py (cp_decompose)
    "sparse": {"core", "obs",
               "analysis"},               # sparse/stream.py (lock checks)
    "store": {"core", "obs", "schedule", "sparse"},
    "api": {"comm", "core", "kernels", "obs", "schedule", "sparse", "store",
            "analysis",                   # api/__init__, planning, solver
            "serve"},                     # api/solver.py (to_snapshot)
    "serve": {"api", "core", "obs", "store"},
    "analysis": {"api", "comm", "configs", "core", "kernels", "serve",
                 "sparse", "store"},
    "configs": {"api", "sparse"},
    "launch": {"analysis", "api", "comm", "compat", "core", "obs", "sparse",
               "store"},
}

# packages the CP system no longer carries; no file may name them
DELETED = re.compile(r"repro\.(models|training|serving)\b"
                     r"|repro\.launch\.(train|shapes)\b")


def _packages():
    return sorted(d for d in os.listdir(SRC)
                  if os.path.isdir(os.path.join(SRC, d))
                  and d != "__pycache__")


def _py_files(top):
    for dirpath, _, files in os.walk(top):
        for f in sorted(files):
            if f.endswith(".py"):
                yield os.path.join(dirpath, f)


def _repro_imports(path):
    """The ``repro`` packages (first component after ``repro.``) that the
    module at ``path`` imports."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            if node.module == "repro":
                names = [f"repro.{a.name}" for a in node.names]
            else:
                names = [node.module]
        else:
            continue
        out.update(n.split(".")[1] for n in names
                   if n.startswith("repro."))
    return out


def _package_imports(pkg):
    found = {}
    for path in _py_files(os.path.join(SRC, pkg)):
        for target in _repro_imports(path) - {pkg}:
            found.setdefault(target, os.path.relpath(path, ROOT))
    return found


@pytest.mark.parametrize("pkg", _packages())
def test_package_imports_stay_in_their_layer(pkg):
    assert pkg in ALLOWED, f"repro.{pkg} has no row in ALLOWED"
    found = _package_imports(pkg)
    extra = {t: where for t, where in found.items() if t not in ALLOWED[pkg]}
    assert not extra, f"repro.{pkg} imports packages outside its row: {extra}"


def test_no_package_below_launch_imports_it():
    importers = {}
    for pkg in _packages():
        where = _package_imports(pkg).get("launch")
        if pkg != "launch" and where:
            importers[pkg] = where
    assert not importers, importers


def test_no_module_names_a_deleted_package():
    here = os.path.abspath(__file__)
    tops = [os.path.join(ROOT, d)
            for d in ("src", "tests", "examples", "benchmarks", "bench")]
    paths = [p for top in tops for p in _py_files(top)]
    paths.append(os.path.join(ROOT, "chip_smoke.py"))
    hits = []
    for path in paths:
        if os.path.abspath(path) == here:
            continue
        with open(path) as f:
            for n, line in enumerate(f, 1):
                if DELETED.search(line):
                    hits.append(f"{os.path.relpath(path, ROOT)}:{n}")
    assert not hits, hits
