"""The check catches a broken timed path.

Each test drives a whole run of a cell (generation, plan, compile, the
first sweep, the window, the reference and the comparison) on the CPU at a
small scale, skipping only the look for a chip, with one fault planted in
the program underneath. ``correct`` must come out false. The cells run on
one chip, so there is no exchange between chips to leave out.
"""
import jax.numpy as jnp
import pytest

import run
from conftest import tiny_cell

SCALE = 2e-4
CELLS = ["amazon-r32.resident", "twitch-r32.resident"]


def _run(workload, seed=11):
    cell = tiny_cell(workload, SCALE)
    return run.run_cell(cell, seed, 0.5, False, require_tpu=False)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    out = _run(workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert {"sweep_s", "setup_s"} <= set(out["metrics"])


def _state_unchanged(monkeypatch):
    """Every mode update hands back the factor it was given."""
    from repro.core import als
    make = als.make_mode_update

    def broken(plan, mode, mesh, **kw):
        update = make(plan, mode, mesh, **kw)

        def call(f_old, dev, others, grams):
            _f, _g, m, lam = update(f_old, dev, others, grams)
            return f_old, grams[mode], m, jnp.ones_like(lam)
        return call
    monkeypatch.setattr(als, "make_mode_update", broken)


def _half_the_nonzeros(monkeypatch):
    """The MTTKRP drops every other nonzero and doubles the rest."""
    from repro.kernels import ops
    local = ops.mttkrp_local

    def broken(indices, values, *args, **kw):
        keep = (jnp.arange(values.shape[0]) % 2 == 0).astype(values.dtype)
        return local(indices, values * keep * 2, *args, **kw)
    monkeypatch.setattr(ops, "mttkrp_local", broken)


def _answer_altered(monkeypatch):
    """The MTTKRP loses one output row (local row 1) where it is made."""
    from repro.kernels import ops
    local = ops.mttkrp_local

    def broken(*args, **kw):
        return local(*args, **kw).at[1].set(0.0)
    monkeypatch.setattr(ops, "mttkrp_local", broken)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_the_nonzeros,
                                   _answer_altered],
                         ids=["state_unchanged", "half_the_nonzeros",
                              "answer_altered"])
def test_fault_makes_the_run_incorrect(workload, fault, monkeypatch):
    fault(monkeypatch)
    out = _run(workload)
    assert not out["correct"], out["checks"]
