"""The comparison that decides ``correct``.

The program's first sweep (every mode's MTTKRP ``M``, normalized factor
``F`` and column norms ``λ``, and the sweep's fit) is compared with the
reference's first sweep from the same tensor and initial factors:

    ec_err      max over modes of ‖M − M_ref‖_F / ‖M_ref‖_F
    factor_err  max over modes of ‖F − F_ref‖_F / ‖F_ref‖_F
    lam_err     max over modes and columns of |λ − λ_ref| / λ_ref
    fit_err     |fit − fit_ref|

Each has a limit of its own, set in the configuration's file from the
readings of sound runs and of the bfloat16 control (see PERF.md). A number
that is not finite fails.
"""
from __future__ import annotations

import math

import numpy as np

__all__ = ["NUMBERS", "per_mode", "compare", "judge"]

NUMBERS = ("ec_err", "factor_err", "lam_err", "fit_err")


def _rel(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def per_mode(prog, ref) -> dict:
    """``ec_err``, ``factor_err`` and ``lam_err`` mode by mode. ``prog`` and
    ``ref`` are :class:`reference.SweepOut` in the global row layout."""
    n = len(ref.m)
    return {
        "ec_err": [_rel(prog.m[d], ref.m[d]) for d in range(n)],
        "factor_err": [_rel(prog.f[d], ref.f[d]) for d in range(n)],
        "lam_err": [float(np.max(np.abs(np.asarray(prog.lam[d], np.float64)
                                        - ref.lam[d]) / ref.lam[d]))
                    for d in range(n)],
    }


def compare(prog, ref) -> dict:
    """The compared numbers: the worst mode of each, and the fit's gap."""
    out = {k: max(v) for k, v in per_mode(prog, ref).items()}
    out["fit_err"] = abs(float(prog.fit) - float(ref.fit))
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) — every number that has a
    limit finite and at or under it. A configuration leaves out the limit
    of a number whose sound and control readings do not separate."""
    checks = {k: {"value": numbers[k], "limit": limits[k]}
              for k in NUMBERS if k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
