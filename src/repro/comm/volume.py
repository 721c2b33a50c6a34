"""Exchange-volume accounting: modelled bytes vs HLO-measured bytes.

The model follows the canonical ring formulas (the paper's §4.9 cost):

* gather — after the intra-group merge each device holds
  ``rows_max / r`` output rows; a ring (or bandwidth-optimal all-gather)
  moves every remote block through every device once, so each device
  **sends** ``(m-1) · rows_max/r · R`` elements per mode update (the
  ``overlap`` variant moves the same bytes, just pipelined).
* merge — a reduce-scatter over the ``r`` group members sends
  ``(r-1) · rows_max/r · R`` elements per device (identity when r = 1,
  the paper's zero-communication case).

With a bf16 wire both terms halve — exactly the ratio the launcher and the
``exchange_overlap`` benchmark assert between modelled fp32 and bf16 runs.

The *measured* side parses a compiled computation's HLO with
:mod:`repro.comm.hlo` (loop-weighted per-device bytes for all-gather /
collective-permute / reduce-scatter / all-reduce), so model drift is
visible machine-readably instead of silently.
"""
from __future__ import annotations

import numpy as np

from repro.comm.hlo import collective_bytes

__all__ = ["wire_bytes", "mode_exchange_bytes", "modelled_exchange_bytes",
           "measured_exchange_bytes"]

_WIRE_BYTES = {"float32": 4, "bfloat16": 2, None: 4}

# HLO collective kinds that carry exchange traffic (the EC kernels emit none
# of these; anything else in the update — e.g. the gram psum — is not
# exchange and is reported separately by the roofline tooling).
EXCHANGE_COLLECTIVES = ("all-gather", "collective-permute", "reduce-scatter",
                        "all-reduce")


def wire_bytes(wire_dtype: str | None) -> int:
    """Bytes per element on the wire for a named wire dtype."""
    try:
        return _WIRE_BYTES[wire_dtype]
    except KeyError:
        return int(np.dtype(wire_dtype).itemsize)


def mode_exchange_bytes(part, rank: int, *, wire_dtype: str | None = None,
                        ) -> dict:
    """Modelled per-device exchange bytes for one mode update of
    ``part`` (a :class:`~repro.core.partition.ModePartition`)."""
    wb = wire_bytes(wire_dtype)
    m, r = int(part.num_devices), int(part.r)
    gather_rows = part.rows_max // r
    gather = (m - 1) * gather_rows * rank * wb
    merge = (r - 1) * (part.rows_max // r) * rank * wb if r > 1 else 0
    return {"gather_bytes": int(gather), "merge_bytes": int(merge),
            "total_bytes": int(gather + merge)}


def modelled_exchange_bytes(plan, rank: int, *,
                            wire_dtype: str | None = None) -> dict:
    """Modelled per-device exchange bytes for one full ALS sweep of
    ``plan`` (every mode's merge + gather)."""
    per_mode = [mode_exchange_bytes(p, rank, wire_dtype=wire_dtype)
                for p in plan.modes]
    return {
        "wire_dtype": wire_dtype or "float32",
        "per_mode": per_mode,
        "sweep_total_bytes": int(sum(p["total_bytes"] for p in per_mode)),
    }


def measured_exchange_bytes(hlo_text: str) -> dict:
    """Per-device exchange bytes measured from compiled HLO (loop-weighted,
    via :func:`repro.comm.hlo.collective_bytes`), split by collective
    kind plus the summed total."""
    coll = collective_bytes(hlo_text)
    picked = {k: float(v) for k, v in coll.items()
              if k in EXCHANGE_COLLECTIVES}
    return {"by_kind": picked, "total_bytes": float(sum(picked.values()))}
