"""Three-term roofline of a compiled dry-run artifact (no hardware).

    compute    = FLOPs_per_chip / peak_FLOPs          (197 TF/s bf16, v5e)
    memory     = bytes_per_chip / HBM_bw              (819 GB/s)
    collective = coll_bytes_per_chip / link_bw        (~50 GB/s/link ICI)

* **collective bytes** and **dot FLOPs** — loop-weighted, parsed from the
  post-SPMD HLO by :mod:`repro.comm.hlo`; ``cost_analysis()["flops"]`` is
  also reported (raw) but counts a loop body once.
* **memory bytes** — ``cost_analysis()["bytes accessed"]``, which both
  undercounts loops and overcounts fusion-boundary traffic, so the memory
  term is a rough bound.
"""
from __future__ import annotations

import dataclasses
from typing import Any

__all__ = ["HW", "roofline_terms"]


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12      # bf16 per chip
    hbm_bw: float = 819e9           # bytes/s
    link_bw: float = 50e9           # bytes/s per ICI link


def roofline_terms(cost: dict[str, Any], coll: dict[str, float],
                   hw: HW = HW(), *, dot_flops: float | None = None,
                   ) -> dict[str, float]:
    raw_flops = float(cost.get("flops", 0.0) or 0.0)
    raw_bytes = float(cost.get("bytes accessed", 0.0) or 0.0)
    flops = dot_flops if dot_flops else raw_flops
    cb = float(coll.get("total", 0.0))
    terms = {
        "flops_per_chip": flops,
        "raw_hlo_flops": raw_flops,
        "bytes_per_chip": raw_bytes,
        "raw_hlo_bytes": raw_bytes,
        "coll_bytes_per_chip": cb,
        "t_compute": flops / hw.peak_flops,
        "t_memory": raw_bytes / hw.hbm_bw,
        "t_collective": cb / hw.link_bw,
    }
    dom = max(("t_compute", "t_memory", "t_collective"),
              key=lambda k: terms[k])
    terms["bottleneck"] = dom
    t_max = terms[dom]
    terms["step_time_bound"] = t_max
    terms["roofline_fraction"] = (terms["t_compute"] / t_max) if t_max > 0 else 0.0
    return terms
