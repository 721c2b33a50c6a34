"""repro.comm — the factor-exchange subsystem (paper §4.9, Algorithm 3).

Everything that moves factor partitions between devices lives here:

* :mod:`repro.comm.collectives` — the gather-variant registry
  (``allgather | ring | overlap``) and merge-variant registry
  (``psum_scatter | ring_rs``), including the chunked double-buffered
  overlap gather and the bf16-wire / fp32-accumulate mixed-precision path.
* :mod:`repro.comm.spec` — :class:`ExchangeSpec`, the resolved, hashable
  configuration ``core.mttkrp`` bakes into traces, and
  :func:`resolve_exchange_spec` (config → spec, same precedence rules as
  ``kernels/ops.py``).
* :mod:`repro.comm.autotune` — chunk-size autotuner for the overlap
  variant, sharing the EC autotuner's JSON cache.
* :mod:`repro.comm.volume` — modelled vs HLO-measured exchange volume.
* :mod:`repro.comm.hlo` — the loop-weighted HLO-text parser behind the
  measured volume and the dry run's roofline.

``repro.core.exchange`` is a thin backwards-compatibility shim over this
package.
"""
from repro.comm.collectives import (DEFAULT_MERGE, DEFAULT_VARIANT,
                                    ENV_MERGE, ENV_VARIANT, GATHER_VARIANTS,
                                    MERGE_VARIANTS, all_gather_axes,
                                    axis_size, default_chunk_rows,
                                    merge_partials, overlap_all_gather,
                                    resolve_merge, resolve_variant,
                                    ring_all_gather, ring_reduce_scatter)
from repro.comm.spec import ExchangeSpec, resolve_exchange_spec
from repro.comm.volume import (measured_exchange_bytes, mode_exchange_bytes,
                               modelled_exchange_bytes, wire_bytes)

__all__ = [
    "GATHER_VARIANTS", "MERGE_VARIANTS", "ENV_VARIANT", "ENV_MERGE",
    "DEFAULT_VARIANT", "DEFAULT_MERGE",
    "resolve_variant", "resolve_merge", "axis_size", "default_chunk_rows",
    "ring_all_gather", "overlap_all_gather", "all_gather_axes",
    "ring_reduce_scatter", "merge_partials",
    "ExchangeSpec", "resolve_exchange_spec",
    "wire_bytes", "mode_exchange_bytes", "modelled_exchange_bytes",
    "measured_exchange_bytes",
]
