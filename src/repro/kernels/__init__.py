"""MTTKRP EC kernels: the slot-order oracle (ref), blocked Pallas kernel with
XLA pre-gather (mttkrp_pallas), and the fused in-kernel-gather streaming
kernel (mttkrp_fused). Variant dispatch, and the pure-XLA ``ref`` variant,
live in ops; (tile, block_p, num_buffers) selection in autotune. See EXPERIMENTS.md §Perf."""
from repro.kernels.mttkrp_fused import ec_fused
from repro.kernels.mttkrp_pallas import ec_blocked
from repro.kernels.ops import (KERNEL_VARIANTS, default_interpret,
                               mttkrp_local, resolve_variant)

__all__ = ["ec_blocked", "ec_fused", "mttkrp_local", "resolve_variant",
           "KERNEL_VARIANTS", "default_interpret"]
