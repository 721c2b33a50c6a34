"""The production CP mesh of the dry run.

Defined as a function (never a module-level constant) so importing this
module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init; tests and benches see the real (single) device.
"""
from __future__ import annotations

from repro import compat

__all__ = ["make_cp_production_mesh"]


def make_cp_production_mesh(*, multi_pod: bool = False, replication: int = 16):
    """The CP-ALS mesh ("group","sub") over one pod (256 chips) or two
    (512), with |sub| = ``replication`` (the intra-group merge axis; 1 →
    pure paper scheme)."""
    total = 512 if multi_pod else 256
    assert total % replication == 0
    return compat.make_mesh(
        (total // replication, replication), ("group", "sub"))
