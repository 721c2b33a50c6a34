"""The repo's conventions over a few jax APIs (jax >= 0.9).

All repo code goes through these wrappers: :func:`shard_map` always runs
with ``check_vma=False`` (the collectives here are written per device), and
:func:`make_mesh` gives every axis the ``Auto`` type.
"""
from __future__ import annotations

import jax
from jax import lax

__all__ = ["shard_map", "axis_size", "cost_analysis", "make_mesh"]


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` with every axis of type ``Auto``."""
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()``, ``{}`` where the backend gives none."""
    return compiled.cost_analysis() or {}


def axis_size(axis_name: str) -> int:
    """Static mesh-axis size inside shard_map."""
    return lax.axis_size(axis_name)


def shard_map(f, *, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
