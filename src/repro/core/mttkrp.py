"""Distributed MTTKRP (paper Algorithms 1–2) via shard_map.

Per output mode ``d``:
  1. every device runs the EC on its shard (Pallas kernel or the XLA ref) —
     no cross-device write conflicts by the partitioning invariant,
  2. replication groups (r>1) merge partials with an intra-group
     reduce-scatter (``psum_scatter`` or the explicit ``ring_rs`` schedule;
     identity for the paper's r=1),
  3. the output factor partitions are exchanged via the configured
     :class:`repro.comm.ExchangeSpec` — XLA's native all-gather, the
     Algorithm-3 ``ring``, or the chunked double-buffered ``overlap``
     schedule, optionally on a bf16 wire — and the gathered
     ``(n_groups * rows_max, R)`` slabs are packed to the owned rows
     (:func:`pack_slabs`, under the same ``factor_exchange`` scope),
     yielding the replicated factor, in its mode's packed ownership layout
     (core/partition.py), for the next mode.

Device axes: the CP mesh is (n_groups, r) named ("group", "sub"); the
production dry run (launch/dryrun.py) builds the same mesh over 256 or 512
devices.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import comm
from repro.compat import shard_map
from repro.core.partition import (CPPlan, ModePartition,
                                  block_segment_descriptors)
from repro.kernels import ops as kops
from repro.obs import profiler as obs_profiler

__all__ = ["DeviceArrays", "cp_mesh", "shard_plan_mode", "distributed_mttkrp",
           "make_mttkrp_fn", "shard_super_shard", "zero_partials",
           "make_partial_mttkrp_fn", "make_streaming_finish_fn",
           "pack_slabs"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DeviceArrays:
    """One mode's shard arrays, laid out (n_groups, r, ...) for shard_map.
    Registered as a pytree so jit in_shardings / ShapeDtypeStruct trees
    work directly."""

    indices: jax.Array        # (G, r, nnz_max, N) int32
    values: jax.Array         # (G, r, nnz_max) f32
    local_rows: jax.Array     # (G, r, nnz_max) int32
    block_to_tile: jax.Array  # (G, r, nblocks) int32
    tile_visited: jax.Array   # (G, r, ntiles) f32
    # Per-block row-segment descriptors for the "sorted" EC variant; small
    # (O(nblocks * tile)) and derived from local_rows at shard time, never
    # serialized (see core.partition.block_segment_descriptors).
    seg_starts: jax.Array     # (G, r, nblocks, tile + 2) int32
    seg_rows: jax.Array       # (G, r, nblocks, tile + 1) int32


def cp_mesh(num_devices: int, r: int, devices=None) -> Mesh:
    """Mesh for CP runs: (group, sub) with |sub| = r."""
    if devices is None:
        devices = np.asarray(jax.devices()[:num_devices])
    assert num_devices % r == 0
    dev = np.asarray(devices).reshape(num_devices // r, r)
    return Mesh(dev, ("group", "sub"))


def shard_plan_mode(part: ModePartition, mesh: Mesh,
                    group_axes=("group",), sub_axis="sub") -> DeviceArrays:
    """Move one mode's host arrays onto the mesh, sharded one-shard-per-device.

    Out-of-core partitions (``part.lazy``, see
    :class:`repro.store.StoreModePartition`) never stack a host-side
    ``(m, nnz_max)`` array: each device's slice is streamed from the store
    and placed on its device one at a time, so peak host memory stays
    bounded by a single device's shard plus the store's chunk size.
    """
    g, r = part.n_groups, part.r

    def reshape(x):
        return x.reshape((g, r) + x.shape[1:])

    def put(x, trailing):
        sh = NamedSharding(mesh, P(group_axes, sub_axis, *([None] * trailing)))
        return jax.device_put(reshape(x), sh)

    if getattr(part, "lazy", False):
        indices, values, local_rows, seg_starts, seg_rows = _shard_lazy_mode(
            part, mesh, group_axes, sub_axis)
    else:
        ss, sr = block_segment_descriptors(
            part.local_rows, tile=part.tile, block_p=part.block_p)
        indices = put(part.indices, 2)
        values = put(part.values, 1)
        local_rows = put(part.local_rows, 1)
        seg_starts = put(ss, 2)
        seg_rows = put(sr, 2)

    return DeviceArrays(
        indices=indices,
        values=values,
        local_rows=local_rows,
        block_to_tile=put(part.block_to_tile, 1),
        tile_visited=put(part.tile_visited, 1),
        seg_starts=seg_starts,
        seg_rows=seg_rows,
    )


def _shard_lazy_mode(part, mesh: Mesh, group_axes, sub_axis):
    """Per-device streaming placement of a lazy partition's O(nnz) arrays.

    Materializes ONE device's ``(indices, values, local_rows)`` at a time
    (``part.device_arrays``), places the three buffers on that device, and
    assembles the global sharded arrays from the single-device pieces —
    the host never holds more than one device's slice.
    """
    g, r = part.n_groups, part.r
    nmodes = part.nmodes
    nblocks = part.nnz_max // part.block_p
    nseg = part.tile + 1
    shapes = {
        "indices": ((g, r, part.nnz_max, nmodes), np.int32, 2),
        "values": ((g, r, part.nnz_max), np.float32, 1),
        "local_rows": ((g, r, part.nnz_max), np.int32, 1),
        "seg_starts": ((g, r, nblocks, nseg + 1), np.int32, 2),
        "seg_rows": ((g, r, nblocks, nseg), np.int32, 2),
    }
    shardings = {
        k: NamedSharding(mesh, P(group_axes, sub_axis, *([None] * tr)))
        for k, (_, _, tr) in shapes.items()}
    bufs = {k: [] for k in shapes}
    # one index map serves all the arrays: the (group, sub) placement is
    # identical, only trailing (replicated) dims differ
    dev_map = shardings["values"].devices_indices_map(shapes["values"][0])
    for device, idx in dev_map.items():
        gg = idx[0].start or 0
        ss = idx[1].start or 0
        di, dv, dr = part.device_arrays(gg * r + ss)
        dss, dsr = block_segment_descriptors(dr, tile=part.tile,
                                             block_p=part.block_p)
        bufs["indices"].append(jax.device_put(di[None, None], device))
        bufs["values"].append(jax.device_put(dv[None, None], device))
        bufs["local_rows"].append(jax.device_put(dr[None, None], device))
        bufs["seg_starts"].append(jax.device_put(dss[None, None], device))
        bufs["seg_rows"].append(jax.device_put(dsr[None, None], device))
        del di, dv, dr, dss, dsr  # host copy freed before the next device
    return tuple(
        jax.make_array_from_single_device_arrays(
            shapes[k][0], shardings[k], bufs[k])
        for k in ("indices", "values", "local_rows", "seg_starts",
                  "seg_rows"))


def _local_ec(part_meta: dict, indices, values, local_rows, block_to_tile,
              tile_visited, seg_starts, seg_rows, factors, *,
              use_kernel: bool, variant: str | None, num_buffers: int,
              interpret: bool | None):
    return kops.mttkrp_local(
        indices, values, local_rows, block_to_tile, factors,
        mode=part_meta["mode"], num_rows=part_meta["rows_max"],
        tile=part_meta["tile"], block_p=part_meta["block_p"],
        use_kernel=use_kernel, variant=variant, num_buffers=num_buffers,
        interpret=interpret, tile_mask=tile_visited,
        seg_starts=seg_starts, seg_rows=seg_rows)


def pack_slabs(x, rows_owned, rows_max: int):
    """The exchange's gathered ``(n_groups * rows_max, ...)`` slabs in the
    packed ownership layout: ``concat(slab_0[:owned_0], …,
    slab_{G-2}[:owned_{G-2}], slab_{G-1})``, one static copy. With one group
    the slab is the packed factor, and no op is emitted."""
    n_groups = len(rows_owned)
    if n_groups == 1:
        return x
    parts = [x[g * rows_max:g * rows_max + int(rows_owned[g])]
             for g in range(n_groups - 1)]
    parts.append(x[(n_groups - 1) * rows_max:])
    return jnp.concatenate(parts, axis=0)


def _exchange(merged, part, all_axes, exchange_spec):
    """The all-gather of every device's merged slab, then the packing copy:
    the factor exchange of one mode update, under ``factor_exchange``."""
    with obs_profiler.device_scope("factor_exchange"):
        out = comm.all_gather_axes(merged, all_axes,
                                   **exchange_spec.gather_kwargs())
        return pack_slabs(out, part.rows_owned, part.rows_max)


def make_mttkrp_fn(
    part: ModePartition,
    mesh: Mesh,
    *,
    group_axes: tuple[str, ...] = ("group",),
    sub_axis: str = "sub",
    use_kernel: bool = True,
    variant: str | None = None,
    num_buffers: int = 2,
    interpret: bool | None = None,
    ring: bool | None = None,
    exchange_spec: comm.ExchangeSpec | None = None,
):
    """Build the jit-able distributed MTTKRP for one mode.

    Returns fn(device_arrays, factors) -> replicated output factor
    (padded_rows, R) f32 in the packed ownership layout: the EC's
    ``(rows_max, R)`` partial per device, the merge, the all-gather of the
    slabs and the packing copy (:func:`pack_slabs`). ``factors`` are the
    replicated packed factor matrices (one per mode; the output mode's
    entry is ignored).

    ``variant`` selects the EC kernel (``"ref" | "blocked" | "fused"``, see
    repro.kernels.ops); ``num_buffers`` is the fused variant's DMA ring
    depth. ``exchange_spec`` (a :class:`repro.comm.ExchangeSpec`) selects
    the exchange schedule — gather variant, merge variant, overlap chunk
    size, wire dtype; ``ring`` is the legacy boolean spelling of the gather
    variant, honoured only when no spec is given.
    """
    meta = dict(mode=part.mode, rows_max=part.rows_max, tile=part.tile,
                block_p=part.block_p)
    all_axes = tuple(group_axes) + (sub_axis,)
    if exchange_spec is None:
        exchange_spec = comm.ExchangeSpec(
            variant=comm.resolve_variant(None, ring))

    def local_fn(indices, values, local_rows, block_to_tile, tile_visited,
                 seg_starts, seg_rows, *factors):
        # strip the (1,1,...) sharded leading dims added by shard_map
        indices = indices.reshape(indices.shape[-2:])
        values = values.reshape(values.shape[-1])
        local_rows = local_rows.reshape(local_rows.shape[-1])
        block_to_tile = block_to_tile.reshape(block_to_tile.shape[-1])
        tile_visited = tile_visited.reshape(tile_visited.shape[-1])
        seg_starts = seg_starts.reshape(seg_starts.shape[-2:])
        seg_rows = seg_rows.reshape(seg_rows.shape[-2:])
        with obs_profiler.device_scope("ec_local"):
            partial = _local_ec(meta, indices, values, local_rows,
                                block_to_tile, tile_visited, seg_starts,
                                seg_rows, list(factors),
                                use_kernel=use_kernel,
                                variant=variant, num_buffers=num_buffers,
                                interpret=interpret)
        with obs_profiler.device_scope("merge"):
            merged = comm.merge_partials(
                partial, sub_axis if part.r > 1 else None,
                **exchange_spec.merge_kwargs())
        return _exchange(merged, part, all_axes, exchange_spec)

    in_specs = (
        P(group_axes, sub_axis, None, None),
        P(group_axes, sub_axis, None),
        P(group_axes, sub_axis, None),
        P(group_axes, sub_axis, None),
        P(group_axes, sub_axis, None),
        P(group_axes, sub_axis, None, None),
        P(group_axes, sub_axis, None, None),
    )

    def fn(dev: DeviceArrays, factors: Sequence[jax.Array]) -> jax.Array:
        nf = len(factors)
        f_specs = tuple(P(None, None) for _ in range(nf))
        shmap = shard_map(
            local_fn,
            mesh=mesh,
            in_specs=in_specs + f_specs,
            out_specs=P(None, None),
        )
        return shmap(dev.indices, dev.values, dev.local_rows,
                     dev.block_to_tile, dev.tile_visited, dev.seg_starts,
                     dev.seg_rows, *factors)

    return fn


# -- epoch streaming: super-shard partial accumulation ------------------------

def shard_super_shard(part, stream_plan, k: int, mesh: Mesh, *, spill=None,
                      group_axes=("group",), sub_axis="sub") -> DeviceArrays:
    """Place super-shard ``k`` of an out-of-core mode on the mesh.

    Unlike :func:`shard_plan_mode`, ALL five arrays are per-device here —
    the blocking metadata (``block_to_tile``/``tile_visited``) differs per
    tile window, not just the payload. Shapes are the stream plan's static
    caps, so every super-shard of a mode hits the same compiled update.
    Devices whose window list is exhausted get empty ``(0, 0)`` windows:
    pure padding, exact no-ops under the tile mask.

    ``spill`` (a :class:`~repro.sparse.stream.WindowSpill`) short-circuits
    the chunk-scan materialization with the window's on-disk copy from an
    earlier sweep; non-empty windows built fresh are saved back. Empty pad
    windows are never spilled — rebuilding them is pure allocation.
    """
    g, r = part.n_groups, part.r
    sp = stream_plan
    nseg = part.tile + 1
    names = ("indices", "values", "local_rows", "block_to_tile",
             "tile_visited", "seg_starts", "seg_rows")
    shapes = {
        "indices": ((g, r, sp.nnz_cap, part.nmodes), 2),
        "values": ((g, r, sp.nnz_cap), 1),
        "local_rows": ((g, r, sp.nnz_cap), 1),
        "block_to_tile": ((g, r, sp.nblocks), 1),
        "tile_visited": ((g, r, sp.n_tiles), 1),
        "seg_starts": ((g, r, sp.nblocks, nseg + 1), 2),
        "seg_rows": ((g, r, sp.nblocks, nseg), 2),
    }
    shardings = {
        n: NamedSharding(mesh, P(group_axes, sub_axis, *([None] * tr)))
        for n, (_, tr) in shapes.items()}
    bufs: dict[str, list] = {n: [] for n in names}
    dev_map = shardings["values"].devices_indices_map(shapes["values"][0])
    for device, idx in dev_map.items():
        gg = idx[0].start or 0
        ss = idx[1].start or 0
        dev_id = gg * r + ss
        t0, t1 = sp.windows[dev_id][k]
        skey = (k, t0, t1, sp.nnz_cap, sp.nblocks)
        arrs = (spill.load(part.mode, dev_id, skey)
                if spill is not None else None)
        if arrs is None:
            arrs = part.super_shard_arrays(dev_id, t0, t1,
                                           nnz_cap=sp.nnz_cap,
                                           nblocks=sp.nblocks)
            if spill is not None and t1 > t0:
                spill.save(part.mode, dev_id, skey, arrs)
        # descriptors derive from the window's local_rows (arrs[2]) after
        # any spill load, so the spill format stays 5 arrays
        arrs = tuple(arrs) + block_segment_descriptors(
            arrs[2], tile=part.tile, block_p=part.block_p)
        for name, a in zip(names, arrs):
            bufs[name].append(jax.device_put(a[None, None], device))
        del arrs  # host copy freed before the next device streams
    return DeviceArrays(**{
        n: jax.make_array_from_single_device_arrays(
            shapes[n][0], shardings[n], bufs[n])
        for n in names})


def zero_partials(part, mesh: Mesh, rank: int, *, group_axes=("group",),
                  sub_axis="sub") -> jax.Array:
    """Zero per-device MTTKRP accumulator, (G, r, rows_max, R) sharded one
    block per device — the running sum super-shard partials fold into."""
    sh = NamedSharding(mesh, P(group_axes, sub_axis, None, None))
    return jax.device_put(
        jnp.zeros((part.n_groups, part.r, part.rows_max, rank), jnp.float32),
        sh)


def make_partial_mttkrp_fn(
    part,
    mesh: Mesh,
    *,
    group_axes: tuple[str, ...] = ("group",),
    sub_axis: str = "sub",
    use_kernel: bool = True,
    variant: str | None = None,
    num_buffers: int = 2,
    interpret: bool | None = None,
):
    """Jit-able ``fn(acc, dev, factors) -> acc`` folding one super-shard's
    local EC into the per-device accumulator — no merge, no gather.

    Because super-shards split at tile boundaries, each output row is
    produced by exactly ONE super-shard's EC call, with unchanged block and
    slot order; all other super-shards contribute an exact float zero
    there. Accumulating into a zero-initialized ``acc`` therefore yields
    the resident single-call partial bit-for-bit, and the downstream
    merge/gather (:func:`make_streaming_finish_fn`) is byte-identical to
    the resident path's.
    """
    meta = dict(mode=part.mode, rows_max=part.rows_max, tile=part.tile,
                block_p=part.block_p)

    def local_fn(acc, indices, values, local_rows, block_to_tile,
                 tile_visited, seg_starts, seg_rows, *factors):
        acc = acc.reshape(acc.shape[-2:])
        indices = indices.reshape(indices.shape[-2:])
        values = values.reshape(values.shape[-1])
        local_rows = local_rows.reshape(local_rows.shape[-1])
        block_to_tile = block_to_tile.reshape(block_to_tile.shape[-1])
        tile_visited = tile_visited.reshape(tile_visited.shape[-1])
        seg_starts = seg_starts.reshape(seg_starts.shape[-2:])
        seg_rows = seg_rows.reshape(seg_rows.shape[-2:])
        with obs_profiler.device_scope("ec_local"):
            partial = _local_ec(meta, indices, values, local_rows,
                                block_to_tile, tile_visited, seg_starts,
                                seg_rows, list(factors),
                                use_kernel=use_kernel, variant=variant,
                                num_buffers=num_buffers, interpret=interpret)
        return (acc + partial)[None, None]

    acc_spec = P(group_axes, sub_axis, None, None)
    arr_specs = (
        P(group_axes, sub_axis, None, None),
        P(group_axes, sub_axis, None),
        P(group_axes, sub_axis, None),
        P(group_axes, sub_axis, None),
        P(group_axes, sub_axis, None),
        P(group_axes, sub_axis, None, None),
        P(group_axes, sub_axis, None, None),
    )

    def fn(acc: jax.Array, dev: DeviceArrays,
           factors: Sequence[jax.Array]) -> jax.Array:
        f_specs = tuple(P(None, None) for _ in factors)
        shmap = shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(acc_spec,) + arr_specs + f_specs,
            out_specs=acc_spec,
        )
        return shmap(acc, dev.indices, dev.values, dev.local_rows,
                     dev.block_to_tile, dev.tile_visited, dev.seg_starts,
                     dev.seg_rows, *factors)

    return fn


def make_streaming_finish_fn(
    part,
    mesh: Mesh,
    *,
    group_axes: tuple[str, ...] = ("group",),
    sub_axis: str = "sub",
    ring: bool | None = None,
    exchange_spec: comm.ExchangeSpec | None = None,
):
    """Jit-able ``fn(acc) -> (padded_rows, R)``: the merge (intra-group
    reduce-scatter for r>1) + exchange (all-gather and packing copy) of
    :func:`make_mttkrp_fn`, run ONCE on the accumulated super-shard
    partials. Same collectives, same schedule, same wire dtype as the
    resident path."""
    all_axes = tuple(group_axes) + (sub_axis,)
    if exchange_spec is None:
        exchange_spec = comm.ExchangeSpec(
            variant=comm.resolve_variant(None, ring))

    def local_fn(acc):
        acc = acc.reshape(acc.shape[-2:])
        with obs_profiler.device_scope("merge"):
            merged = comm.merge_partials(
                acc, sub_axis if part.r > 1 else None,
                **exchange_spec.merge_kwargs())
        return _exchange(merged, part, all_axes, exchange_spec)

    acc_spec = P(group_axes, sub_axis, None, None)

    def fn(acc: jax.Array) -> jax.Array:
        shmap = shard_map(local_fn, mesh=mesh, in_specs=(acc_spec,),
                          out_specs=P(None, None))
        return shmap(acc)

    return fn


def distributed_mttkrp(plan: CPPlan, mode: int, mesh: Mesh,
                       dev_arrays: DeviceArrays, factors: Sequence[jax.Array],
                       **kw) -> jax.Array:
    """Convenience one-shot wrapper (un-jitted)."""
    fn = make_mttkrp_fn(plan.modes[mode], mesh, **kw)
    return fn(dev_arrays, factors)
