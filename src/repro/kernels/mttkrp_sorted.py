"""Third-generation EC kernel: row-sorted segments, no one-hot scatter.

``ec_fused`` (mttkrp_fused.py) removed the ``(nnz, R)`` gathered
intermediate but still commits every block's partial output through a
``tile × block_p`` one-hot matmul — ``2·block_p·tile·R`` FLOPs per block of
pure scatter overhead that also rewrites the whole output tile once per
block. ``ec_sorted`` removes that too, following the segmented-reduction
design of Nisa et al. (arXiv 1904.03329) and the FLYCOO per-mode sorted
copy (arXiv 2405.08470):

  * the device shard is row-sorted (``layout="sorted"`` in
    core/partition.py): each block's ``local_rows`` decompose into at most
    ``tile + 1`` runs of equal output row, described by per-block segment
    descriptors (``seg_starts``/``seg_rows``, see
    ``core.partition.block_segment_descriptors``) that the kernel DMAs into
    SMEM one block at a time, together with the block's values,
  * factor rows stream exactly as in ``ec_fused`` (``RowGather``) —
    HBM-resident lane-padded factors, an HBM index slab staged per block
    into SMEM, a rotating ring of ``num_buffers`` VMEM slots filled by async
    row DMAs, one aggregated semaphore wait per slot,
  * each segment accumulates in a ``(1, R)`` register/VMEM accumulator and
    read-modify-writes its output row once — the row's current partial is
    loaded, the segment's elementwise products ``(val · A[i0]) · B[i1] ...``
    are added in slot order, and the row is stored back. No one-hot matmul,
    no per-block tile rewrite, and the ``row_in_tile`` array is never
    shipped to the kernel at all.

Accumulation order is *slot order*, exactly the order XLA's scatter-add
(`segment_sum`) uses, and the elementwise product is formed in the
oracle's order, so the result is bit-identical to the slot-order oracle
(``kernels/ref.py:mttkrp_local_ref``) — on both layouts (on the
legacy blocked layout a pad run may revisit an earlier row, but pads
contribute exact ``0.0`` adds in the same slot positions).

Kernel contract (core/partition.py): fixed-size ``block_p`` blocks, every
block updates rows inside one output tile, blocks of a tile consecutive,
padding entries have ``values == 0`` and in-bounds index/row entries.
Operand layouts and the chunked launch follow ``tpu_layout``.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu_layout as tl
from repro.kernels.mttkrp_fused import (RowGather, check_num_buffers,
                                        gather_scratch)

__all__ = ["ec_sorted"]


def _sorted_kernel(nin: int, n: int, nseg: int, base, b2t, vals_hbm,
                   seg_hbm, idx_hbm, *refs):
    """refs layout (after the scalar-prefetched ``base``/``b2t`` and the HBM
    value slab, segment records and index slab):

      fac_ref_0 .. fac_ref_{nin-1},  lane-padded factors, HBM-resident
      acc_ref,                       running output (aliased to out_ref)
      out_ref,
      vals_smem, seg_smem, e_buf, idx_smem, row_buf, row_sems, stage_sem
    """
    acc_ref, out_ref = refs[nin], refs[nin + 1]
    vals_smem, seg_smem, e_buf = refs[nin + 2:nin + 5]
    gather = RowGather(idx_hbm, refs[:nin], *refs[nin + 5:])
    i = pl.program_id(0)
    slot = gather.pipeline(base[0], i, n)

    # This block's values and segment record, staged for scalar reads.
    g = base[0] + i
    srows = vals_smem.shape[0]
    flat0 = g * gather.block_p
    off = jax.lax.rem(flat0, tl.LANES)
    for src, dst in ((vals_hbm.at[pl.ds(flat0 // tl.LANES, srows), :],
                      vals_smem),
                     (seg_hbm.at[pl.ds(g, 1), :], seg_smem)):
        stage = pltpu.make_async_copy(src, dst, gather.stage_sem)
        stage.start()
        stage.wait()

    @pl.when(tl.first_visit(b2t, i))
    def _init():
        out_ref[...] = acc_ref[...]

    # Elementwise products in ref's order, (val * A[i0]) * B[i1] ..., staged
    # in VMEM so the segment adds below read finished rows (a product formed
    # inside the add loop could be contracted into a fused multiply-add and
    # round differently from ref).
    def product(p, _):
        q = off + p
        e = vals_smem[q // tl.LANES, jax.lax.rem(q, tl.LANES)] \
            * gather.row_buf[slot, 0, pl.ds(p, 1), :]
        for w in range(1, nin):
            e = e * gather.row_buf[slot, w, pl.ds(p, 1), :]
        e_buf[pl.ds(p, 1), :] = e
        return 0

    jax.lax.fori_loop(0, gather.block_p, product, 0)

    # Segmented reduction: each run of equal output row accumulates in a
    # (1, R) accumulator, added in slot order (== segment_sum's order), and
    # its row is read-modify-written exactly once per segment.
    for s in range(nseg):
        start = seg_smem[0, s]
        end = seg_smem[0, s + 1]
        row = seg_smem[0, nseg + 1 + s]

        @pl.when(end > start)
        def _segment(start=start, end=end, row=row):
            out_ref[pl.ds(row, 1), :] = jax.lax.fori_loop(
                start, end, lambda p, acc: acc + e_buf[pl.ds(p, 1), :],
                out_ref[pl.ds(row, 1), :])


def ec_sorted(
    values: jax.Array,                 # (nnz,)  nnz = nblocks * block_p
    seg_starts: jax.Array,             # (nblocks, S+1) int32, S = tile+1
    seg_rows: jax.Array,               # (nblocks, S) int32 in [0, tile)
    block_to_tile: jax.Array,          # (nblocks,) int32, scalar-prefetched
    input_indices: jax.Array,          # (nin, nnz) int32 rows into factors[w]
    factors: Sequence[jax.Array],      # nin arrays (padded_w, R), HBM-resident
    *,
    num_rows: int,                     # rows_max (multiple of tile)
    tile: int,
    block_p: int,
    num_buffers: int = 2,
    interpret: bool = False,
) -> jax.Array:
    """Segmented-reduction EC on the row-sorted block layout.

    Returns (num_rows, R) f32, bit-identical to the slot-order oracle
    (``kernels/ref.py``).
    ``input_indices[j]`` indexes ``factors[j]`` (the output mode is
    compacted away by the caller, see ops.py); descriptors come from
    ``core.partition.block_segment_descriptors``.
    """
    nnz = values.shape[0]
    assert nnz % block_p == 0, (nnz, block_p)
    assert num_rows % tile == 0, (num_rows, tile)
    check_num_buffers(num_buffers)
    tl.check_block_p(block_p)
    nblocks = nnz // block_p
    nin = len(factors)
    assert input_indices.shape == (nin, nnz), (input_indices.shape, nnz, nin)
    nseg = seg_rows.shape[-1]
    assert seg_starts.shape == (nblocks, nseg + 1), (seg_starts.shape, nseg)
    assert seg_rows.shape == (nblocks, nseg), (seg_rows.shape, nblocks)
    r = factors[0].shape[-1]
    facs = [tl.pad_lanes(f.astype(jnp.float32)) for f in factors]
    rp = facs[0].shape[-1]
    vals = tl.lane_slab(values.astype(jnp.float32))
    # one lane-aligned record per block: [starts (S+1) | rows (S) | pad]
    seg = tl.pad_lanes(jnp.concatenate(
        [seg_starts.astype(jnp.int32), seg_rows.astype(jnp.int32)], axis=1))
    idx = tl.lane_slab(input_indices.astype(jnp.int32))
    srows = tl.slab_rows(block_p)

    def launch(n, base, b2t, out):
        tile_spec = pl.BlockSpec((tile, rp), lambda i, base, b2t: (b2t[i], 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (3 + nin)
            + [tile_spec],
            out_specs=tile_spec,
            scratch_shapes=[
                pltpu.SMEM((srows, tl.LANES), jnp.float32),
                pltpu.SMEM((1, seg.shape[1]), jnp.int32),
                pltpu.VMEM((block_p, rp), jnp.float32),
            ] + gather_scratch(nin, block_p, rp, num_buffers),
        )
        return pl.pallas_call(
            functools.partial(_sorted_kernel, nin, n, nseg),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((num_rows, rp), jnp.float32),
            input_output_aliases={5 + nin: 0},
            interpret=interpret,
            name=f"amped_ec_sorted_nin{nin}_nb{num_buffers}",
        )(base, b2t, vals, seg, idx, *facs, out)

    out = tl.chunked(launch, nblocks=nblocks, block_to_tile=block_to_tile,
                     out=jnp.zeros((num_rows, rp), jnp.float32))
    return out[:, :r]
