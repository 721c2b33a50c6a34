"""Second-generation fused EC kernel: in-kernel factor gather with
double-buffered HBM streaming.

``ec_blocked`` (mttkrp_pallas.py) needs the input factor rows gathered by XLA
*before* the kernel, materializing ``N-1`` arrays of shape ``(nnz, R)`` in
HBM per MTTKRP call — at billion-scale nnz that intermediate dwarfs the
nonzero payload and makes the EC gather-bandwidth-bound. ``ec_fused``
eliminates it, following the paper's Alg. 2 where each R×P threadblock loads
its own factor rows straight from global memory:

  * the factor matrices stay resident in HBM (``pl.ANY`` memory space) —
    they are never tiled into VMEM by the pipeline; their lane dimension is
    zero-padded to a multiple of 128 (a DMA'd row must span whole lane
    tiles) and the output is sliced back to ``R`` columns,
  * the input-mode indices stay in HBM too, as one lane-dense slab per input
    mode; to gather block ``g`` the kernel DMAs ``g``'s index rows into SMEM
    (scalar addressing) and issues one async HBM→VMEM copy per (nonzero,
    input mode) row into a rotating ring of ``num_buffers`` VMEM slots
    (``pltpu.make_async_copy``). Block ``i`` starts the gather of block
    ``i + num_buffers - 1``, so the DMA of the next blocks overlaps the VPU
    Hadamard product and MXU one-hot accumulation of block ``i``,
  * a single aggregated semaphore wait per slot (a descriptor covering the
    whole ``(nin, block_p, R)`` slot) retires all of a block's row copies.

No ``(nnz, R)`` gathered intermediate ever exists: per MTTKRP call the factor
rows are read from HBM exactly once, streamed through VMEM, and consumed in
place.

Kernel contract (identical to ``ec_blocked``, enforced by core/partition.py):
blocks are fixed-size ``block_p`` runs of nonzeros, every block updates rows
inside one output tile, blocks of a tile are consecutive, padding entries
have ``values == 0`` (their index entries point at row 0, an always-valid
row, so the prefetched DMA is a harmless read). Operand layouts and the
chunked launch follow ``tpu_layout``.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu_layout as tl
from repro.kernels.mttkrp_pallas import onehot_commit

__all__ = ["ec_fused", "RowGather", "gather_scratch", "check_num_buffers"]

MAX_NUM_BUFFERS = 4


def check_num_buffers(num_buffers: int) -> None:
    if not (2 <= num_buffers <= MAX_NUM_BUFFERS):
        raise ValueError(
            f"num_buffers must be in [2, {MAX_NUM_BUFFERS}], got {num_buffers}")


class RowGather:
    """The double-buffered in-kernel row gather ``ec_fused`` and
    ``ec_sorted`` share. ``idx_hbm`` is the ``(nin, n / 128, 128)`` index
    slab, ``fac_refs`` the lane-padded HBM factors; the scratch refs are
    those :func:`gather_scratch` declares."""

    def __init__(self, idx_hbm, fac_refs, idx_smem, row_buf, row_sems,
                 stage_sem):
        self.idx_hbm, self.fac_refs = idx_hbm, fac_refs
        self.idx_smem, self.row_buf = idx_smem, row_buf
        self.row_sems, self.stage_sem = row_sems, stage_sem
        self.num_buffers, self.nin, self.block_p = row_buf.shape[:3]
        self.srows = idx_smem.shape[0] // self.nin

    def start(self, g, slot):
        """Stage block ``g``'s indices into SMEM, then launch its row DMAs
        into ``row_buf[slot]``."""
        flat0 = g * self.block_p
        off = jax.lax.rem(flat0, tl.LANES)
        for w in range(self.nin):
            stage = pltpu.make_async_copy(
                self.idx_hbm.at[w, pl.ds(flat0 // tl.LANES, self.srows), :],
                self.idx_smem.at[pl.ds(w * self.srows, self.srows), :],
                self.stage_sem)
            stage.start()
            stage.wait()

        def body(p, _):
            q = off + p
            for w in range(self.nin):
                row = self.idx_smem[w * self.srows + q // tl.LANES,
                                    jax.lax.rem(q, tl.LANES)]
                pltpu.make_async_copy(self.fac_refs[w].at[row],
                                      self.row_buf.at[slot, w, p],
                                      self.row_sems.at[slot]).start()
            return 0

        jax.lax.fori_loop(0, self.block_p, body, 0)

    def pipeline(self, base, i, n: int):
        """Keep ``num_buffers - 1`` blocks in flight over this launch's
        ``n`` blocks ``base .. base + n - 1``; wait for block ``base + i``
        and return its ring slot."""
        lookahead = self.num_buffers - 1

        @pl.when(i == 0)
        def _prologue():
            for k in range(min(lookahead, n)):
                self.start(base + k, k % self.num_buffers)

        @pl.when(i + lookahead < n)
        def _prefetch():
            self.start(base + i + lookahead,
                       jax.lax.rem(i + lookahead, self.num_buffers))

        slot = jax.lax.rem(i, self.num_buffers)
        # Aggregated wait: retire all nin*block_p row copies of this slot.
        pltpu.make_async_copy(self.row_buf.at[slot], self.row_buf.at[slot],
                              self.row_sems.at[slot]).wait()
        return slot


def gather_scratch(nin: int, block_p: int, rp: int, num_buffers: int) -> list:
    """Scratch operands of :class:`RowGather`, in its argument order."""
    return [
        pltpu.SMEM((nin * tl.slab_rows(block_p), tl.LANES), jnp.int32),
        pltpu.VMEM((num_buffers, nin, block_p, rp), jnp.float32),
        pltpu.SemaphoreType.DMA((num_buffers,)),
        pltpu.SemaphoreType.DMA,
    ]


def _fused_kernel(nin: int, n: int, base, b2t, vals_ref, seg_ref, idx_hbm,
                  *refs):
    """refs layout (after the scalar-prefetched ``base``/``b2t``, the value
    and row-in-tile windows and the HBM index slab):

      fac_ref_0 .. fac_ref_{nin-1},  lane-padded factors, HBM-resident
      acc_ref,                       running output (aliased to out_ref)
      out_ref,
      idx_smem, row_buf, row_sems, stage_sem
    """
    acc_ref, out_ref = refs[nin], refs[nin + 1]
    gather = RowGather(idx_hbm, refs[:nin], *refs[nin + 2:])
    i = pl.program_id(0)
    slot = gather.pipeline(base[0], i, n)

    @pl.when(tl.first_visit(b2t, i))
    def _init():
        out_ref[...] = acc_ref[...]

    e = gather.row_buf[slot, 0]
    for w in range(1, nin):
        e = e * gather.row_buf[slot, w]
    out_ref[...] += onehot_commit(tl.window_row(vals_ref, base, i),
                                  tl.window_row(seg_ref, base, i), e,
                                  out_ref.shape[0])


def ec_fused(
    values: jax.Array,                 # (nnz,)  nnz = nblocks * block_p
    row_in_tile: jax.Array,            # (nnz,) int32 in [0, tile)
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
    """Fused EC: gather + Hadamard + accumulate, no gathered intermediate.

    Returns (num_rows, R) f32 (tiles no block visits are 0).
    ``input_indices[j]`` indexes ``factors[j]`` (the output mode is already
    compacted away by the caller, see ops.py).
    """
    nnz = values.shape[0]
    assert nnz % block_p == 0, (nnz, block_p)
    assert num_rows % tile == 0, (num_rows, tile)
    check_num_buffers(num_buffers)
    tl.check_block_p(block_p)
    nblocks = nnz // block_p
    nin = len(factors)
    assert input_indices.shape == (nin, nnz), (input_indices.shape, nnz, nin)
    r = factors[0].shape[-1]
    facs = [tl.pad_lanes(f.astype(jnp.float32)) for f in factors]
    rp = facs[0].shape[-1]
    vals = values.reshape(nblocks, block_p)
    seg = row_in_tile.astype(jnp.int32).reshape(nblocks, block_p)
    idx = tl.lane_slab(input_indices.astype(jnp.int32))

    def launch(n, base, b2t, out):
        window = tl.window_spec(nblocks, block_p)
        tile_spec = pl.BlockSpec((tile, rp), lambda i, base, b2t: (b2t[i], 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            in_specs=[window, window]
            + [pl.BlockSpec(memory_space=pl.ANY)] * (1 + nin) + [tile_spec],
            out_specs=tile_spec,
            scratch_shapes=gather_scratch(nin, block_p, rp, num_buffers),
        )
        return pl.pallas_call(
            functools.partial(_fused_kernel, nin, n),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((num_rows, rp), jnp.float32),
            input_output_aliases={5 + nin: 0},
            interpret=interpret,
            name=f"amped_ec_fused_nin{nin}_nb{num_buffers}",
        )(base, b2t, vals, seg, idx, *facs, out)

    out = tl.chunked(launch, nblocks=nblocks, block_to_tile=block_to_tile,
                     out=jnp.zeros((num_rows, rp), jnp.float32))
    return out[:, :r]
