"""Pallas TPU kernel for the MTTKRP elementwise computation (EC).

TPU adaptation of the paper's R×P threadblock (Alg. 2): the atomic scatter
into the output factor matrix becomes a **one-hot matmul on the MXU**.

Preprocessing (core/partition.py) guarantees:
  * nonzeros are blocked into fixed-size blocks of ``P`` (the paper's P),
  * all nonzeros of a block update rows inside ONE output row tile of height
    ``TILE`` (``block_to_tile`` maps block → tile; blocks for a tile are
    consecutive),
  * padding entries have value 0 (exact no-ops).

Grid = (num_blocks,). The output BlockSpec's index_map reads the
scalar-prefetched ``block_to_tile`` array, so consecutive blocks hitting the
same tile keep the accumulator resident in VMEM (Pallas revisiting); when
the map changes the tile is loaded from the running output, which starts at
zero. Per block the kernel computes

    E = val ⊙ A[i0,:] ⊙ B[i1,:] ⊙ ...      (P, R)   on the VPU
    out_tile += onehot(row_in_tile)ᵀ @ E    (TILE,R)  on the MXU

which is the paper's EC with zero write conflicts — the same race-freedom
the output-mode sharding buys across devices, pushed down to lane level.
(The kernel folds ``val`` into the one-hot operand instead of E; operand
layouts and the chunked launch follow ``tpu_layout``.)

Input factor rows are gathered by XLA ahead of the kernel (``ops.py``),
materializing (nnz, R) intermediates in HBM; ``mttkrp_fused.ec_fused`` is the
follow-up that performs the gather in-kernel via double-buffered async HBM
copies. Variant selection lives in ``ops.KERNEL_VARIANTS``.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu_layout as tl

__all__ = ["ec_blocked", "onehot_commit"]


def onehot_commit(vals_row, seg_row, e, tile: int) -> jax.Array:
    """One block's contribution to its ``(tile, R)`` output tile:
    ``(onehot(row_in_tile) * val) @ E`` on the MXU, with ``vals_row`` and
    ``seg_row`` the block's ``(1, P)`` values and rows-in-tile and ``e`` its
    ``(P, R)`` product of input factor rows."""
    p = seg_row.shape[-1]
    onehot = seg_row == jax.lax.broadcasted_iota(jnp.int32, (tile, p), 0)
    lhs = jnp.where(onehot, vals_row.astype(jnp.float32), 0.0)
    return jnp.dot(lhs, e, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _ec_kernel(nin: int, base, b2t, vals_ref, seg_ref, *refs):
    # refs: rows_ref_0..rows_ref_{nin-1}, acc_ref (aliased to out), out_ref
    rows_refs = refs[:nin]
    acc_ref, out_ref = refs[nin], refs[nin + 1]
    i = pl.program_id(0)

    @pl.when(tl.first_visit(b2t, i))
    def _init():
        out_ref[...] = acc_ref[...]

    e = rows_refs[0][...].astype(jnp.float32)
    for rr in rows_refs[1:]:
        e = e * rr[...].astype(jnp.float32)
    out_ref[...] += onehot_commit(tl.window_row(vals_ref, base, i),
                                  tl.window_row(seg_ref, base, i), e,
                                  out_ref.shape[0])


def ec_blocked(
    values: jax.Array,                 # (nnz,)  nnz = nblocks * block_p
    row_in_tile: jax.Array,            # (nnz,) int32 in [0, tile)
    block_to_tile: jax.Array,          # (nblocks,) int32, scalar-prefetched
    gathered_rows: Sequence[jax.Array],  # each (nnz, R)
    *,
    num_rows: int,                     # rows_max (multiple of tile)
    tile: int,
    block_p: int,
    interpret: bool = False,
) -> jax.Array:
    """Blocked EC: returns (num_rows, R) f32 (tiles no block visits are 0)."""
    nnz = values.shape[0]
    assert nnz % block_p == 0, (nnz, block_p)
    assert num_rows % tile == 0, (num_rows, tile)
    nblocks = nnz // block_p
    r = gathered_rows[0].shape[-1]
    nin = len(gathered_rows)
    vals = values.reshape(nblocks, block_p)
    seg = row_in_tile.astype(jnp.int32).reshape(nblocks, block_p)

    def launch(n, base, b2t, out):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            in_specs=[tl.window_spec(nblocks, block_p)] * 2 + [
                pl.BlockSpec((block_p, r), lambda i, base, b2t: (base[0] + i, 0))
                for _ in range(nin)
            ] + [pl.BlockSpec((tile, r), lambda i, base, b2t: (b2t[i], 0))],
            out_specs=pl.BlockSpec((tile, r), lambda i, base, b2t: (b2t[i], 0)),
        )
        return pl.pallas_call(
            functools.partial(_ec_kernel, nin),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((num_rows, r), jnp.float32),
            input_output_aliases={4 + nin: 0},
            interpret=interpret,
            name=f"amped_ec_nin{nin}",
        )(base, b2t, vals, seg, *gathered_rows, out)

    return tl.chunked(launch, nblocks=nblocks, block_to_tile=block_to_tile,
                      out=jnp.zeros((num_rows, r), jnp.float32))
