"""Kernel-variant dispatch for the MTTKRP EC.

``mttkrp_local`` is the single-device EC used inside shard_map by
core/mttkrp.py. Four interchangeable variants (see EXPERIMENTS.md §Perf):

  ``ref``      pure XLA, no Pallas: over chunks of blocks, gather +
               product, each block summed into its tile, then a
               scatter-add over the chunk's blocks (the slot-order oracle
               is kernels/ref.py, which no variant runs)
  ``blocked``  XLA pre-gather of (nnz, R) input rows + Pallas one-hot-matmul
               EC kernel (mttkrp_pallas.ec_blocked)
  ``fused``    in-kernel factor gather with double-buffered HBM streaming —
               no gathered intermediate (mttkrp_fused.ec_fused)
  ``sorted``   fused's in-kernel gather + segmented reduction over the
               row-sorted block layout — no one-hot scatter, each output
               row written once per segment; bit-identical to the oracle
               (mttkrp_sorted.ec_sorted; needs seg_starts/seg_rows
               descriptors, see core.partition.block_segment_descriptors)

Selection precedence: explicit ``variant=`` argument > ``AMPED_EC_VARIANT``
environment variable > default (``blocked``). ``use_kernel=False`` keeps its
historical meaning and forces ``ref`` unless a variant is named explicitly.
Off-TPU backends run the Pallas variants in ``interpret=True`` mode.
"""
from __future__ import annotations

import os
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.kernels import tpu_layout as tl
from repro.kernels.mttkrp_fused import ec_fused
from repro.kernels.mttkrp_pallas import ec_blocked
from repro.kernels.mttkrp_sorted import ec_sorted

__all__ = ["mttkrp_local", "default_interpret", "resolve_variant",
           "kernel_kwargs_from_config", "variant_vmem_bytes",
           "ref_input_bytes", "ref_takes_loop",
           "KERNEL_VARIANTS", "ENV_VARIANT", "DEFAULT_VARIANT",
           "DEFAULT_NUM_BUFFERS"]

ENV_VARIANT = "AMPED_EC_VARIANT"
DEFAULT_VARIANT = "blocked"
DEFAULT_NUM_BUFFERS = 2


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def resolve_variant(variant: str | None = None, use_kernel: bool = True) -> str:
    """Resolve the EC kernel variant name (see module docstring)."""
    if variant is None:
        if not use_kernel:
            return "ref"
        variant = os.environ.get(ENV_VARIANT, DEFAULT_VARIANT)
    if variant not in KERNEL_VARIANTS:
        raise ValueError(
            f"unknown EC variant {variant!r}; expected one of "
            f"{sorted(KERNEL_VARIANTS)}")
    return variant


def kernel_kwargs_from_config(cfg, *, nmodes: int | None = None,
                              rank: int | None = None) -> dict:
    """Resolve a :class:`repro.api.KernelConfig`-shaped object (duck-typed:
    ``use_kernel``, ``variant``, ``num_buffers``, ``autotune`` attributes)
    into the kwargs ``make_mttkrp_fn`` / ``mttkrp_local`` take. This is the
    single point where config-level kernel selection becomes concrete —
    including the DMA ring depth: explicit ``num_buffers`` > autotuned
    winner (when ``cfg.autotune`` and the problem key ``(nmodes, rank)`` is
    given; memoized, so repeated resolution is free) > DEFAULT_NUM_BUFFERS."""
    variant = resolve_variant(getattr(cfg, "variant", None),
                              getattr(cfg, "use_kernel", True))
    num_buffers = getattr(cfg, "num_buffers", None)
    if num_buffers is None and getattr(cfg, "autotune", False) and \
            variant != "ref" and nmodes is not None and rank is not None:
        from repro.kernels import autotune
        num_buffers = autotune.autotune_ec(nmodes, rank,
                                           variant=variant).num_buffers
    return dict(
        use_kernel=variant != "ref",
        variant=variant,
        num_buffers=DEFAULT_NUM_BUFFERS if num_buffers is None
        else int(num_buffers),
    )


def variant_vmem_bytes(variant: str, *, tile: int, block_p: int, rank: int,
                       nin: int, num_buffers: int = DEFAULT_NUM_BUFFERS,
                       itemsize: int = 4) -> int:
    """Model of one grid step's VMEM working set per EC variant — the
    quantity the autotuner's candidate grid implicitly bounds and rule
    AP-P006 (repro.analysis.plan_rules) checks against the budget.

    Per block the kernels hold: the (block_p,) values and row-in-tile
    slabs, the per-input factor rows ((block_p, rank) per input — times
    the DMA ring depth for the fused/sorted in-kernel gather), the
    (tile, rank) output tile accumulator, and — for ``sorted`` — the
    (S+1,)+(S,) segment descriptors with S = tile + 1. ``ref`` runs no
    Pallas kernel and models as 0."""
    if variant == "ref":
        return 0
    slabs = 2 * block_p * itemsize            # values + row_in_tile
    out_tile = tile * rank * itemsize
    if variant == "blocked":
        # pre-gathered (block_p, rank) input slabs, one per input mode
        gathered = nin * block_p * rank * itemsize
        return slabs + gathered + out_tile
    # fused/sorted: (block_p, nin) index slab + ring of gathered rows
    idx_slab = block_p * nin * itemsize
    ring = num_buffers * nin * block_p * rank * itemsize
    seg = (2 * tile + 3) * itemsize if variant == "sorted" else 0
    return slabs + idx_slab + ring + out_tile + seg


def _mask_unvisited(out: jax.Array, tile_mask: jax.Array | None,
                    tile: int) -> jax.Array:
    if tile_mask is None:
        return out
    # The kernels accumulate into a zeroed output, so unvisited tiles are
    # already 0; the select keeps that a stated contract (select, don't
    # multiply: NaN * 0 == NaN).
    mask = jnp.repeat(tile_mask > 0, tile)[:, None]
    return jnp.where(mask, out, 0.0)


# The ``ref`` EC works through the shard in chunks of blocks, so the
# gathered factor rows (lane-padded to 128 floats) never exist for the
# whole shard at once. Where the input factors fit in VMEM together (128
# MiB on a v5e, with room left for the chunk's own rows), the chunks run in
# a loop of REF_LOOP_BLOCKS blocks and the compiler keeps the factors and
# each chunk's rows there. Otherwise a loop would leave a factor in HBM and
# gather from it row by row, so the chunks are laid out one after another
# in the program, each gathering at most REF_CHUNK_BYTES of padded rows,
# and the compiler stages each factor into VMEM for its gather in turn.
REF_VMEM_FACTOR_BYTES = 100 << 20
REF_LOOP_BLOCKS = 128
REF_CHUNK_BYTES = 512 << 20


def ref_input_bytes(input_rows: Sequence[int], rank: int) -> int:
    """Bytes of the ``ref`` EC's input factors as it weighs them against
    ``REF_VMEM_FACTOR_BYTES``: their rows, each lane-padded to 128 floats."""
    return sum(int(n) for n in input_rows) * tl.round_up(rank, tl.LANES) * 4


def ref_takes_loop(input_rows: Sequence[int], rank: int) -> bool:
    """Whether the ``ref`` EC runs its chunks in the VMEM loop, for input
    factors of ``input_rows`` rows at ``rank``."""
    return ref_input_bytes(input_rows, rank) <= REF_VMEM_FACTOR_BYTES


def _ref_tiles(indices, values, local_rows, factors, mode, tile, block_p):
    """The slots' products summed within each block into the block's tile:
    ``(nblocks, tile, R)``."""
    nblocks = values.shape[0] // block_p
    e = values.astype(jnp.float32)[:, None]
    for w in range(len(factors)):
        if w != mode:
            e = e * factors[w][indices[:, w]].astype(jnp.float32)
    rank = e.shape[-1]
    row_in_tile = (local_rows % tile).reshape(nblocks, block_p, 1)
    onehot = row_in_tile == jax.lax.broadcasted_iota(
        local_rows.dtype, (1, 1, tile), 2)
    return jnp.einsum("bpt,bpr->btr", onehot.astype(jnp.float32),
                      e.reshape(nblocks, block_p, rank),
                      precision=jax.lax.Precision.HIGHEST)


def _run_ref(indices, values, local_rows, block_to_tile, factors, *,
             mode, num_rows, tile, block_p, interpret, tile_mask,
             num_buffers, seg_starts, seg_rows):
    """Two-level reduction over the block layout, in XLA.

    The slots' products ``values * prod_{w != mode} F_w[indices[:, w]]``
    are summed within each ``block_p``-slot block into the block's
    ``tile``-row tile (no block straddles a tile), and the blocks' tiles
    are then scatter-added into the output by ``block_to_tile``: one
    update per block, not per slot. The in-block sum contracts with a
    one-hot of each slot's row in its tile, built from an iota inside the
    fusion, at HIGHEST precision: the one-hot is exact in bfloat16 and the
    float32 products are split into bfloat16 parts that keep every bit.
    The shard is processed in chunks of blocks (see REF_LOOP_BLOCKS), each
    scatter-adding into the running output in block order, so the result
    does not depend on the chunking. Pad slots hold value 0 and add exact
    zeros; tiles no block visits stay 0. The sums run in another order
    than the slot-order oracle (kernels/ref.py), so they agree with it to
    float32 rounding, and bit for bit where every sum is exact."""
    del interpret, tile_mask, num_buffers, seg_starts, seg_rows
    rank = factors[0].shape[-1]
    row_bytes = tl.round_up(rank, tl.LANES) * 4
    inputs = [w for w in range(len(factors)) if w != mode]
    if ref_takes_loop([factors[w].shape[0] for w in inputs], rank):
        chunk, unroll = REF_LOOP_BLOCKS, False
    else:
        chunk = max(1, REF_CHUNK_BYTES // (len(inputs) * block_p * row_bytes))
        unroll = True

    def launch(n, base, b2t, out):
        s = base[0] * block_p
        cut = lambda x: jax.lax.dynamic_slice_in_dim(x, s, n * block_p)
        tiles = _ref_tiles(cut(indices), cut(values), cut(local_rows),
                           factors, mode, tile, block_p)
        return out.at[b2t].add(tiles)

    out = tl.chunked(launch, nblocks=block_to_tile.shape[0],
                     block_to_tile=block_to_tile, chunk=chunk, unroll=unroll,
                     out=jnp.zeros((num_rows // tile, tile, rank), jnp.float32))
    return out.reshape(num_rows, rank)


def _run_blocked(indices, values, local_rows, block_to_tile, factors, *,
                 mode, num_rows, tile, block_p, interpret, tile_mask,
                 num_buffers, seg_starts, seg_rows):
    del num_buffers, seg_starts, seg_rows
    gathered = [factors[w][indices[:, w]]
                for w in range(len(factors)) if w != mode]
    row_in_tile = (local_rows % tile).astype(jnp.int32)
    out = ec_blocked(
        values, row_in_tile, block_to_tile, gathered,
        num_rows=num_rows, tile=tile, block_p=block_p, interpret=interpret)
    return _mask_unvisited(out, tile_mask, tile)


def _run_fused(indices, values, local_rows, block_to_tile, factors, *,
               mode, num_rows, tile, block_p, interpret, tile_mask,
               num_buffers, seg_starts, seg_rows):
    del seg_starts, seg_rows
    # Compact the input-mode index columns into one (nin, nnz) array; the
    # factor matrices themselves stay in HBM (no (nnz, R) intermediate).
    in_modes = [w for w in range(len(factors)) if w != mode]
    input_indices = jnp.stack([indices[:, w] for w in in_modes])
    row_in_tile = (local_rows % tile).astype(jnp.int32)
    out = ec_fused(
        values, row_in_tile, block_to_tile, input_indices,
        [factors[w] for w in in_modes],
        num_rows=num_rows, tile=tile, block_p=block_p,
        num_buffers=num_buffers, interpret=interpret)
    return _mask_unvisited(out, tile_mask, tile)


def _run_sorted(indices, values, local_rows, block_to_tile, factors, *,
                mode, num_rows, tile, block_p, interpret, tile_mask,
                num_buffers, seg_starts, seg_rows):
    del local_rows  # descriptors replace the per-slot rows
    if seg_starts is None or seg_rows is None:
        raise ValueError(
            "variant='sorted' needs per-block segment descriptors; compute "
            "them with core.partition.block_segment_descriptors(local_rows, "
            "tile=..., block_p=...) and pass seg_starts=/seg_rows=")
    in_modes = [w for w in range(len(factors)) if w != mode]
    input_indices = jnp.stack([indices[:, w] for w in in_modes])
    out = ec_sorted(
        values, seg_starts, seg_rows, block_to_tile, input_indices,
        [factors[w] for w in in_modes],
        num_rows=num_rows, tile=tile, block_p=block_p,
        num_buffers=num_buffers, interpret=interpret)
    return _mask_unvisited(out, tile_mask, tile)


KERNEL_VARIANTS = {
    "ref": _run_ref,
    "blocked": _run_blocked,
    "fused": _run_fused,
    "sorted": _run_sorted,
}


def mttkrp_local(
    indices: jax.Array,        # (nnz, N) int32, padded layouts
    values: jax.Array,         # (nnz,)
    local_rows: jax.Array,     # (nnz,) int32 in [0, num_rows)
    block_to_tile: jax.Array,  # (nblocks,) int32
    factors: Sequence[jax.Array],
    *,
    mode: int,
    num_rows: int,
    tile: int,
    block_p: int,
    use_kernel: bool = True,
    variant: str | None = None,
    num_buffers: int = 2,
    interpret: bool | None = None,
    tile_mask: jax.Array | None = None,  # (num_rows/tile,) 1=visited
    seg_starts: jax.Array | None = None,  # (nblocks, S+1) int32 ("sorted")
    seg_rows: jax.Array | None = None,    # (nblocks, S) int32 ("sorted")
) -> jax.Array:
    """Local (per-device) EC over this device's shard. Returns (num_rows, R) f32."""
    variant = resolve_variant(variant, use_kernel)
    if interpret is None:
        interpret = default_interpret()
    return KERNEL_VARIANTS[variant](
        indices, values, local_rows, block_to_tile, factors,
        mode=mode, num_rows=num_rows, tile=tile, block_p=block_p,
        interpret=interpret, tile_mask=tile_mask, num_buffers=num_buffers,
        seg_starts=seg_starts, seg_rows=seg_rows)
