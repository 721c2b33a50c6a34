"""Operand layout and launch plumbing shared by the Pallas EC kernels.

The TPU kernel compiler (Mosaic) accepts only some shapes, and the kernels
are written around three of its rules:

* **Lanes.** A DMA'd slice must span whole 128-lane tiles. Per-row factor
  gathers therefore read factors padded with zero columns to a multiple of
  128 (:func:`pad_lanes`), and per-nonzero scalars that a kernel DMAs into
  SMEM travel as lane-dense ``(n / 128, 128)`` slabs (:func:`lane_slab`);
  a block of ``block_p`` nonzeros spans :func:`slab_rows` of them.
* **Sublanes.** A block's last two dimensions must be multiples of
  ``(8, 128)`` or the array's own. Per-nonzero vectors a kernel reads as
  vectors (values, row-in-tile) arrive as ``(nblocks, block_p)`` arrays in
  windows of :data:`WINDOW` blocks (:func:`window_spec`); the kernel picks
  its block's row with :func:`window_row`.
* **SMEM.** ``block_to_tile`` is scalar-prefetched because the output's
  index map reads it, and SMEM holds 1 MiB. One launch covers at most
  :data:`MAX_CHUNK_BLOCKS` blocks; :func:`chunked` runs the full chunks in a
  ``fori_loop`` and the remainder as one static tail launch, accumulating
  into one output buffer.

Chunked launches accumulate: every kernel takes the running output as an
aliased input and loads a tile from it on the tile's first block, so a tile
split across two chunks keeps its partial sum, and tiles no block visits
stay exactly zero.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["LANES", "WINDOW", "MAX_CHUNK_BLOCKS", "round_up", "pad_lanes",
           "check_block_p", "slab_rows", "lane_slab", "window_spec",
           "window_row", "first_visit", "chunked"]

LANES = 128
WINDOW = 8                  # f32/int32 sublane tile: blocks per vector window
MAX_CHUNK_BLOCKS = 1 << 16  # 256 KiB of int32 block map in SMEM per launch


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def pad_lanes(x: jax.Array) -> jax.Array:
    """Zero-pad the last (lane) dimension to a multiple of 128."""
    r = x.shape[-1]
    rp = round_up(r, LANES)
    if rp == r:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, rp - r)])


def check_block_p(block_p: int) -> None:
    """In-kernel DMAs move whole 128-lane rows of a lane slab, so a block
    must tile them: ``block_p`` divides 128 or is a multiple of it."""
    if LANES % block_p and block_p % LANES:
        raise ValueError(f"block_p={block_p} must divide {LANES} or be a "
                         f"multiple of it")


def slab_rows(block_p: int) -> int:
    """128-lane rows of a lane slab that one block's DMA moves."""
    return max(1, block_p // LANES)


def lane_slab(x: jax.Array) -> jax.Array:
    """``(..., n) -> (..., ceil(n / 128), 128)``, zero-padded at the end."""
    n = x.shape[-1]
    npad = round_up(n, LANES)
    if npad != n:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, npad - n)])
    return x.reshape(x.shape[:-1] + (npad // LANES, LANES))


def window_spec(nblocks: int, block_p: int) -> pl.BlockSpec:
    """BlockSpec of an ``(nblocks, block_p)`` per-nonzero array: the window
    of :data:`WINDOW` blocks holding grid step ``i``'s block ``base + i``
    (``base`` is the first scalar-prefetch operand)."""
    rows = min(WINDOW, nblocks)
    return pl.BlockSpec((rows, block_p),
                        lambda i, base, *_: ((base[0] + i) // rows, 0))


def window_row(ref, base, i) -> jax.Array:
    """Block ``base + i``'s ``(1, block_p)`` row of a :func:`window_spec`
    window."""
    rows = ref.shape[0]
    return ref[pl.ds(jax.lax.rem(base[0] + i, rows), 1), :]


def first_visit(b2t, i) -> jax.Array:
    """True on a tile's first block in this launch: load its running sum."""
    return jnp.logical_or(i == 0, b2t[jnp.maximum(i - 1, 0)] != b2t[i])


def chunked(launch: Callable, *, nblocks: int, block_to_tile: jax.Array,
            out: jax.Array, chunk: int | None = None,
            unroll: bool = False) -> jax.Array:
    """Run ``launch(n, base, b2t, out) -> out`` over consecutive chunks of at
    most ``chunk`` blocks (:data:`MAX_CHUNK_BLOCKS` by default).
    ``base`` is a ``(1,)`` int32 array holding the chunk's first block,
    ``b2t`` the chunk's slice of ``block_to_tile``; ``n`` is static. The full
    chunks run in a ``fori_loop``, or, with ``unroll``, one after another
    in the program with constant bases."""
    if nblocks < 1:
        raise ValueError("an EC launch needs at least one block")
    c = min(nblocks, MAX_CHUNK_BLOCKS if chunk is None else chunk)
    nfull, tail = divmod(nblocks, c)

    def body(k, out):
        start = k * c
        b2t = jax.lax.dynamic_slice_in_dim(block_to_tile, start, c)
        return launch(c, jnp.reshape(start, (1,)).astype(jnp.int32), b2t, out)

    if nfull == 1:
        out = launch(c, jnp.zeros((1,), jnp.int32), block_to_tile[:c], out)
    elif unroll:
        for k in range(nfull):
            out = launch(c, jnp.full((1,), k * c, jnp.int32),
                         block_to_tile[k * c:(k + 1) * c], out)
    else:
        out = jax.lax.fori_loop(0, nfull, body, out)
    if tail:
        start = nfull * c
        out = launch(tail, jnp.full((1,), start, jnp.int32),
                     block_to_tile[start:], out)
    return out
