"""Ahead-of-time compiles of the EC kernels for one TPU v5e chip.

jax ships the TPU compiler, which compiles for a chip that is described
(``jax.experimental.topologies``) rather than attached. These tests catch
what interpret mode cannot: a kernel the chip's compiler (Mosaic) refuses —
misaligned DMA slices, block shapes off the (8, 128) tiling, scalar
operands that overflow SMEM. Nothing runs; each program must compile and
hold its kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

# (tile, block_p) pairs of the autotune grid (kernels/autotune.py), spread
# over the (R, nin) cases so every variant meets all four.
GEOMETRY = {(32, 2): (8, 64), (32, 4): (16, 128),
            (128, 2): (16, 64), (128, 4): (8, 128)}
NBLOCKS, CHUNK_BLOCKS = 300, 128  # two chunks in the fori_loop + a tail
ROWS = 512


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # compiles for a described chip cannot be read back from a
        # persistent cache; keep any configured cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield topo, SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_call(variant, r, nin, tile, block_p, dev):
    from repro.kernels.mttkrp_fused import ec_fused
    from repro.kernels.mttkrp_pallas import ec_blocked
    from repro.kernels.mttkrp_sorted import ec_sorted
    nnz = NBLOCKS * block_p
    kw = dict(num_rows=ROWS, tile=tile, block_p=block_p, interpret=False)
    head = [_sds(dev, (nnz,))]
    b2t = _sds(dev, (NBLOCKS,), jnp.int32)
    if variant == "blocked":
        return ((lambda v, s, b, *g: ec_blocked(v, s, b, list(g), **kw)),
                head + [_sds(dev, (nnz,), jnp.int32), b2t]
                + [_sds(dev, (nnz, r))] * nin)
    idx = _sds(dev, (nin, nnz), jnp.int32)
    facs = [_sds(dev, (1000, r))] * nin
    if variant == "fused":
        return ((lambda v, s, b, i, *f: ec_fused(v, s, b, i, list(f), **kw)),
                head + [_sds(dev, (nnz,), jnp.int32), b2t, idx] + facs)
    seg = [_sds(dev, (NBLOCKS, tile + 2), jnp.int32),
           _sds(dev, (NBLOCKS, tile + 1), jnp.int32)]
    return ((lambda v, ss, sr, b, i, *f:
             ec_sorted(v, ss, sr, b, i, list(f), **kw)),
            head + seg + [b2t, idx] + facs)


@pytest.mark.parametrize("r,nin", sorted(GEOMETRY))
@pytest.mark.parametrize("variant", ["blocked", "fused", "sorted"])
def test_ec_kernel_compiles_for_v5e(one_chip, variant, r, nin, monkeypatch):
    from repro.kernels import tpu_layout
    monkeypatch.setattr(tpu_layout, "MAX_CHUNK_BLOCKS", CHUNK_BLOCKS)
    _, dev = one_chip
    tile, block_p = GEOMETRY[(r, nin)]
    fn, args = _kernel_call(variant, r, nin, tile, block_p, dev)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_resident_mode_update_compiles_for_v5e(one_chip):
    """One resident ALS mode update on the fused path (shard_map + EC +
    exchange + solve) at rank 32, as the solver jits it."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import als, mttkrp
    from repro.core.coo import random_sparse
    from repro.core.partition import build_plan
    topo, _ = one_chip
    t = random_sparse((300, 120, 90), 6000, seed=0, distribution="zipf")
    plan = build_plan(t, 1, replication=1, tile=16, block_p=128)
    mesh = mttkrp.cp_mesh(1, 1, devices=np.asarray(topo.devices[:1]))
    update = als.make_mode_update(plan, 0, mesh, use_kernel=True,
                                  variant="fused", num_buffers=2,
                                  interpret=False)
    part = plan.modes[0]
    grid = ("group", "sub")

    def sharded(shape, dtype, trailing):
        return jax.ShapeDtypeStruct(
            (1, 1) + shape, dtype,
            sharding=NamedSharding(mesh, P(*grid, *([None] * trailing))))

    nblocks = part.nblocks
    dev = mttkrp.DeviceArrays(
        indices=sharded((part.nnz_max, 3), jnp.int32, 2),
        values=sharded((part.nnz_max,), jnp.float32, 1),
        local_rows=sharded((part.nnz_max,), jnp.int32, 1),
        block_to_tile=sharded((nblocks,), jnp.int32, 1),
        tile_visited=sharded((part.rows_max // part.tile,), jnp.float32, 1),
        seg_starts=sharded((nblocks, part.tile + 2), jnp.int32, 2),
        seg_rows=sharded((nblocks, part.tile + 1), jnp.int32, 2))
    rep = NamedSharding(mesh, P())
    facs = [jax.ShapeDtypeStruct((m.padded_rows, 32), jnp.float32,
                                 sharding=rep) for m in plan.modes]
    grams = [jax.ShapeDtypeStruct((32, 32), jnp.float32, sharding=rep)] * 3
    compiled = update.lower(facs[0], dev, facs[1:], grams).compile()
    assert "tpu_custom_call" in compiled.as_text()
