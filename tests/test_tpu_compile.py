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
import re

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


def _hlo_shapes(text):
    """{instruction name: dims} over every instruction of a compiled HLO
    module whose result is a single array."""
    shapes = {}
    for m in re.finditer(r"%([\w.-]+) = \w+\[([\d,]*)\]", text):
        shapes[m.group(1)] = [int(d) for d in m.group(2).split(",") if d]
    return shapes


def _operand_dims(text, op):
    """Dims of each operand of every ``op`` instruction in ``text``."""
    shapes = _hlo_shapes(text)
    out = []
    for m in re.finditer(rf"= [^=]*? {op}\(([^)]*)\)", text):
        names = re.findall(r"%([\w.-]+)", m.group(1))
        out.append([shapes.get(n) for n in names])
    return out


def _without_metadata(text):
    """A compiled HLO module's text without its source locations: each
    instruction's metadata and the tables it points into."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return "\n".join(
        ln for ln in text.splitlines()
        if not re.match(r'\d+ [{"]|(FileNames|FunctionNames|FileLocations'
                        r'|StackFrames)$', ln))


AMAZON = (48216, 17744, 18056)          # amazon-r32's padded factor rows
TWITCH = (186296, 73944, 9408, 80, 80)  # twitch-r32's
SMOKE = (289272, 106456, 108311)        # chip_smoke.py's amazon at 0.06
LARGE = (964241, 354854, 361037)        # amazon at scale 0.2
# (factor rows, output mode, tile, layout, blocks in the shard, whether the
# slot-order oracle's temporaries are compared, how the EC runs its
# chunks): a few hundred blocks and amazon-r32's shards, whose input
# factors fit in VMEM together as they are (a "loop"); twitch-r32's mode 3
# (137 MB lane-padded, 34 MB lane-packed) and chip_smoke.py's mode-0 shard
# (row-sorted layout, tile 16, about 42.9 M slots; 110 MB lane-padded, 27
# MB lane-packed), whose factors fit only lane-packed (a "packed" loop);
# and an amazon mode-1 update at scale 0.2, whose factors do not fit even
# lane-packed (170 MB), so its chunks are "unrolled".
REF_SHAPES = {
    "300": (AMAZON, 0, 8, "blocked", 300, True, "loop"),
    "89000": (AMAZON, 0, 8, "blocked", 89_000, True, "loop"),
    "amazon-mode1": (AMAZON, 1, 8, "blocked", 87_300, True, "loop"),
    "twitch-mode3": (TWITCH, 3, 8, "blocked", 24_170, False, "packed"),
    "chip_smoke": (SMOKE, 0, 16, "sorted", 335_000, False, "packed"),
    "large-mode1": (LARGE, 1, 8, "blocked", 9_000, False, "unrolled"),
}


@pytest.mark.parametrize("case", sorted(REF_SHAPES))
def test_ref_mode_update_scatters_blocks_for_v5e(one_chip, monkeypatch,
                                                 case):
    """One resident ALS mode update on the ``ref`` EC (R 32, block_p 128),
    compiled for the chip at the shapes of ``REF_SHAPES``: no scatter takes
    one update row per nonzero, no sort runs over the nonzeros, the block
    scatter-add takes at most one chunk of blocks (a ``while`` of
    ``kops.REF_LOOP_BLOCKS`` where the input factors fit in VMEM, as they
    are or lane-packed, else unrolled chunks), the EC's matmuls run at
    HIGHEST, the program is the one that gathers from the factors as they
    are unless they fit in VMEM only lane-packed, and the program's
    temporaries stay within a few chunks' gathered rows
    (``kops.REF_CHUNK_BYTES``) however large the shard. At the amazon
    shapes they are also no larger than the slot-order oracle's
    (kernels/ref.py) in the same update, to within 1 MiB. (Where the output has few rows, as
    in twitch's mode 3, the compiler fuses the oracle's gathers into its
    scatter and the oracle holds less; its scatter then runs over every
    nonzero.)"""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import als, mttkrp
    from repro.core.coo import random_sparse
    from repro.core.partition import build_plan
    from repro.kernels import ops as kops
    from repro.kernels.ref import mttkrp_local_ref
    rows, mode, tile, layout, nblocks, vs_oracle, ec = REF_SHAPES[case]
    loop = ec != "unrolled"
    nmodes = len(rows)
    topo, _ = one_chip
    # the plan sets the factor rows and tile geometry; the shard's shapes
    # are given below, at the block count under test
    t = random_sparse(rows, 20000, seed=0, distribution="zipf")
    plan = build_plan(t, 1, replication=1, tile=tile, block_p=128,
                      layout=layout)
    part = plan.modes[mode]
    nnz = nblocks * part.block_p
    mesh = mttkrp.cp_mesh(1, 1, devices=np.asarray(topo.devices[:1]))
    grid = ("group", "sub")

    def sharded(shape, dtype, trailing):
        return jax.ShapeDtypeStruct(
            (1, 1) + shape, dtype,
            sharding=NamedSharding(mesh, P(*grid, *([None] * trailing))))

    dev = mttkrp.DeviceArrays(
        indices=sharded((nnz, nmodes), jnp.int32, 2),
        values=sharded((nnz,), jnp.float32, 1),
        local_rows=sharded((nnz,), jnp.int32, 1),
        block_to_tile=sharded((nblocks,), jnp.int32, 1),
        tile_visited=sharded((part.rows_max // part.tile,), jnp.float32, 1),
        seg_starts=sharded((nblocks, part.tile + 2), jnp.int32, 2),
        seg_rows=sharded((nblocks, part.tile + 1), jnp.int32, 2))
    rep = NamedSharding(mesh, P())
    facs = [jax.ShapeDtypeStruct((m.padded_rows, 32), jnp.float32,
                                 sharding=rep) for m in plan.modes]
    grams = [jax.ShapeDtypeStruct((32, 32), jnp.float32, sharding=rep)] \
        * nmodes

    def compile_update():
        update = als.make_mode_update(plan, mode, mesh, use_kernel=False,
                                      variant="ref", interpret=False)
        others = [facs[w] for w in range(nmodes) if w != mode]
        return update.lower(facs[mode], dev, others, grams).compile()

    def per_nonzero(text):
        """(scatters whose updates have nnz rows, sorts over nnz keys)."""
        scatters = [d for d in _operand_dims(text, "scatter")
                    if d[-1] and d[-1][0] == nnz]
        sorts = [d for d in _operand_dims(text, "sort")
                 if any(x and x[0] == nnz for x in d)]
        return scatters, sorts

    compiled = compile_update()
    text = compiled.as_text()
    block_scatters = [d[-1] for d in _operand_dims(text, "scatter")
                      if d[-1] and d[-1][1:] == [part.tile, 32]]
    assert block_scatters, "the block scatter-add is missing"
    # in-VMEM loop chunks or unrolled chunks of REF_CHUNK_BYTES: a chunk is
    # far smaller than the shard (except at a few hundred blocks)
    assert max(d[0] for d in block_scatters) == (
        kops.REF_LOOP_BLOCKS if loop
        else kops.REF_CHUNK_BYTES // ((nmodes - 1) * 128 * 512))
    op_names = re.findall(r'op_name="([^"]*)"', text)
    assert any(n.endswith("/ec_local/while") for n in op_names) == loop
    assert per_nonzero(text) == ([], [])
    # the in-block contraction keeps float32: every EC matmul at HIGHEST
    ec_dots = [ln for ln in text.splitlines()
               if "convolution(" in ln and "/ec_local/" in ln]
    assert ec_dots and all("operand_precision={highest,highest}" in ln
                           for ln in ec_dots), ec_dots
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 4 * kops.REF_CHUNK_BYTES, temp
    # the program gathering from the factors as they are: the same one, to
    # the byte, unless the factors fit in VMEM only lane-packed
    assert kops.ref_rows_per_lane_row(
        [m.padded_rows for w, m in enumerate(plan.modes) if w != mode],
        32) == (4 if ec == "packed" else 1)
    with monkeypatch.context() as mp:
        mp.setattr(kops, "ref_rows_per_lane_row", lambda rows, r: 1)
        assert (_without_metadata(compile_update().as_text())
                == _without_metadata(text)) == (ec != "packed")
    if not vs_oracle:
        return

    def oracle(indices, values, local_rows, block_to_tile, factors, *,
               mode, num_rows, **_):
        return mttkrp_local_ref(indices, values, local_rows, factors, mode,
                                num_rows)

    monkeypatch.setitem(kops.KERNEL_VARIANTS, "ref", oracle)
    compiled_oracle = compile_update()
    scatters, sorts = per_nonzero(compiled_oracle.as_text())
    # the checks above see them where they are (at mode 1 the compiler
    # sorts nothing for the oracle's scatter)
    assert scatters and (sorts or mode != 0)
    # At mode 1 both programs' temporaries are the shard relayouts (the
    # indices' and local_rows', 223.5 MB): within 1 MiB, the loop's
    # bookkeeping and the arena's packing
    assert temp <= compiled_oracle.memory_analysis().temp_size_in_bytes \
        + (1 << 20)


_COLLECTIVE_RE = re.compile(
    r"= .*? (collective-permute(?:-start|-done)?|all-gather(?:-start|-done)?"
    r"|all-reduce(?:-start|-done)?|reduce-scatter|all-to-all)\(")


@pytest.mark.parametrize("mode", [0, 1])
def test_four_chip_exchange_collectives_are_scoped_for_v5e(one_chip, mode):
    """A paper-preset mode update over the four chips of a v5e 2x2 (CDF
    ownership across 4 groups, the ``ref`` EC under ``shard_map``, the
    Algorithm-3 ring): every collective the compiler emits carries the
    ``factor_exchange`` or ``merge`` scope, so the trace's exchange time
    (``bench/metrics/exchange_ms.py``) sees all of it and none falls in
    the solve."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import api, comm
    from repro.core import als, mttkrp
    from repro.core.coo import random_sparse
    topo, _ = one_chip
    cfg = api.preset("paper", {"rank": 32, "runtime.num_devices": 4})
    t = random_sparse((3000, 1200, 900), 60000, seed=0, distribution="zipf")
    plan = api.plan(t, cfg)
    mesh = mttkrp.cp_mesh(4, 1, devices=np.asarray(topo.devices[:4]))
    part = plan.modes[mode]
    grid = ("group", "sub")

    def sharded(shape, dtype, trailing):
        return jax.ShapeDtypeStruct(
            (4, 1) + shape, dtype,
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
    spec = comm.resolve_exchange_spec(cfg.exchange, plan=plan, rank=32,
                                      mesh=mesh)
    update = als.make_mode_update(
        plan, mode, mesh, **cfg.kernel.mttkrp_kwargs(nmodes=3, rank=32),
        exchange_spec=spec)
    others = [facs[w] for w in range(3) if w != mode]
    text = update.lower(facs[mode], dev, others, grams).compile().as_text()
    collectives = [ln for ln in text.splitlines()
                   if _COLLECTIVE_RE.search(ln)]
    assert collectives, "the exchange emitted no collective"
    for ln in collectives:
        m = re.search(r'op_name="([^"]*)"', ln)
        assert m and re.search(r"(^|/)(factor_exchange|merge)(/|$)",
                               m.group(1)), ln


AMAZON_4CHIP = (192848, 70971, 72207)   # amazon-4chip.resident's mode sizes


@pytest.fixture(scope="module")
def amazon_4chip_plan():
    """A paper-preset 4-device plan at amazon-4chip's mode sizes (a Zipf
    sample of the nonzeros: CDF ownership leaves most rows to the last
    group, as in the cell) and its partition report at rank 32."""
    from repro import api
    from repro.api.solver import partition_report
    from repro.core.coo import random_sparse
    cfg = api.preset("paper", {"rank": 32, "runtime.num_devices": 4})
    t = random_sparse(AMAZON_4CHIP, 200_000, seed=0, distribution="zipf")
    plan = api.plan(t, cfg)
    return cfg, plan, partition_report(plan, 32)


@pytest.mark.parametrize("mode", [0, 1, 2])
def test_amazon_4chip_packed_factors_steer_the_ec_for_v5e(
        one_chip, amazon_4chip_plan, mode):
    """A mode update of the 4-chip cell compiled for a v5e 2x2 on the
    packed factors: every mode's ``ref`` EC is a ``while`` whose operands
    hold its gather sources in VMEM (``S(1)``). Mode 0 reads two small
    factors (73.3 MB lane-padded) as they are; modes 1 and 2 read mode 0's
    192,848 rows (135 MB lane-padded), so they gather from lane-packed
    copies, four rows to a 128-lane row (33.9 MB). Every collective carries
    ``factor_exchange`` or ``merge``, and the packing copy (``pack_slabs``'
    concatenate) sits under ``factor_exchange``."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import comm
    from repro.core import als, mttkrp
    from repro.kernels import ops as kops
    topo, _ = one_chip
    cfg, plan, report = amazon_4chip_plan
    k = 1 if mode == 0 else 4
    assert report["per_mode"][mode]["ec_rows_per_lane_row"] == k
    assert report["per_mode"][mode]["ec_loop"]
    assert report["per_mode"][mode]["factor_rows_over_rows"] <= 1.001
    mesh = mttkrp.cp_mesh(4, 1, devices=np.asarray(topo.devices[:4]))
    part = plan.modes[mode]
    # more blocks than one unrolled chunk would hold
    nblocks = kops.REF_CHUNK_BYTES // (2 * 128 * 512) + 904
    nnz = nblocks * part.block_p
    grid = ("group", "sub")

    def sharded(shape, dtype, trailing):
        return jax.ShapeDtypeStruct(
            (4, 1) + shape, dtype,
            sharding=NamedSharding(mesh, P(*grid, *([None] * trailing))))

    dev = mttkrp.DeviceArrays(
        indices=sharded((nnz, 3), jnp.int32, 2),
        values=sharded((nnz,), jnp.float32, 1),
        local_rows=sharded((nnz,), jnp.int32, 1),
        block_to_tile=sharded((nblocks,), jnp.int32, 1),
        tile_visited=sharded((part.rows_max // part.tile,), jnp.float32, 1),
        seg_starts=sharded((nblocks, part.tile + 2), jnp.int32, 2),
        seg_rows=sharded((nblocks, part.tile + 1), jnp.int32, 2))
    rep = NamedSharding(mesh, P())
    facs = [jax.ShapeDtypeStruct((m.padded_rows, 32), jnp.float32,
                                 sharding=rep) for m in plan.modes]
    grams = [jax.ShapeDtypeStruct((32, 32), jnp.float32, sharding=rep)] * 3
    spec = comm.resolve_exchange_spec(cfg.exchange, plan=plan, rank=32,
                                      mesh=mesh)
    update = als.make_mode_update(
        plan, mode, mesh, **cfg.kernel.mttkrp_kwargs(nmodes=3, rank=32),
        exchange_spec=spec)
    others = [facs[w] for w in range(3) if w != mode]
    text = update.lower(facs[mode], dev, others, grams).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', text)
    assert "jit(update)/shard_map/ec_local/while" in op_names
    block_scatters = [d[-1] for d in _operand_dims(text, "scatter")
                      if d[-1] and d[-1][1:] == [part.tile, 32]]
    assert max(d[0] for d in block_scatters) == kops.REF_LOOP_BLOCKS, \
        block_scatters
    loops = [ln for ln in text.splitlines()
             if re.search(r"= \(.*\) while\(", ln) and "/ec_local/" in ln]
    assert len(loops) == 1, loops
    for w in range(3):
        if w != mode:
            rows = plan.modes[w].padded_rows
            source = f"{-(-rows // 32) * 8},128" if k == 4 else f"{rows},32"
            assert re.search(rf"f32\[{source}\]\{{[^}}]*S\(1\)", loops[0]), \
                (w, source, loops[0][:400])
    for ln in text.splitlines():
        if _COLLECTIVE_RE.search(ln):
            m = re.search(r'op_name="([^"]*)"', ln)
            assert m and re.search(r"(^|/)(factor_exchange|merge)(/|$)",
                                   m.group(1)), ln
    packing = [n for n in op_names if n.endswith("/concatenate")]
    assert packing and all(n.endswith("/factor_exchange/concatenate")
                           for n in packing), packing
