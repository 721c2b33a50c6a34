"""Row-sorted hierarchical-COO EC (``ec_sorted``) vs the slot-order oracle
(``kernels/ref.py``): bit-identity on real partitions, degenerate shapes, the
``segment_sum(indices_are_sorted=True)`` hint, the out-of-core store and
super-shard paths, and the autotune cache v2 -> v3 migration."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.coo import SparseTensor, random_sparse
from repro.core.partition import (ModePartition, block_segment_descriptors,
                                  build_plan, partition_mode)
from repro.kernels import ops as kops
from repro.kernels.ref import mttkrp_local_ref


def _sorted_case(nmodes, rank, seed=0, nnz=400, num_devices=1,
                 replication=1, tile=8, block_p=128, strategy="amped_cdf"):
    shape = tuple([24, 18, 12, 10, 8][:nmodes])
    t = random_sparse(shape, nnz, seed=seed, distribution="zipf")
    part, _, _ = partition_mode(t, 1, num_devices, strategy=strategy,
                                replication=replication, tile=tile,
                                block_p=block_p, layout="sorted")
    rng = np.random.default_rng(seed + 1)
    factors = [jnp.asarray(
        rng.normal(size=(t.shape[w], rank)).astype(np.float32))
        for w in range(nmodes)]
    return t, part, factors


def _run(part, factors, variant, dev=0, num_buffers=2, mode=1):
    kw = dict(mode=mode, num_rows=part.rows_max, tile=part.tile,
              block_p=part.block_p)
    extra = {}
    if variant == "sorted":
        ss, sr = block_segment_descriptors(part.local_rows[dev],
                                           tile=part.tile,
                                           block_p=part.block_p)
        extra = dict(seg_starts=jnp.asarray(ss), seg_rows=jnp.asarray(sr))
    return kops.mttkrp_local(
        jnp.asarray(part.indices[dev]), jnp.asarray(part.values[dev]),
        jnp.asarray(part.local_rows[dev]),
        jnp.asarray(part.block_to_tile[dev]), factors,
        variant=variant, num_buffers=num_buffers, interpret=True,
        tile_mask=jnp.asarray(part.tile_visited[dev]), **kw, **extra)


def _oracle(part, factors, dev=0, mode=1):
    """The slot-order oracle on one device's shard."""
    return mttkrp_local_ref(
        jnp.asarray(part.indices[dev]), jnp.asarray(part.values[dev]),
        jnp.asarray(part.local_rows[dev]), factors, mode, part.rows_max)


@pytest.mark.parametrize("nmodes", [3, 4, 5])
@pytest.mark.parametrize("rank", [8, 32])
def test_sorted_matches_ref_bitwise(nmodes, rank):
    """Segmented reduction over the row-sorted layout accumulates the same
    values in the same order as segment_sum — BIT-identical, not approx."""
    _, part, factors = _sorted_case(nmodes, rank, seed=nmodes * 10 + rank)
    assert part.block_layout == "sorted"
    got = np.asarray(_run(part, factors, "sorted"))
    ref = np.asarray(_oracle(part, factors))
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("strategy,num_devices,replication", [
    ("amped_cdf", 2, 1),
    ("amped_cdf", 2, 2),
    ("equal_nnz", 1, 1),
])
def test_sorted_multi_device_shards(strategy, num_devices, replication):
    """Every device shard of a multi-device / replicated sorted partition:
    ec_sorted == ref bitwise (replication keeps factor indices global)."""
    _, part, factors = _sorted_case(3, 16, seed=3, num_devices=num_devices,
                                    replication=replication,
                                    strategy=strategy)
    for dev in range(num_devices):
        got = np.asarray(_run(part, factors, "sorted", dev=dev))
        ref = np.asarray(_oracle(part, factors, dev=dev))
        np.testing.assert_array_equal(got, ref, err_msg=f"dev {dev}")


@pytest.mark.parametrize("num_buffers", [2, 3, 4])
def test_sorted_num_buffers(num_buffers):
    """DMA-ring depth changes only the prefetch schedule, never the sums."""
    _, part, factors = _sorted_case(3, 16, seed=5)
    got = np.asarray(_run(part, factors, "sorted", num_buffers=num_buffers))
    ref = np.asarray(_oracle(part, factors))
    np.testing.assert_array_equal(got, ref)


def test_ref_hint_bit_identity():
    """``indices_are_sorted=True`` is declarative — on a row-sorted shard the
    oracle's hinted segment_sum returns the exact bits of the unhinted
    call."""
    _, part, factors = _sorted_case(3, 16, seed=7)
    rows = np.asarray(part.local_rows[0])
    assert (np.diff(rows) >= 0).all()  # layout contract
    plain = mttkrp_local_ref(jnp.asarray(part.indices[0]),
                             jnp.asarray(part.values[0]), jnp.asarray(rows),
                             factors, 1, part.rows_max, sorted_rows=False)
    hinted = mttkrp_local_ref(jnp.asarray(part.indices[0]),
                              jnp.asarray(part.values[0]), jnp.asarray(rows),
                              factors, 1, part.rows_max, sorted_rows=True)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(hinted))


# -- degenerate shapes -------------------------------------------------------

def test_sorted_empty_shard():
    """A device owning no nonzeros (2 groups, every update on one output
    index) must produce exact zeros — all its blocks are padding."""
    ind = np.zeros((50, 3), np.int64)
    ind[:, 1] = np.arange(50) % 7
    ind[:, 2] = np.arange(50) % 5
    t = SparseTensor(ind.astype(np.int32), np.ones(50, np.float32), (3, 7, 5))
    part, _, _ = partition_mode(t, 0, 2, strategy="amped_cdf", replication=1,
                                layout="sorted")
    empty = int(np.argmin(part.nnz_true))
    assert part.nnz_true[empty] == 0
    rng = np.random.default_rng(0)
    factors = [jnp.asarray(rng.normal(size=(s, 8)).astype(np.float32))
               for s in t.shape]
    out = _run(part, factors, "sorted", dev=empty, mode=0)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_sorted_single_segment_spans_blocks():
    """Every nonzero updates ONE output row: a single segment that spans
    multiple full blocks (plus its pad tail) must accumulate across block
    boundaries and write that row once."""
    nnz = 50
    ind = np.zeros((nnz, 3), np.int64)
    ind[:, 0] = np.arange(nnz) % 5
    ind[:, 1] = 2                       # the one output row (mode 1)
    ind[:, 2] = np.arange(nnz) // 5
    t = SparseTensor(ind.astype(np.int32),
                     np.random.default_rng(1).normal(size=nnz)
                     .astype(np.float32), (5, 7, 12))
    part, _, _ = partition_mode(t, 1, 1, tile=8, block_p=16, layout="sorted")
    assert part.indices[0].shape[0] // part.block_p >= 3  # spans >= 3 blocks
    assert len(np.unique(part.local_rows[0])) == 1        # one segment
    rng = np.random.default_rng(2)
    factors = [jnp.asarray(rng.normal(size=(s, 8)).astype(np.float32))
               for s in t.shape]
    got = np.asarray(_run(part, factors, "sorted"))
    ref = np.asarray(_oracle(part, factors))
    np.testing.assert_array_equal(got, ref)
    # exactly one written row
    assert (np.abs(got).sum(axis=1) != 0).sum() == 1


def test_sorted_all_padding_trailing_block():
    """Unequal device loads pad the lighter shard with whole trailing
    blocks; those blocks must be exact no-ops under the segmented walk."""
    # 90 nonzeros on output row 0 vs 6 on row 2: amped_cdf's 2 groups split
    # 90/6, and the light shard pads up to the heavy shard's block cap
    nnz = 96
    ind = np.zeros((nnz, 3), np.int64)
    ind[:90, 1] = 0
    ind[90:, 1] = 2
    ind[:, 0] = np.arange(nnz) % 7
    ind[:, 2] = np.arange(nnz) // 7
    t = SparseTensor(ind.astype(np.int32),
                     np.random.default_rng(6).normal(size=nnz)
                     .astype(np.float32), (7, 4, 16))
    part, _, _ = partition_mode(t, 1, 2, strategy="amped_cdf", replication=1,
                                tile=4, block_p=32, layout="sorted")
    rng = np.random.default_rng(7)
    factors = [jnp.asarray(rng.normal(size=(s, 8)).astype(np.float32))
               for s in t.shape]
    light = int(np.argmin(part.nnz_true))
    assert part.nnz_true[light] > 0  # light but not empty
    blocks = np.asarray(part.values[light]).reshape(-1, part.block_p)
    assert (blocks == 0).all(axis=1).any()  # >= 1 all-padding block
    got = np.asarray(_run(part, factors, "sorted", dev=light))
    ref = np.asarray(_oracle(part, factors, dev=light))
    np.testing.assert_array_equal(got, ref)


def test_sorted_segment_boundaries_on_block_edges():
    """Each output row owns EXACTLY block_p nonzeros: every segment starts
    at slot 0 and ends at slot block_p of its own block — the boundary
    edge case of the descriptor walk (no in-block carry, no pad tail)."""
    block_p, rows = 16, 4
    nnz = block_p * rows
    ind = np.zeros((nnz, 3), np.int64)
    ind[:, 1] = np.arange(nnz) // block_p
    ind[:, 0] = np.arange(nnz) % 4
    ind[:, 2] = (np.arange(nnz) % block_p) // 4 + 4 * (np.arange(nnz)
                                                       // (4 * block_p))
    t = SparseTensor(ind.astype(np.int32),
                     np.random.default_rng(3).normal(size=nnz)
                     .astype(np.float32), (4, rows, 16))
    part, _, _ = partition_mode(t, 1, 1, tile=2, block_p=block_p,
                                layout="sorted")
    assert (np.asarray(part.values[0]) != 0).all()  # no padding at all
    ss, sr = block_segment_descriptors(part.local_rows[0], tile=part.tile,
                                       block_p=part.block_p)
    nb = part.indices[0].shape[0] // block_p
    # one segment per block, ending exactly on the block edge
    np.testing.assert_array_equal(ss[:nb, 0], 0)
    np.testing.assert_array_equal(ss[:nb, 1], block_p)
    rng = np.random.default_rng(4)
    factors = [jnp.asarray(rng.normal(size=(s, 8)).astype(np.float32))
               for s in t.shape]
    got = np.asarray(_run(part, factors, "sorted"))
    ref = np.asarray(_oracle(part, factors))
    np.testing.assert_array_equal(got, ref)


# -- out-of-core store + super-shard paths -----------------------------------

def test_sorted_store_plan_bit_identity(tmp_path):
    """build_plan_from_store(layout='sorted') reproduces the in-memory
    sorted partition bit-for-bit, and each streamed super-shard window
    keeps rows nondecreasing and runs ec_sorted == ref bitwise."""
    from repro.store import (TensorStore, build_plan_from_store,
                             split_mode_super_shards, write_store_from_coo)

    t = random_sparse((24, 18, 12), 600, seed=0, distribution="zipf")
    path = str(tmp_path / "s.store")
    write_store_from_coo(t, path, chunk_nnz=128)
    store = TensorStore(path)

    pm = build_plan(t, 2, strategy="amped_cdf", replication=1,
                    layout="sorted")
    ps = build_plan_from_store(store, 2, strategy="amped_cdf",
                               replication=1, layout="sorted")
    for d in range(3):
        a, b = pm.modes[d], ps.modes[d]
        for k in ModePartition.META_FIELDS:
            assert getattr(a, k) == getattr(b, k), k
        assert b.block_layout == "sorted"
        for dev in range(2):
            di, dv, dr = b.device_arrays(dev)
            np.testing.assert_array_equal(di, a.indices[dev])
            np.testing.assert_array_equal(dv, a.values[dev])
            np.testing.assert_array_equal(dr, a.local_rows[dev])

    part = ps.modes[1]
    # a budget small enough to force a real split but above the floors
    nnz_cap_full = part.device_arrays(0)[1].shape[0]
    sp = split_mode_super_shards(
        part, max(64 * 1024, nnz_cap_full * (4 * 3 + 8 + 4) // 2))
    rng = np.random.default_rng(5)
    factors = [jnp.asarray(rng.normal(size=(s, 8)).astype(np.float32))
               for s in t.shape]
    for dev in range(part.num_devices):
        for (t0, t1) in sp.windows[dev]:
            wi, wv, wr, b2t, vis = part.super_shard_arrays(
                dev, t0, t1, nnz_cap=sp.nnz_cap, nblocks=sp.nblocks)
            assert (np.diff(wr) >= 0).all()  # sorted within every window
            ss, sr = block_segment_descriptors(wr, tile=part.tile,
                                               block_p=part.block_p)
            kw = dict(mode=1, num_rows=part.rows_max, tile=part.tile,
                      block_p=part.block_p)
            got = kops.mttkrp_local(
                jnp.asarray(wi), jnp.asarray(wv), jnp.asarray(wr),
                jnp.asarray(b2t), factors, variant="sorted",
                interpret=True, tile_mask=jnp.asarray(vis),
                seg_starts=jnp.asarray(ss), seg_rows=jnp.asarray(sr), **kw)
            ref = mttkrp_local_ref(
                jnp.asarray(wi), jnp.asarray(wv), jnp.asarray(wr), factors,
                1, part.rows_max)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref),
                                          err_msg=f"dev {dev} [{t0},{t1})")


def test_sorted_end_to_end_als():
    """The 'sorted' preset's full ALS (plan -> compile -> run) produces the
    same factors bitwise as the plain-jnp paper path on the SAME
    row-sorted plan — the whole pipeline, not just one local EC."""
    import repro.api as api

    t = random_sparse((16, 12, 10), 300, seed=2, distribution="zipf")
    base = api.preset("paper", {"rank": 4, "runtime.tol": 0.0,
                                "partition.layout": "sorted",
                                "partition.replication": 1})
    srt = base.with_overrides({"kernel.use_kernel": True,
                               "kernel.variant": "sorted",
                               "kernel.autotune": False})
    outs = {}
    for name, cfg in (("ref", base), ("sorted", srt)):
        solver = api.compile(api.plan(t, cfg), cfg)
        outs[name] = [np.asarray(f) for f in solver.run(2).factors]
    for a, b in zip(outs["ref"], outs["sorted"]):
        np.testing.assert_array_equal(a, b)


# -- autotune cache v2 -> v3 migration ---------------------------------------

def test_autotune_cache_v2_migration(tmp_path, monkeypatch):
    """A v2 cache (dtype slot, no device-kind slot) migrates to v3 with the
    backend segment standing in for the kind; ``xchg_*`` exchange entries
    pass through byte-identical, garbage is dropped, and re-migrating the
    migrated file changes nothing."""
    import json

    import jax

    from repro.kernels import autotune as at

    backend = jax.default_backend()
    grid = {"nnz": 256, "tiles": [8], "block_ps": [64],
            "num_buffers_grid": [2]}
    xchg = {"chunk_rows": 512, "timings": {"c512": 0.5}}
    v2 = {
        "_format": 2,
        f"3m_r8_float32_{backend}_fused": {
            "tile": 8, "block_p": 64, "num_buffers": 2, "grid": grid,
            "timings": {"t8_p64_b2": 1.0}},
        f"4m_r16_bfloat16_{backend}_sorted": {
            "tile": 8, "block_p": 64, "num_buffers": 3, "grid": grid,
            "timings": {"t8_p64_b3": 2.0}},
        "xchg_ring_r16_float32": xchg,
        "not a key": {"tile": 1},
    }
    path = tmp_path / "cache.json"
    path.write_text(json.dumps(v2))
    monkeypatch.setenv(at.ENV_CACHE, str(path))
    at._MEMO.clear()

    loaded = at._load_cache(str(path))
    assert loaded["_format"] == at.CACHE_FORMAT_VERSION
    assert f"3m_r8_float32_{backend}_{backend}_fused" in loaded
    assert f"4m_r16_bfloat16_{backend}_{backend}_sorted" in loaded
    assert loaded["xchg_ring_r16_float32"] == xchg  # untouched
    assert "not a key" not in loaded
    on_disk = json.loads(path.read_text())  # migration persisted
    assert on_disk.get("_format") == at.CACHE_FORMAT_VERSION
    # idempotent: migrating a migrated cache is the identity
    assert at._migrate_cache(on_disk) == {k: v for k, v in on_disk.items()}
