"""Pallas EC kernel vs pure-jnp oracle: shape/dtype sweeps + hypothesis."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st

from repro.kernels.mttkrp_pallas import ec_blocked
from repro.kernels.ref import ec_rows_ref, mttkrp_local_ref
from repro.kernels import ops as kops


def _mk(nblocks, tile, n_tiles, p, r, nin, seed, dtype=np.float32,
        monotone=True):
    rng = np.random.default_rng(seed)
    nnz = nblocks * p
    # monotone block→tile map (kernel contract: revisits are consecutive)
    if monotone:
        b2t = np.sort(rng.integers(0, n_tiles, size=nblocks))
    else:
        b2t = rng.integers(0, n_tiles, size=nblocks)
    rows_in_tile = rng.integers(0, tile, size=nnz)
    vals = rng.normal(size=nnz).astype(dtype)
    vals[rng.random(nnz) < 0.2] = 0.0  # padding-like entries
    gathered = [rng.normal(size=(nnz, r)).astype(dtype) for _ in range(nin)]
    return b2t.astype(np.int32), rows_in_tile.astype(np.int32), vals, gathered


def _oracle(b2t, rows_in_tile, vals, gathered, tile, n_tiles, p):
    glob = np.repeat(b2t, p) * tile + rows_in_tile
    out = ec_rows_ref(jnp.asarray(vals),
                      [jnp.asarray(g) for g in gathered],
                      jnp.asarray(glob.astype(np.int32)), n_tiles * tile)
    return np.asarray(out)


@pytest.mark.parametrize("tile,p,r,nin", [
    (8, 16, 8, 1), (8, 32, 16, 2), (16, 64, 32, 2), (8, 128, 32, 4),
    (32, 32, 64, 3),
])
def test_kernel_shape_sweep(tile, p, r, nin):
    nblocks, n_tiles = 7, 5
    b2t, rit, vals, gathered = _mk(nblocks, tile, n_tiles, p, r, nin, seed=1)
    out = ec_blocked(jnp.asarray(vals), jnp.asarray(rit), jnp.asarray(b2t),
                     [jnp.asarray(g) for g in gathered],
                     num_rows=n_tiles * tile, tile=tile, block_p=p,
                     interpret=True)
    # mask unvisited tiles like ops.mttkrp_local does
    visited = np.zeros(n_tiles, np.float32)
    visited[b2t] = 1
    got = np.asarray(out) * np.repeat(visited, tile)[:, None]
    got = np.nan_to_num(got, nan=0.0)
    ref = _oracle(b2t, rit, vals, gathered, tile, n_tiles, p)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_kernel_dtypes(dtype):
    tile, p, r = 8, 32, 16
    nblocks, n_tiles = 4, 3
    b2t, rit, vals, gathered = _mk(nblocks, tile, n_tiles, p, r, 2, seed=2)
    vals_d = jnp.asarray(vals).astype(dtype)
    gath_d = [jnp.asarray(g).astype(dtype) for g in gathered]
    out = ec_blocked(vals_d, jnp.asarray(rit), jnp.asarray(b2t), gath_d,
                     num_rows=n_tiles * tile, tile=tile, block_p=p,
                     interpret=True)
    assert out.dtype == jnp.float32  # f32 accumulation regardless of input
    visited = np.zeros(n_tiles, np.float32)
    visited[b2t] = 1
    got = np.nan_to_num(np.asarray(out) * np.repeat(visited, tile)[:, None])
    ref = _oracle(b2t, rit, np.asarray(vals_d, np.float32),
                  [np.asarray(g, np.float32) for g in gath_d],
                  tile, n_tiles, p)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)


@given(st.integers(0, 10_000), st.integers(1, 6), st.integers(1, 4))
@settings(max_examples=10, deadline=None)
def test_kernel_property(seed, nblocks, n_tiles):
    tile, p, r = 8, 16, 8
    b2t, rit, vals, gathered = _mk(nblocks, tile, n_tiles, p, r, 2, seed=seed)
    out = ec_blocked(jnp.asarray(vals), jnp.asarray(rit), jnp.asarray(b2t),
                     [jnp.asarray(g) for g in gathered],
                     num_rows=n_tiles * tile, tile=tile, block_p=p,
                     interpret=True)
    visited = np.zeros(n_tiles, np.float32)
    visited[b2t] = 1
    got = np.nan_to_num(np.asarray(out) * np.repeat(visited, tile)[:, None])
    ref = _oracle(b2t, rit, vals, gathered, tile, n_tiles, p)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_ops_wrapper_matches_ref(small_tensor):
    """mttkrp_local kernel path == jnp path on real partition arrays."""
    from repro.core.partition import partition_mode
    t = small_tensor
    part, g2p, _ = partition_mode(t, 1, 1, strategy="amped_cdf",
                                  replication=1)
    rng = np.random.default_rng(0)
    factors = [jnp.asarray(rng.normal(size=(t.shape[w], 16)).astype(np.float32))
               for w in range(3)]
    # single device → indices untranslated == global
    kw = dict(mode=1, num_rows=part.rows_max, tile=part.tile,
              block_p=part.block_p)
    a = kops.mttkrp_local(jnp.asarray(part.indices[0]),
                          jnp.asarray(part.values[0]),
                          jnp.asarray(part.local_rows[0]),
                          jnp.asarray(part.block_to_tile[0]), factors,
                          use_kernel=True, interpret=True,
                          tile_mask=jnp.asarray(part.tile_visited[0]), **kw)
    b = kops.mttkrp_local(jnp.asarray(part.indices[0]),
                          jnp.asarray(part.values[0]),
                          jnp.asarray(part.local_rows[0]),
                          jnp.asarray(part.block_to_tile[0]), factors,
                          use_kernel=False, **kw)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-4)


# ---------------------------------------------------------------------------
# Fused in-kernel-gather EC (mttkrp_fused.ec_fused) — see EXPERIMENTS.md §Perf
# ---------------------------------------------------------------------------

def _partitioned_case(nmodes, rank, seed=0, nnz=400, num_devices=1,
                      replication=1, tile=8, block_p=128, skew="zipf"):
    """Random tensor → real partition arrays → random (shape[w], rank)
    factors (global layout — single-device partitions keep indices
    untranslated)."""
    from repro.core.coo import random_sparse
    from repro.core.partition import partition_mode
    shape = tuple([24, 18, 12, 10, 8][:nmodes])
    t = random_sparse(shape, nnz, seed=seed, distribution=skew)
    part, g2p, _ = partition_mode(t, 1, num_devices, strategy="amped_cdf",
                                  replication=replication, tile=tile,
                                  block_p=block_p)
    rng = np.random.default_rng(seed + 1)
    factors = [jnp.asarray(
        rng.normal(size=(t.shape[w], rank)).astype(np.float32))
        for w in range(nmodes)]
    return t, part, factors


def _run_variant(part, factors, variant, dev=0, num_buffers=2):
    kw = dict(mode=1, num_rows=part.rows_max, tile=part.tile,
              block_p=part.block_p)
    return kops.mttkrp_local(
        jnp.asarray(part.indices[dev]), jnp.asarray(part.values[dev]),
        jnp.asarray(part.local_rows[dev]),
        jnp.asarray(part.block_to_tile[dev]), factors,
        variant=variant, num_buffers=num_buffers, interpret=True,
        tile_mask=jnp.asarray(part.tile_visited[dev]), **kw)


@pytest.mark.parametrize("nmodes", [3, 4, 5])
@pytest.mark.parametrize("rank", [8, 32])
def test_fused_matches_ref(nmodes, rank):
    _, part, factors = _partitioned_case(nmodes, rank, seed=nmodes * 10 + rank)
    got = np.asarray(_run_variant(part, factors, "fused"))
    ref = np.asarray(_run_variant(part, factors, "ref"))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("num_buffers", [2, 3, 4])
def test_fused_num_buffers(num_buffers):
    """Deeper DMA rings change only the schedule, never the result."""
    _, part, factors = _partitioned_case(3, 16, seed=5)
    got = np.asarray(_run_variant(part, factors, "fused",
                                  num_buffers=num_buffers))
    ref = np.asarray(_run_variant(part, factors, "ref"))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_fused_matches_blocked():
    _, part, factors = _partitioned_case(4, 16, seed=9)
    got = np.asarray(_run_variant(part, factors, "fused"))
    blk = np.asarray(_run_variant(part, factors, "blocked"))
    np.testing.assert_allclose(got, blk, rtol=1e-4, atol=1e-4)


def test_fused_empty_shard():
    """A device that owns no nonzeros (2 groups, skewed tensor) must produce
    exact zeros — all its blocks are padding."""
    from repro.core.coo import SparseTensor
    from repro.core.partition import partition_mode
    # every nonzero updates output index 0 → group 1 of 2 owns nothing
    ind = np.zeros((50, 3), np.int64)
    ind[:, 1] = np.arange(50) % 7
    ind[:, 2] = np.arange(50) % 5
    t = SparseTensor(ind.astype(np.int32),
                     np.ones(50, np.float32), (3, 7, 5))
    part, _, _ = partition_mode(t, 0, 2, strategy="amped_cdf", replication=1)
    empty = int(np.argmin(part.nnz_true))
    assert part.nnz_true[empty] == 0
    rng = np.random.default_rng(0)
    factors = [jnp.asarray(rng.normal(size=(s, 8)).astype(np.float32))
               for s in t.shape]
    kw = dict(mode=0, num_rows=part.rows_max, tile=part.tile,
              block_p=part.block_p)
    out = kops.mttkrp_local(
        jnp.asarray(part.indices[empty]), jnp.asarray(part.values[empty]),
        jnp.asarray(part.local_rows[empty]),
        jnp.asarray(part.block_to_tile[empty]), factors,
        variant="fused", interpret=True,
        tile_mask=jnp.asarray(part.tile_visited[empty]), **kw)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_fused_replicated_shards():
    """r>1: each replica's fused partial equals its ref partial (the
    intra-group reduce-scatter then merges identical quantities)."""
    _, part, factors = _partitioned_case(3, 16, seed=3, num_devices=2,
                                         replication=2)
    assert part.r == 2 and part.n_groups == 1
    for dev in range(2):
        got = np.asarray(_run_variant(part, factors, "fused", dev=dev))
        ref = np.asarray(_run_variant(part, factors, "ref", dev=dev))
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_fused_padding_blocks():
    """nnz far from a block_p multiple → heavy in-tile padding plus whole
    trailing pad blocks; all must be exact no-ops."""
    _, part, factors = _partitioned_case(3, 16, seed=11, nnz=37, block_p=128)
    assert (part.values == 0).any()  # real padding present
    got = np.asarray(_run_variant(part, factors, "fused"))
    ref = np.asarray(_run_variant(part, factors, "ref"))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_fused_hlo_has_no_gathered_intermediate():
    """The acceptance property: the fused path lowers with NO gather op at
    all (factor rows are streamed in-kernel), while the blocked path
    materializes one (nnz, R) gather per input mode."""
    _, part, factors = _partitioned_case(3, 16, seed=2)
    kw = dict(mode=1, num_rows=part.rows_max, tile=part.tile,
              block_p=part.block_p, interpret=True,
              tile_mask=jnp.asarray(part.tile_visited[0]))
    args = (jnp.asarray(part.indices[0]), jnp.asarray(part.values[0]),
            jnp.asarray(part.local_rows[0]),
            jnp.asarray(part.block_to_tile[0]), factors)

    def hlo(variant):
        f = jax.jit(lambda *a: kops.mttkrp_local(*a, variant=variant, **kw))
        return f.lower(*args).as_text()

    assert hlo("fused").count("gather") == 0
    assert hlo("blocked").count('"stablehlo.gather"(') == 2  # 1/input mode


def test_autotune_smoke(tmp_path, monkeypatch):
    """Tiny-grid autotune run: returns a config from the grid, persists it,
    and the second call is served from the on-disk cache."""
    from repro.kernels import autotune as at
    monkeypatch.setenv(at.ENV_CACHE, str(tmp_path / "cache.json"))
    at._MEMO.clear()
    kw = dict(variant="fused", nnz=256, tiles=(8,), block_ps=(64, 128),
              num_buffers_grid=(2,), repeats=1)
    cfg = at.autotune_ec(3, 8, **kw)
    assert cfg.tile == 8 and cfg.block_p in (64, 128) and cfg.num_buffers == 2
    assert len(cfg.timings) == 2
    at._MEMO.clear()  # force the disk-cache path
    cfg2 = at.autotune_ec(3, 8, **kw)
    assert (cfg2.tile, cfg2.block_p, cfg2.num_buffers) == \
        (cfg.tile, cfg.block_p, cfg.num_buffers)
    # a different candidate grid must NOT reuse the cached winner
    cfg3 = at.autotune_ec(3, 8, **{**kw, "tiles": (16,)})
    assert cfg3.tile == 16


def test_autotune_cache_key_dtype_and_rank(tmp_path, monkeypatch):
    """Regression: the v1 cache keyed only (nmodes, rank, backend, variant),
    so an fp32 and a bf16 sweep — and, in a key missing rank, different R —
    collided on one entry and replayed each other's tile/block_p winners.
    The v3 key carries dtype, rank AND the device kind; distinct
    (dtype, rank) points must produce distinct cache entries."""
    import json

    import jax.numpy as jnp

    from repro.kernels import autotune as at

    path = tmp_path / "cache.json"
    monkeypatch.setenv(at.ENV_CACHE, str(path))
    at._MEMO.clear()
    kw = dict(variant="ref", nnz=256, tiles=(8,), block_ps=(64,),
              num_buffers_grid=(2,), repeats=1)
    at.autotune_ec(3, 8, dtype=jnp.float32, **kw)
    at.autotune_ec(3, 8, dtype=jnp.bfloat16, **kw)
    at.autotune_ec(3, 16, dtype=jnp.float32, **kw)
    cache = json.loads(path.read_text())
    entries = {k for k in cache if not k.startswith("_")}
    assert cache["_format"] == at.CACHE_FORMAT_VERSION
    assert len(entries) == 3, entries  # no collisions
    backend = __import__("jax").default_backend()
    kind = at.device_kind_tag()
    assert f"3m_r8_float32_{backend}_{kind}_ref" in entries
    assert f"3m_r8_bfloat16_{backend}_{kind}_ref" in entries
    assert f"3m_r16_float32_{backend}_{kind}_ref" in entries


def test_autotune_cache_v1_migration(tmp_path, monkeypatch):
    """Loading a v1 cache chain-migrates its (fp32-timed) entries through
    the dtype-qualified v2 form to the kind-qualified v3 form, drops
    unrecognizable keys, and persists the migrated file; a bf16 request
    then MISSES the migrated fp32 entry (the collision the bugfix removes)
    while an fp32 request with the same grid hits it."""
    import json

    import jax
    import jax.numpy as jnp

    from repro.kernels import autotune as at

    backend = jax.default_backend()
    grid = {"nnz": 256, "tiles": [8], "block_ps": [64],
            "num_buffers_grid": [2]}
    v1 = {
        f"3m_r8_{backend}_ref": {"tile": 8, "block_p": 64, "num_buffers": 2,
                                 "grid": grid, "timings": {"t8_p64_b2": 1.0}},
        "garbage key": {"tile": 1},
    }
    path = tmp_path / "cache.json"
    path.write_text(json.dumps(v1))
    monkeypatch.setenv(at.ENV_CACHE, str(path))

    at._MEMO.clear()
    loaded = at._load_cache(str(path))
    assert loaded["_format"] == at.CACHE_FORMAT_VERSION
    # v1 key gains a float32 dtype slot AND a device-kind slot (stand-in:
    # the key's backend segment — exact on CPU)
    assert f"3m_r8_float32_{backend}_{backend}_ref" in loaded
    assert "garbage key" not in loaded
    on_disk = json.loads(path.read_text())  # migration persisted
    assert on_disk.get("_format") == at.CACHE_FORMAT_VERSION
    # idempotent: migrating a migrated cache changes nothing
    assert at._migrate_v1(on_disk) == {k: v for k, v in on_disk.items()}

    kw = dict(variant="ref", nnz=256, tiles=(8,), block_ps=(64,),
              num_buffers_grid=(2,), repeats=1)
    hit = at.autotune_ec(3, 8, dtype=jnp.float32, **kw)
    assert dict(hit.timings) == {"t8_p64_b2": 1.0}  # served from migration
    at._MEMO.clear()
    miss = at.autotune_ec(3, 8, dtype=jnp.bfloat16, **kw)
    assert dict(miss.timings) != {"t8_p64_b2": 1.0}  # re-tuned, no replay


@pytest.mark.parametrize("variant", ["blocked", "fused", "sorted"])
def test_chunked_launch_matches_single_launch(variant, monkeypatch):
    """Splitting the grid into launches of a few blocks (a fori_loop over
    full chunks plus a tail) gives the single launch's output bit for bit:
    a tile that straddles two launches carries its partial sum through the
    aliased running output."""
    from repro.core.coo import random_sparse
    from repro.core.partition import block_segment_descriptors, partition_mode
    from repro.kernels.mttkrp_fused import ec_fused
    from repro.kernels.mttkrp_sorted import ec_sorted
    from repro.kernels import tpu_layout
    t = random_sparse((40, 18, 12), 600, seed=4, distribution="zipf")
    part, _, _ = partition_mode(t, 0, 1, replication=1, tile=8, block_p=16,
                                layout="sorted")
    assert part.nblocks > 7
    rng = np.random.default_rng(4)
    factors = [jnp.asarray(rng.normal(size=(s, 16)).astype(np.float32))
               for s in t.shape]
    ind = part.indices[0]
    vals, b2t = jnp.asarray(part.values[0]), jnp.asarray(part.block_to_tile[0])
    rit = jnp.asarray(part.local_rows[0] % part.tile)
    kw = dict(num_rows=part.rows_max, tile=part.tile, block_p=part.block_p,
              interpret=True)
    if variant == "blocked":
        gathered = [factors[w][ind[:, w]] for w in (1, 2)]
        run = lambda: ec_blocked(vals, rit, b2t, gathered, **kw)
    elif variant == "fused":
        idx = jnp.asarray(ind[:, 1:].T)
        run = lambda: ec_fused(vals, rit, b2t, idx, factors[1:], **kw)
    else:
        ss, sr = block_segment_descriptors(part.local_rows[0], tile=part.tile,
                                           block_p=part.block_p)
        idx = jnp.asarray(ind[:, 1:].T)
        run = lambda: ec_sorted(vals, jnp.asarray(ss), jnp.asarray(sr), b2t,
                                idx, factors[1:], **kw)
    single = np.asarray(run())
    monkeypatch.setattr(tpu_layout, "MAX_CHUNK_BLOCKS", 3)
    np.testing.assert_array_equal(np.asarray(run()), single)


# ---------------------------------------------------------------------------
# The ``ref`` variant (each block summed into its tile, one scatter-add over
# blocks) vs the slot-order oracle kernels/ref.py:mttkrp_local_ref
# ---------------------------------------------------------------------------

def _ref_and_oracle(indices, values, local_rows, b2t, factors, *, mode,
                    num_rows, tile=8, block_p=128):
    """(ops ``ref`` variant, slot-order oracle) on one shard, as numpy."""
    got = kops.mttkrp_local(
        jnp.asarray(indices), jnp.asarray(values), jnp.asarray(local_rows),
        jnp.asarray(b2t), factors, mode=mode, num_rows=num_rows, tile=tile,
        block_p=block_p, variant="ref")
    want = mttkrp_local_ref(jnp.asarray(indices), jnp.asarray(values),
                            jnp.asarray(local_rows), factors, mode, num_rows)
    return np.asarray(got), np.asarray(want)


def _integer_data(values, shapes, rank, seed):
    """Small integers in place of the values (pads stay 0) and the factors:
    every float32 partial sum is then exact, whatever the order."""
    rng = np.random.default_rng(seed)
    ints = rng.integers(1, 4, size=values.shape) * rng.choice([-1, 1],
                                                              values.shape)
    vals = np.where(values != 0, ints, 0).astype(np.float32)
    factors = [jnp.asarray(rng.integers(-3, 4, size=(s, rank))
                           .astype(np.float32)) for s in shapes]
    return vals, factors


def _normal_factors(shapes, rank, seed):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=(s, rank)).astype(np.float32))
            for s in shapes]


def _assert_close_to_oracle(got, want, indices, values, local_rows, factors,
                            mode, num_rows):
    """|ref − oracle| ≤ 1e-5 of the sum of the terms' magnitudes, row by row
    (a float32 reordering bound; exact cancellation leaves no relative
    scale)."""
    mag = np.asarray(mttkrp_local_ref(
        jnp.asarray(indices), jnp.abs(jnp.asarray(values)),
        jnp.asarray(local_rows), [jnp.abs(f) for f in factors], mode,
        num_rows))
    np.testing.assert_array_less(np.abs(got - want), 1e-5 * mag + 1e-30)


REF_LAYOUTS = ["blocked", "sorted"]


@pytest.mark.parametrize("layout", REF_LAYOUTS)
@pytest.mark.parametrize("nmodes", [3, 4, 5])
@pytest.mark.parametrize("rank", [8, 32])
def test_ref_ec_bitwise_on_integers(layout, nmodes, rank):
    """Integer values and factors: every sum is exact, so the two-level
    reduction equals the slot-order oracle bit for bit."""
    from repro.core.coo import random_sparse
    from repro.core.partition import partition_mode
    shape = tuple([24, 18, 12, 10, 8][:nmodes])
    t = random_sparse(shape, 900, seed=nmodes + rank, distribution="zipf")
    part, _, _ = partition_mode(t, 1, 1, replication=1, tile=8, block_p=128,
                                layout=layout)
    vals, factors = _integer_data(part.values[0], shape, rank, seed=rank)
    got, want = _ref_and_oracle(part.indices[0], vals, part.local_rows[0],
                                part.block_to_tile[0], factors, mode=1,
                                num_rows=part.rows_max)
    assert np.abs(want).max() > 0
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("layout", REF_LAYOUTS)
@pytest.mark.parametrize("nmodes", [3, 4, 5])
@pytest.mark.parametrize("rank", [8, 32])
def test_ref_ec_matches_oracle(layout, nmodes, rank):
    """N(0, 1) values and factors: agreement to float32 reordering."""
    from repro.core.coo import random_sparse
    from repro.core.partition import partition_mode
    shape = tuple([24, 18, 12, 10, 8][:nmodes])
    t = random_sparse(shape, 900, seed=nmodes * 7 + rank,
                      distribution="zipf")
    part, _, _ = partition_mode(t, 1, 1, replication=1, tile=8, block_p=128,
                                layout=layout)
    factors = _normal_factors(shape, rank, seed=rank + 1)
    args = (part.indices[0], part.values[0], part.local_rows[0])
    got, want = _ref_and_oracle(*args, part.block_to_tile[0], factors,
                                mode=1, num_rows=part.rows_max)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    _assert_close_to_oracle(got, want, *args, factors, 1, part.rows_max)


@pytest.mark.parametrize("layout", REF_LAYOUTS)
def test_ref_ec_pad_only_device(layout):
    """A device that owns no nonzeros holds only pad blocks: exact zeros."""
    from repro.core.coo import SparseTensor
    from repro.core.partition import partition_mode
    ind = np.zeros((50, 3), np.int32)
    ind[:, 1] = np.arange(50) % 7
    ind[:, 2] = np.arange(50) % 5
    t = SparseTensor(ind, np.ones(50, np.float32), (3, 7, 5))
    part, _, _ = partition_mode(t, 0, 2, strategy="amped_cdf", replication=1,
                                layout=layout)
    empty = int(np.argmin(part.nnz_true))
    assert part.nnz_true[empty] == 0 and part.nblocks >= 1
    factors = _normal_factors(t.shape, 8, seed=0)
    got, want = _ref_and_oracle(
        part.indices[empty], part.values[empty], part.local_rows[empty],
        part.block_to_tile[empty], factors, mode=0, num_rows=part.rows_max)
    np.testing.assert_array_equal(got, 0.0)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("data", ["integer", "normal"])
def test_ref_ec_heavy_row_spans_blocks(data):
    """One output row holds most nonzeros: its tile's run spans hundreds of
    blocks, which the block scatter adds into one tile."""
    from repro.core.coo import SparseTensor
    from repro.core.partition import partition_mode
    rng = np.random.default_rng(3)
    n, shape = 40_000, (20, 60, 50)
    ind = np.stack([np.where(rng.random(n) < 0.9, 5, rng.integers(0, 20, n)),
                    rng.integers(0, 60, n), rng.integers(0, 50, n)], 1)
    t = SparseTensor(ind.astype(np.int32),
                     rng.normal(size=n).astype(np.float32), shape)
    part, _, _ = partition_mode(t, 0, 1, replication=1, tile=8, block_p=128)
    b2t = part.block_to_tile[0]
    assert np.bincount(b2t).max() >= 256  # one tile, hundreds of blocks
    if data == "integer":
        vals, factors = _integer_data(part.values[0], shape, 16, seed=1)
    else:
        vals, factors = part.values[0], _normal_factors(shape, 16, seed=1)
    args = (part.indices[0], vals, part.local_rows[0])
    got, want = _ref_and_oracle(*args, b2t, factors, mode=0,
                                num_rows=part.rows_max)
    if data == "integer":
        np.testing.assert_array_equal(got, want)
    else:
        _assert_close_to_oracle(got, want, *args, factors, 0, part.rows_max)


@pytest.mark.parametrize("path", ["loop", "unrolled"])
def test_ref_ec_chunks_match_one_chunk(path, monkeypatch):
    """The shard in chunks of a few blocks, in a loop (factors that fit in
    VMEM together) or laid out one after another (factors that do not):
    tiles split across chunks keep their partial sums, so the output equals
    the one-chunk output bit for bit, and the oracle's on integer data."""
    from repro.core.coo import random_sparse
    from repro.core.partition import partition_mode
    t = random_sparse((40, 18, 12), 3000, seed=7, distribution="zipf")
    part, _, _ = partition_mode(t, 0, 1, replication=1, tile=8, block_p=16)
    assert part.nblocks > 20
    args = (part.indices[0], part.values[0], part.local_rows[0],
            part.block_to_tile[0])
    kw = dict(mode=0, num_rows=part.rows_max, block_p=part.block_p)
    factors = _normal_factors(t.shape, 16, seed=7)
    int_vals, int_factors = _integer_data(part.values[0], t.shape, 16, seed=7)
    one_chunk, _ = _ref_and_oracle(*args, factors, **kw)
    if path == "loop":
        monkeypatch.setattr(kops, "REF_LOOP_BLOCKS", 3)
    else:
        monkeypatch.setattr(kops, "REF_VMEM_FACTOR_BYTES", 0)
        # 5 blocks of 16 slots, two input modes of 128 padded floats
        monkeypatch.setattr(kops, "REF_CHUNK_BYTES", 5 * 16 * 2 * 512)
    got, _ = _ref_and_oracle(*args, factors, **kw)
    np.testing.assert_array_equal(got, one_chunk)
    got, want = _ref_and_oracle(args[0], int_vals, *args[2:], int_factors,
                                **kw)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("nmodes", [3, 4, 5])
@pytest.mark.parametrize("rank", [8, 16, 32, 64, 128])
def test_ref_ec_lane_packed_gather(nmodes, rank, monkeypatch):
    """Input factors that fit in VMEM only lane-packed (here: a VMEM budget
    of exactly their packed bytes): where R divides 128 the ``ref`` EC
    gathers their rows from lane-packed copies, 128 / R rows to a 128-lane
    row; no factor here has a multiple of 8 · (128 / R) rows, so every copy
    ends in pad rows. On integer data it equals the unpacked form (rows
    gathered from the factors as they are) and the slot-order oracle bit
    for bit; on N(0, 1) data the oracle to float32 rounding. At R = 128
    nothing is packed: the program is the unpacked form's."""
    from repro.core.coo import random_sparse
    from repro.core.partition import partition_mode
    shape = (45, 37, 29, 23, 19)[:nmodes]
    inputs = shape[:1] + shape[2:]
    k = 128 // rank
    assert all(s % (8 * k) for s in shape)
    monkeypatch.setattr(kops, "REF_VMEM_FACTOR_BYTES",
                        sum(-(-n // (8 * k)) * 8 for n in inputs) * 512)
    assert kops.ref_rows_per_lane_row(inputs, rank) == k
    assert kops.ref_takes_loop(inputs, rank)
    t = random_sparse(shape, 1200, seed=nmodes * 3 + rank,
                      distribution="zipf")
    part, _, _ = partition_mode(t, 1, 1, replication=1, tile=8, block_p=128)
    rows, b2t = part.local_rows[0], part.block_to_tile[0]
    kw = dict(mode=1, num_rows=part.rows_max)
    vals, int_factors = _integer_data(part.values[0], shape, rank, seed=rank)
    factors = _normal_factors(shape, rank, seed=rank + 2)

    def run(vals, factors):
        return _ref_and_oracle(part.indices[0], vals, rows, b2t, factors,
                               **kw)

    def program():
        return jax.jit(lambda *f: kops.mttkrp_local(
            jnp.asarray(part.indices[0]), jnp.asarray(vals),
            jnp.asarray(rows), jnp.asarray(b2t), list(f), tile=8,
            block_p=128, variant="ref", **kw)).lower(*factors).as_text()

    packed_ints, want = run(vals, int_factors)
    packed, oracle = run(part.values[0], factors)
    packed_text = program()
    monkeypatch.setattr(kops, "ref_rows_per_lane_row", lambda rows, r: 1)
    unpacked_ints, _ = run(vals, int_factors)
    assert np.abs(want).max() > 0
    np.testing.assert_array_equal(packed_ints, unpacked_ints)
    np.testing.assert_array_equal(packed_ints, want)
    _assert_close_to_oracle(packed, oracle, part.indices[0], part.values[0],
                            rows, factors, 1, part.rows_max)
    assert (packed_text == program()) == (k == 1)


def test_ref_ec_trailing_pad_blocks():
    """A light device of a two-device plan ends in whole pad blocks that
    revisit its last tile; they add exact zeros there."""
    from repro.core.coo import random_sparse
    from repro.core.partition import partition_mode
    t = random_sparse((64, 18, 12), 1500, seed=5, distribution="zipf")
    part, _, _ = partition_mode(t, 0, 2, strategy="amped_cdf",
                                replication=1, tile=8, block_p=128)
    real_blocks = -(-part.nnz_true // part.block_p)
    light = int(np.argmin(part.nnz_true))
    assert real_blocks[light] < part.nblocks  # trailing pad blocks exist
    b2t = part.block_to_tile[light]
    tail = b2t[real_blocks[light]:]
    assert (tail == tail[0]).all()
    for data in ("integer", "normal"):
        if data == "integer":
            vals, factors = _integer_data(part.values[light], t.shape, 8,
                                          seed=2)
        else:
            vals = part.values[light]
            factors = _normal_factors(t.shape, 8, seed=2)
        args = (part.indices[light], vals, part.local_rows[light])
        got, want = _ref_and_oracle(*args, b2t, factors, mode=0,
                                    num_rows=part.rows_max)
        if data == "integer":
            np.testing.assert_array_equal(got, want)
        else:
            _assert_close_to_oracle(got, want, *args, factors, 0,
                                    part.rows_max)


def test_ref_ec_super_shard_windows(tmp_path):
    """Streamed super-shard windows: each window's ``ref`` EC matches the
    oracle, and the windows' partials add up to the resident shard's
    ``ref`` EC bit for bit (each tile's blocks meet in one window)."""
    from repro.core.coo import random_sparse
    from repro.store import (TensorStore, build_plan_from_store,
                             split_mode_super_shards, write_store_from_coo)
    t = random_sparse((96, 18, 12), 3000, seed=6, distribution="zipf")
    path = str(tmp_path / "s.store")
    write_store_from_coo(t, path, chunk_nnz=256)
    part = build_plan_from_store(TensorStore(path), 1, replication=1).modes[0]
    nnz_cap_full = part.device_arrays(0)[1].shape[0]
    sp = split_mode_super_shards(
        part, max(64 * 1024, nnz_cap_full * (4 * 3 + 8 + 4) // 3))
    assert len([w for w in sp.windows[0] if w != (0, 0)]) > 1
    factors = _normal_factors(t.shape, 16, seed=3)
    kw = dict(mode=0, num_rows=part.rows_max)
    acc = np.zeros((part.rows_max, 16), np.float32)
    for (t0, t1) in sp.windows[0]:
        wi, wv, wr, b2t, _ = part.super_shard_arrays(
            0, t0, t1, nnz_cap=sp.nnz_cap, nblocks=sp.nblocks)
        got, want = _ref_and_oracle(wi, wv, wr, b2t, factors, **kw)
        _assert_close_to_oracle(got, want, wi, wv, wr, factors, 0,
                                part.rows_max)
        acc = acc + got
    di, dv, dr = part.device_arrays(0)
    resident, _ = _ref_and_oracle(di, dv, dr, part.block_to_tile[0],
                                  factors, **kw)
    np.testing.assert_array_equal(acc, resident)
