"""Out-of-core tensor store: format round-trip, plan-from-stats (zero chunk
reads, asserted via access instrumentation), bit-identity of the streamed
per-device shards with the in-memory partition path, bounded-memory
materialization (tracemalloc on a tensor 10x the chunk size), chunk
skipping on clustered files, and the end-to-end
convert -> TensorStore -> api.plan -> CPSolver pipeline producing factors
bit-identical to the SparseTensor path."""
import json
import os
import subprocess
import sys
import tracemalloc

import numpy as np
import pytest

import repro.api as api
from repro.core.coo import SparseTensor, random_sparse
from repro.core.partition import ModePartition, build_plan
from repro.sparse.io import write_tns
from repro.store import (OutOfCoreError, StoreFormatError, StoreWriter,
                         TensorStore, build_plan_from_store, convert_tns,
                         write_profile_store, write_store_from_coo)
from repro.store import format as store_fmt


@pytest.fixture(scope="module")
def dup_tensor():
    """Zipf tensor WITH duplicate coordinates — duplicates are what make
    arrival-order stability observable in the blocked layout."""
    return random_sparse((200, 60, 30), 5000, seed=3, distribution="zipf",
                         dedup=False)


@pytest.fixture(scope="module")
def dup_store(dup_tensor, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("store") / "dup.store")
    write_store_from_coo(dup_tensor, path, chunk_nnz=512)
    return TensorStore(path)


# -- format / round-trip ----------------------------------------------------

def test_store_roundtrip_exact(dup_tensor, dup_store):
    st = dup_store
    assert st.shape == dup_tensor.shape
    assert st.nnz == dup_tensor.nnz
    assert st.num_chunks == -(-dup_tensor.nnz // 512)
    back = st.to_coo()
    np.testing.assert_array_equal(back.indices, dup_tensor.indices)
    np.testing.assert_array_equal(back.values, dup_tensor.values)
    assert abs(st.norm() - dup_tensor.norm()) < 1e-6 * dup_tensor.norm()


def test_index_dtypes_minimized(tmp_path):
    t = SparseTensor(np.array([[70000, 3, 1], [1, 2, 0]], np.int64),
                     np.ones(2, np.float32), (70001, 8, 8))
    write_store_from_coo(t, str(tmp_path / "s"))
    st = TensorStore(str(tmp_path / "s"))
    assert st.index_dtypes == ["<u4", "<u2", "<u2"]
    np.testing.assert_array_equal(st.to_coo().indices, t.indices)


def test_manifest_stats(dup_tensor, dup_store):
    st = dup_store
    man = st.manifest
    # exact per-mode histograms come from the binary sidecars
    for d in range(3):
        np.testing.assert_array_equal(st.mode_histogram(d),
                                      dup_tensor.mode_histogram(d))
    # per-chunk min/max and binned histograms match the chunk data
    for k, cstats in enumerate(man["chunks"]):
        ind, _ = st.read_chunk(k)
        for d in range(3):
            assert cstats["min"][d] == int(ind[:, d].min())
            assert cstats["max"][d] == int(ind[:, d].max())
            assert sum(cstats["hist"][d]) == ind.shape[0]
    assert sum(c["nnz"] for c in man["chunks"]) == st.nnz


def test_digest_stable_and_content_keyed(dup_tensor, tmp_path):
    write_store_from_coo(dup_tensor, str(tmp_path / "a"), chunk_nnz=512)
    write_store_from_coo(dup_tensor, str(tmp_path / "b"), chunk_nnz=512)
    assert TensorStore(str(tmp_path / "a")).digest == \
        TensorStore(str(tmp_path / "b")).digest
    t2 = SparseTensor(dup_tensor.indices,
                      dup_tensor.values * np.float32(2.0), dup_tensor.shape)
    write_store_from_coo(t2, str(tmp_path / "c"), chunk_nnz=512)
    assert TensorStore(str(tmp_path / "c")).digest != \
        TensorStore(str(tmp_path / "a")).digest


def test_corruption_detected(dup_tensor, tmp_path):
    path = str(tmp_path / "s")
    write_store_from_coo(dup_tensor, path, chunk_nnz=512)
    # truncated data file
    vpath = os.path.join(path, store_fmt.VALUES_NAME)
    with open(vpath, "r+b") as f:
        f.truncate(os.path.getsize(vpath) - 8)
    with pytest.raises(StoreFormatError, match="truncated|bytes"):
        TensorStore(path)


def test_manifest_tamper_detected(dup_tensor, tmp_path):
    path = str(tmp_path / "s")
    write_store_from_coo(dup_tensor, path, chunk_nnz=512)
    mpath = os.path.join(path, store_fmt.MANIFEST_NAME)
    man = json.load(open(mpath))
    man["nnz"] = man["nnz"] - 1
    json.dump(man, open(mpath, "w"))
    with pytest.raises(StoreFormatError, match="digest"):
        TensorStore(path)
    # a stripped digest is a clear format error, not a KeyError
    man["nnz"] = man["nnz"] + 1
    del man["digest"]
    json.dump(man, open(mpath, "w"))
    with pytest.raises(StoreFormatError, match="digest"):
        TensorStore(path)


def test_writer_validation(tmp_path):
    with pytest.raises(ValueError, match="out of range"):
        with StoreWriter(str(tmp_path / "w1"), (4, 4)) as w:
            w.append(np.array([[4, 0]]), np.ones(1, np.float32))
    with pytest.raises(ValueError, match="empty"):
        StoreWriter(str(tmp_path / "w2"), (4, 4)).close()
    w = StoreWriter(str(tmp_path / "w3"), (4, 4), chunk_nnz=2)
    w.append(np.array([[0, 1], [1, 2], [3, 3]]), np.ones(3, np.float32))
    w.close()
    with pytest.raises(RuntimeError, match="closed"):
        w.append(np.array([[0, 0]]), np.ones(1, np.float32))
    # re-chunking across ragged appends preserved order
    st = TensorStore(str(tmp_path / "w3"))
    assert st.num_chunks == 2 and st.nnz == 3


def test_convert_tns_matches_read_tns(dup_tensor, tmp_path):
    from repro.sparse.io import read_tns
    for name in ("t.tns", "t.tns.gz"):
        tns = str(tmp_path / name)
        write_tns(tns, dup_tensor)
        report = convert_tns(tns, str(tmp_path / (name + ".store")),
                             chunk_nnz=700)
        assert report["nnz"] == dup_tensor.nnz
        assert report["nnz_per_s"] > 0
        st = TensorStore(str(tmp_path / (name + ".store")))
        mem = read_tns(tns)
        assert st.shape == mem.shape  # pass-1 shape detection
        back = st.to_coo()
        np.testing.assert_array_equal(back.indices, mem.indices)
        np.testing.assert_array_equal(back.values, mem.values)


def test_slice_for_device_streams_range(dup_store, dup_tensor):
    got_i, got_v = [], []
    for ind, val in dup_store.slice_for_device(0, 10, 40):
        assert ((ind[:, 0] >= 10) & (ind[:, 0] <= 40)).all()
        got_i.append(ind)
        got_v.append(val)
    keep = (dup_tensor.indices[:, 0] >= 10) & (dup_tensor.indices[:, 0] <= 40)
    np.testing.assert_array_equal(np.concatenate(got_i),
                                  dup_tensor.indices[keep])
    np.testing.assert_array_equal(np.concatenate(got_v),
                                  dup_tensor.values[keep])


# -- plan-from-stats --------------------------------------------------------

def test_plan_reads_no_chunk_data(dup_store):
    """Acceptance: api.plan on a TensorStore partitions from manifest
    histograms only — zero chunk reads, counted by the store itself."""
    cfg = api.paper({"runtime.num_devices": 4, "partition.replication": 2})
    dup_store.reset_access_stats()
    plan = api.plan(dup_store, cfg)
    assert dup_store.access_stats["chunk_reads"] == 0
    assert dup_store.access_stats["nnz_read"] == 0
    assert dup_store.access_stats["hist_reads"] > 0  # stats were consumed
    assert plan.num_devices == 4 and plan.modes[0].lazy


@pytest.mark.parametrize("m,strategy,repl", [
    (1, "amped_cdf", 1),
    (4, "amped_cdf", 1),
    (4, "amped_cdf", 2),
    (4, "equal_nnz", None),   # r = m: the linspace rank split inside groups
    (4, "uniform_index", None),
    (4, "amped_lpt", 1),      # scattered (non-contiguous) group ownership
    (8, "amped_cdf", None),   # auto replication
])
def test_partition_bit_identity(dup_tensor, dup_store, m, strategy, repl):
    """Every strategy, device count, and replication factor: the streamed
    store partition equals the in-memory partition bit-for-bit — metadata,
    cheap arrays, and each device's materialized slice."""
    pm = build_plan(dup_tensor, m, strategy=strategy, replication=repl)
    ps = build_plan_from_store(dup_store, m, strategy=strategy,
                               replication=repl)
    for d in range(3):
        a, b = pm.modes[d], ps.modes[d]
        for k in ModePartition.META_FIELDS:
            assert getattr(a, k) == getattr(b, k), k
        assert a.nnz_max == b.nnz_max
        for k in ("block_to_tile", "tile_visited", "nnz_true", "rows_owned",
                  "blocks_true"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a, k)), np.asarray(getattr(b, k)),
                err_msg=k)
        np.testing.assert_array_equal(pm.global_to_padded[d],
                                      ps.global_to_padded[d])
        np.testing.assert_array_equal(pm.padded_to_global[d],
                                      ps.padded_to_global[d])
        for dev in range(m):
            di, dv, dr = b.device_arrays(dev)
            assert di.dtype == a.indices.dtype
            assert dr.dtype == a.local_rows.dtype
            np.testing.assert_array_equal(di, a.indices[dev])
            np.testing.assert_array_equal(dv, a.values[dev])
            np.testing.assert_array_equal(dr, a.local_rows[dev])


@pytest.mark.parametrize("strategy,repl", [("amped_cdf", 1),
                                           ("amped_cdf", 2),
                                           ("amped_lpt", 1)])
def test_lazy_plan_has_the_packed_layout(dup_tensor, dup_store, strategy,
                                         repl):
    """A store-built lazy plan lays each mode out exactly as the in-memory
    plan does in the packed ownership layout: same owners, offsets, packed
    factor rows and translations, fewer rows than the slabs the wire
    carries, and each group's local rows at its packed rows less its
    offset."""
    from repro.core.partition import mode_layout
    pm = build_plan(dup_tensor, 4, strategy=strategy, replication=repl)
    ps = build_plan_from_store(dup_store, 4, strategy=strategy,
                               replication=repl)
    for d in range(3):
        lay = ps.modes[d].layout
        ref = mode_layout(dup_tensor.mode_histogram(d), d, 4,
                          strategy=strategy, replication=repl)
        np.testing.assert_array_equal(lay.owner, ref.owner)
        np.testing.assert_array_equal(lay.offsets, ref.offsets)
        assert lay.padded_rows == ref.padded_rows == pm.modes[d].padded_rows \
            == ps.modes[d].padded_rows < lay.n_groups * lay.rows_max
        np.testing.assert_array_equal(lay.global_to_padded,
                                      pm.global_to_padded[d])
        part = pm.modes[d]
        for dev in range(4):
            g = dev // part.r
            real = part.values[dev] != 0
            np.testing.assert_array_equal(
                part.indices[dev][real][:, d],
                lay.offsets[g] + part.local_rows[dev][real])


def test_whole_array_access_guarded(dup_store):
    part = build_plan_from_store(dup_store, 4).modes[0]
    for field in ("indices", "values", "local_rows"):
        with pytest.raises(OutOfCoreError, match="device_arrays"):
            getattr(part, field)


def test_materialize_equals_in_memory(dup_tensor, dup_store):
    pm = build_plan(dup_tensor, 2)
    part = build_plan_from_store(dup_store, 2).modes[1].materialize()
    np.testing.assert_array_equal(part.indices, pm.modes[1].indices)
    np.testing.assert_array_equal(part.values, pm.modes[1].values)


# -- bounded memory ---------------------------------------------------------

def _traced_peak(fn):
    tracemalloc.start()
    tracemalloc.reset_peak()
    out = fn()
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    return out, peak


def test_store_path_never_materializes_full_index_array(tmp_path):
    """Acceptance: on a tensor 10x+ the chunk size, neither planning nor
    per-device materialization allocates the full (nnz, nmodes) index
    array — planning stays O(index space), device arrays O(nnz/m +
    chunk). numpy reports its allocations to tracemalloc."""
    chunk = 1000
    t = random_sparse((512, 96, 64), 80_000, seed=11, distribution="zipf",
                      dedup=False)
    assert t.nnz >= 10 * chunk
    full_index_bytes = t.indices.nbytes  # (nnz, 3) int32
    path = str(tmp_path / "big.store")
    write_store_from_coo(t, path, chunk_nnz=chunk)
    st = TensorStore(path)

    plan, plan_peak = _traced_peak(lambda: build_plan_from_store(st, 16))
    assert plan_peak < full_index_bytes // 2, (plan_peak, full_index_bytes)

    part = plan.modes[0]
    (_, _, _), dev_peak = _traced_peak(lambda: part.device_arrays(0))
    assert dev_peak < full_index_bytes // 2, (dev_peak, full_index_bytes)
    # sanity: the in-memory path DOES pay the full array (the thing the
    # store path avoids); its per-mode copies are >= the index array alone
    mem_part = build_plan(t, 16).modes[0]
    assert mem_part.indices.nbytes >= full_index_bytes


def test_chunk_skipping_on_clustered_file(tmp_path):
    """A mode-sorted file (FROSTT files usually are) gives tight per-chunk
    index ranges; a device's materialization must then skip chunks outside
    its owned range instead of scanning the whole store."""
    t = random_sparse((512, 96, 64), 20_000, seed=2, distribution="zipf",
                      dedup=False).sorted_by_mode(0)
    path = str(tmp_path / "sorted.store")
    write_store_from_coo(t, path, chunk_nnz=500)
    st = TensorStore(path)
    plan = build_plan_from_store(st, 4)
    st.reset_access_stats()
    plan.modes[0].device_arrays(0)
    reads = st.access_stats["chunk_reads"]
    assert 0 < reads <= st.num_chunks // 2, (reads, st.num_chunks)
    # correctness unaffected: same arrays as the in-memory path
    pm = build_plan(t, 4)
    di, dv, dr = plan.modes[0].device_arrays(0)
    np.testing.assert_array_equal(di, pm.modes[0].indices[0])


# -- end-to-end through the public API --------------------------------------

def test_e2e_store_solver_bit_identical(dup_tensor, tmp_path):
    """Acceptance: the same .tns file through both pipelines —
    read_tns -> api.plan -> CPSolver   vs
    convert_tns -> TensorStore -> api.plan -> CPSolver —
    produces bit-identical factors."""
    from repro.sparse.io import read_tns
    tns = str(tmp_path / "e2e.tns.gz")
    write_tns(tns, dup_tensor)
    convert_tns(tns, str(tmp_path / "e2e.store"), chunk_nnz=600)

    cfg = api.paper({"rank": 8, "runtime.tol": 0.0,
                     "runtime.num_devices": 1})
    with api.compile(api.plan(read_tns(tns), cfg), cfg) as s1:
        r1 = s1.run(3)
    with api.compile(
            api.plan(TensorStore(str(tmp_path / "e2e.store")), cfg),
            cfg) as s2:
        r2 = s2.run(3)
    assert r1.fits[-1] == pytest.approx(r2.fits[-1], abs=1e-7)
    for a, b in zip(r1.factors, r2.factors):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_lazy_plan_cache_roundtrip(dup_store, tmp_path):
    cfg = api.paper({"runtime.num_devices": 2})
    api.reset_cache_stats()
    p1 = api.plan(dup_store, cfg, cache_dir=str(tmp_path))
    p2 = api.plan(dup_store, cfg, cache_dir=str(tmp_path))
    assert api.CACHE_STATS == {"hits": 1, "misses": 1}
    assert p2.modes[0].lazy
    for d in range(3):
        for dev in range(2):
            a = p1.modes[d].device_arrays(dev)
            b = p2.modes[d].device_arrays(dev)
            for x, y in zip(a, b):
                np.testing.assert_array_equal(x, y)


def test_lazy_plan_cache_rejects_rewritten_store(dup_tensor, tmp_path):
    """A cached lazy plan must not rebind to a store whose content changed
    under the same path — the digest check forces a rebuild."""
    path = str(tmp_path / "s")
    write_store_from_coo(dup_tensor, path, chunk_nnz=512)
    cfg = api.paper({"runtime.num_devices": 2})
    cache = str(tmp_path / "plans")
    api.plan(TensorStore(path), cfg, cache_dir=cache)
    # rewrite the store with different values at the same path
    t2 = SparseTensor(dup_tensor.indices,
                      dup_tensor.values * np.float32(3.0), dup_tensor.shape)
    write_store_from_coo(t2, path, chunk_nnz=512)
    api.reset_cache_stats()
    p = api.plan(TensorStore(path), cfg, cache_dir=cache)
    assert api.CACHE_STATS["misses"] == 1  # new digest -> new entry
    assert float(p.norm) == pytest.approx(3.0 * dup_tensor.norm(), rel=1e-6)


def test_store_rebalance_gated(dup_store):
    cfg = api.paper({"runtime.num_devices": 1, "schedule.rebalance": "on"})
    plan = api.plan(dup_store, cfg)
    with pytest.raises(ValueError, match="out-of-core"):
        api.compile(plan, cfg)


# -- store-native synthetic generator ---------------------------------------

def test_profile_store_generator(tmp_path):
    a = str(tmp_path / "a")
    b = str(tmp_path / "b")
    write_profile_store("twitch", a, scale=2e-6, seed=4, chunk_nnz=256)
    write_profile_store("twitch", b, scale=2e-6, seed=4, chunk_nnz=256)
    sa, sb = TensorStore(a), TensorStore(b)
    assert sa.digest == sb.digest  # deterministic
    assert sa.nnz == max(64, round(474_676_555 * 2e-6))
    assert len(sa.shape) == 5
    # zipf head-heaviness survives the chunked draw
    h0 = sa.mode_histogram(0)
    assert h0[:8].sum() > 0.3 * sa.nnz
    # a different seed re-keys
    write_profile_store("twitch", str(tmp_path / "c"), scale=2e-6, seed=5,
                        chunk_nnz=256)
    assert TensorStore(str(tmp_path / "c")).digest != sa.digest
    # and the generated store plans + solves
    cfg = api.paper({"rank": 4, "runtime.num_devices": 1,
                     "runtime.tol": 0.0})
    with api.compile(api.plan(sa, cfg), cfg) as solver:
        res = solver.run(1)
    assert np.isfinite(res.fits[-1])


# -- multi-device lazy shard placement --------------------------------------

MULTIDEV_SCRIPT = r"""
import json
import numpy as np
import jax
assert jax.device_count() == 4, jax.device_count()

import repro.api as api
from repro.core.coo import random_sparse
from repro.store import TensorStore, write_store_from_coo

t = random_sparse((120, 50, 30), 6000, seed=9, distribution="zipf",
                  dedup=False)
write_store_from_coo(t, "{store}", chunk_nnz=500)
st = TensorStore("{store}")

cfg = api.paper({{"rank": 8, "runtime.tol": 0.0,
                  "partition.replication": 2}})
with api.compile(api.plan(t, cfg), cfg) as s1:
    r1 = s1.run(2)
st.reset_access_stats()
plan = api.plan(st, cfg)
planned_reads = dict(st.access_stats)
with api.compile(plan, cfg) as s2:
    r2 = s2.run(2)
identical = all((np.asarray(a) == np.asarray(b)).all()
                for a, b in zip(r1.factors, r2.factors))
print("RESULT_JSON:" + json.dumps({{
    "identical": identical,
    "fit_mem": float(r1.fits[-1]), "fit_store": float(r2.fits[-1]),
    "plan_chunk_reads": planned_reads["chunk_reads"],
    "compile_chunk_reads": st.access_stats["chunk_reads"]}}))
"""


@pytest.mark.slow
def test_multidevice_store_solver(tmp_path):
    """4 forced host devices: the lazy per-device shard placement feeds a
    real (2, 2) mesh and the solve stays bit-identical to the in-memory
    path; planning reads zero chunks, compile streams them."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    script = MULTIDEV_SCRIPT.format(store=str(tmp_path / "md.store"))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("RESULT_JSON:"))
    out = json.loads(line[len("RESULT_JSON:"):])
    assert out["identical"], out
    assert out["plan_chunk_reads"] == 0, out
    assert out["compile_chunk_reads"] > 0, out
