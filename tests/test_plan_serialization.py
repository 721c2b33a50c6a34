"""Plan serialization: save_plan/load_plan round-trip every ModePartition
array bit-exactly, and stale-signature plans are rejected, never silently
reused."""
import dataclasses
import json
import os

import numpy as np
import pytest

import repro.api as api
from repro.core.coo import random_sparse
from repro.core.partition import ModePartition, build_plan


@pytest.fixture(scope="module")
def plan3():
    t = random_sparse((40, 30, 20), 600, seed=7, distribution="zipf")
    return build_plan(t, 1)


def test_roundtrip_bit_exact(plan3, tmp_path):
    path = api.save_plan(plan3, str(tmp_path / "p"), signature="sig0")
    back = api.load_plan(path)
    assert back.shape == plan3.shape
    assert back.num_devices == plan3.num_devices
    assert back.norm == plan3.norm
    assert back.nmodes == plan3.nmodes
    for d in range(plan3.nmodes):
        orig, got = plan3.modes[d], back.modes[d]
        for k in ModePartition.META_FIELDS:
            assert getattr(got, k) == getattr(orig, k), k
        for k in ModePartition.ARRAY_FIELDS:
            a, b = getattr(orig, k), getattr(got, k)
            assert a.dtype == b.dtype, k          # bit-exact: dtype included
            np.testing.assert_array_equal(a, b, err_msg=k)
        np.testing.assert_array_equal(plan3.global_to_padded[d],
                                      back.global_to_padded[d])
        np.testing.assert_array_equal(plan3.padded_to_global[d],
                                      back.padded_to_global[d])


def test_stale_signature_rejected(plan3, tmp_path):
    path = api.save_plan(plan3, str(tmp_path / "p"), signature="sig0")
    api.load_plan(path, expect_signature="sig0")  # matching: fine
    with pytest.raises(api.PlanSignatureError, match="different problem"):
        api.load_plan(path, expect_signature="sig-other")


def test_format_version_rejected(plan3, tmp_path):
    path = api.save_plan(plan3, str(tmp_path / "p"))
    mpath = os.path.join(path, "manifest.json")
    man = json.load(open(mpath))
    man["format_version"] = 99
    json.dump(man, open(mpath, "w"))
    with pytest.raises(api.PlanSignatureError, match="format"):
        api.load_plan(path)


def test_cache_never_reuses_across_tensors(tmp_path):
    """Same cache dir, different tensor (nnz) or strategy → rebuild."""
    cfg = api.preset("paper", {"runtime.num_devices": 1})
    t1 = random_sparse((40, 30, 20), 600, seed=7, distribution="zipf")
    t2 = random_sparse((40, 30, 20), 700, seed=7, distribution="zipf")
    api.reset_cache_stats()
    api.plan(t1, cfg, cache_dir=str(tmp_path))
    api.plan(t2, cfg, cache_dir=str(tmp_path))            # different nnz
    api.plan(t1, cfg.with_overrides({"partition.strategy": "uniform_index"}),
             cache_dir=str(tmp_path))                     # different strategy
    assert api.CACHE_STATS == {"hits": 0, "misses": 3}
    p2 = api.plan(t2, cfg, cache_dir=str(tmp_path))       # t2 again: a hit
    assert api.CACHE_STATS["hits"] == 1
    assert p2.modes[0].nnz_true.sum() == t2.nnz


def test_corrupted_cache_entry_rebuilds(tmp_path, small_tensor=None):
    t = random_sparse((30, 20, 10), 300, seed=1)
    cfg = api.preset("paper", {"runtime.num_devices": 1})
    api.plan(t, cfg, cache_dir=str(tmp_path))
    # truncate the arrays file of the single cache entry
    (entry,) = os.listdir(tmp_path)
    with open(os.path.join(tmp_path, entry, "arrays.npz"), "wb") as f:
        f.write(b"garbage")
    api.reset_cache_stats()
    p = api.plan(t, cfg, cache_dir=str(tmp_path))         # rebuilds, no raise
    assert api.CACHE_STATS == {"hits": 0, "misses": 1}
    assert p.modes[0].nnz_true.sum() == t.nnz
    # and the rewritten entry is valid again
    api.plan(t, cfg, cache_dir=str(tmp_path))
    assert api.CACHE_STATS["hits"] == 1


@pytest.fixture(scope="module")
def packed_store(tmp_path_factory):
    from repro.store import TensorStore, write_store_from_coo
    t = random_sparse((400, 120, 90), 6000, seed=3, distribution="zipf")
    path = str(tmp_path_factory.mktemp("packed") / "t.store")
    write_store_from_coo(t, path, chunk_nnz=1024)
    return TensorStore(path)


@pytest.mark.parametrize("lazy", [False, True])
def test_packed_four_device_plan_recovers_owners(packed_store, lazy,
                                                 tmp_path):
    """A 4-device plan in the packed layout (CDF over 4 groups) survives
    save_plan/load_plan: the row translations and rows owned round-trip,
    and the owner of every index is recovered from its packed row and the
    groups' offsets (a store-backed plan rebuilds its layouts from them)."""
    from repro.core.partition import mode_layout, owner_from_packed
    from repro.store import build_plan_from_store
    plan = build_plan_from_store(packed_store, 4, replication=1)
    if not lazy:
        plan = dataclasses.replace(
            plan, modes=tuple(p.materialize() for p in plan.modes))
    back = api.load_plan(api.save_plan(plan, str(tmp_path / "p")))
    for d in range(3):
        lay = mode_layout(packed_store.mode_histogram(d), d, 4,
                          replication=1)
        part = back.modes[d]
        assert part.n_groups == 4
        assert part.padded_rows < part.n_groups * part.rows_max
        np.testing.assert_array_equal(part.rows_owned, lay.rows_owned)
        np.testing.assert_array_equal(back.global_to_padded[d],
                                      lay.global_to_padded)
        np.testing.assert_array_equal(
            owner_from_packed(back.global_to_padded[d], part.rows_owned),
            lay.owner)
        if lazy:
            np.testing.assert_array_equal(part.layout.owner, lay.owner)
            for dev in range(4):
                for a, b in zip(part.device_arrays(dev),
                                plan.modes[d].device_arrays(dev)):
                    np.testing.assert_array_equal(a, b)


def test_format_3_plan_rejected(plan3, tmp_path):
    """A plan written before the packed layout (format 3: translations
    into ``n_groups * rows_max`` slabs) is refused by its version, never
    read as packed."""
    path = api.save_plan(plan3, str(tmp_path / "p"))
    mpath = os.path.join(path, "manifest.json")
    man = json.load(open(mpath))
    assert man["format_version"] == 4
    man["format_version"] = 3
    json.dump(man, open(mpath, "w"))
    with pytest.raises(api.PlanSignatureError, match="format 3, expected 4"):
        api.load_plan(path)
