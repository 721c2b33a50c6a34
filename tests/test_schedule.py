"""Unit tests for the scheduling subsystem (repro.schedule): cost model,
static policies, migration planning, and the incremental rebalance apply.
All host/single-device — the end-to-end solver behaviour on 4 virtual
devices lives in test_schedule_multidevice.py."""
import numpy as np
import pytest

import repro.api as api
from repro.core.coo import SparseTensor, random_sparse
from repro.core.partition import build_plan, packed_offsets
from repro.schedule import cost as cost_mod
from repro.schedule import static as static_mod
from repro.schedule.rebalance import (ReplanDecision, apply_rebalance,
                                      imbalance_ratio,
                                      measure_mode_device_times,
                                      plan_group_migrations)


def skewed_tensor(nnz=8000, seed=0):
    """Hot-index mode 0: a few indices carry most nonzeros, the rest
    scatter — equal-nnz member chunks execute very different block counts."""
    rng = np.random.default_rng(seed)
    hot = nnz * 6 // 10
    i0 = np.concatenate([rng.integers(0, 3, hot),
                         rng.integers(3, 1024, nnz - hot)])
    ind = np.stack([i0, rng.integers(0, 40, nnz), rng.integers(0, 40, nnz)],
                   axis=1).astype(np.int32)
    return SparseTensor(ind, rng.standard_normal(nnz).astype(np.float32),
                        (1024, 40, 40)).deduplicated()


# -- cost model ---------------------------------------------------------------

def test_index_work_default_is_histogram():
    hist = np.array([5, 0, 3, 100], np.int64)
    np.testing.assert_array_equal(cost_mod.index_work(hist),
                                  hist.astype(np.float64))


def test_fit_coefficients_recovers_linear_model():
    rng = np.random.default_rng(0)
    nnz = rng.integers(1000, 50000, 32).astype(np.float64)
    slots = nnz * rng.uniform(1.0, 3.0, 32)  # slots >= nnz with padding
    feats = np.stack([nnz, slots, np.ones(32)], axis=1)
    true = cost_mod.CostCoefficients(sec_per_nnz=2e-9, sec_per_slot=5e-9,
                                     sec_fixed=1e-4)
    times = feats @ true.as_array()
    got = cost_mod.fit_coefficients(feats, times)
    assert got.sec_per_nnz == pytest.approx(true.sec_per_nnz, rel=1e-6)
    assert got.sec_per_slot == pytest.approx(true.sec_per_slot, rel=1e-6)
    assert got.sec_fixed == pytest.approx(true.sec_fixed, rel=1e-4)


def test_fit_coefficients_never_negative():
    rng = np.random.default_rng(1)
    feats = np.stack([rng.uniform(1, 2, 16), rng.uniform(1e5, 2e5, 16),
                      np.ones(16)], axis=1)
    times = feats[:, 1] * 1e-8  # slot-dominated; nnz column is noise-level
    got = cost_mod.fit_coefficients(feats, times)
    assert got.sec_per_nnz >= 0 and got.sec_per_slot >= 0 \
        and got.sec_fixed >= 0


def test_ewma_cost_model_smooths():
    m = cost_mod.EwmaCostModel(alpha=0.5)
    feats = np.array([[100.0, 200.0, 1.0], [50.0, 400.0, 1.0],
                      [10.0, 900.0, 1.0]])
    c1 = m.update(feats, feats @ np.array([1e-9, 2e-9, 0.0]))
    assert c1.sec_per_slot == pytest.approx(2e-9, rel=1e-6)
    c2 = m.update(feats, feats @ np.array([1e-9, 4e-9, 0.0]))
    assert c2.sec_per_slot == pytest.approx(3e-9, rel=1e-5)  # EWMA midpoint


def test_device_features_and_exchange_bytes(small_tensor):
    plan = build_plan(small_tensor, 4, strategy="equal_nnz")
    part = plan.modes[0]
    feats = cost_mod.device_features(part)
    assert feats.shape == (4, 3)
    np.testing.assert_array_equal(feats[:, 0], part.nnz_true)
    np.testing.assert_array_equal(feats[:, 1],
                                  part.blocks_true * part.block_p)
    assert cost_mod.exchange_bytes(part, rank=8) > 0
    summary = cost_mod.mode_cost_summary(part, rank=8)
    assert summary["modelled_imbalance"] >= 1.0


# -- static policies ----------------------------------------------------------

def test_policies_match_registry():
    assert set(static_mod.POLICIES) == {"amped_cdf", "amped_lpt",
                                        "uniform_index", "equal_nnz"}
    with pytest.raises(ValueError):
        static_mod.get_policy("nope")


def test_equal_nnz_forces_full_replication():
    pol = static_mod.get_policy("equal_nnz")
    assert pol.replication(np.ones(10), 8) == 8
    assert static_mod.get_policy("amped_cdf").replication(np.ones(10), 8) \
        is None


def test_cdf_policy_uses_cost_model():
    """A per-row cost shifts CDF splits: with row cost dominating, the split
    approaches uniform-index; with pure nnz cost it follows the histogram."""
    hist = np.zeros(100, np.int64)
    hist[:10] = 1000  # hot head
    pol = static_mod.get_policy("amped_cdf")
    by_nnz = pol.assign(hist, 2)
    rowly = pol.assign(hist, 2, cost_mod.CostCoefficients(
        sec_per_nnz=1.0, sec_per_row=1e6))
    # nnz split puts the boundary inside the hot head; row-cost split at 50
    assert (by_nnz == 0).sum() < (rowly == 0).sum()
    assert abs(int((rowly == 0).sum()) - 50) <= 1


@pytest.mark.parametrize("name", ["amped_cdf", "amped_lpt", "uniform_index",
                                  "equal_nnz"])
def test_policy_assign_is_valid_cover(name):
    hist = np.random.default_rng(3).integers(0, 50, 200)
    owner = static_mod.get_policy(name).assign(hist, 4)
    assert owner.shape == (200,)
    assert owner.min() >= 0
    n_groups = 1 if name == "equal_nnz" else 4
    assert owner.max() < n_groups


# -- telemetry probe ----------------------------------------------------------

def test_measure_mode_device_times_shape_and_cache(small_tensor):
    plan = build_plan(small_tensor, 4, strategy="equal_nnz")
    part = plan.modes[0]
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    factors = [jnp.asarray(rng.normal(size=(plan.modes[w].padded_rows, 4))
                           .astype(np.float32)) for w in range(3)]
    cache = {}
    t = measure_mode_device_times(part, factors, jit_cache=cache)
    assert t.shape == (4,) and (t > 0).all()
    assert len(cache) >= 1
    n = len(cache)
    measure_mode_device_times(part, factors, jit_cache=cache)
    assert len(cache) == n  # second probe reuses compiled fns


# -- migration planning -------------------------------------------------------

def _plan_and_part(t, strategy="equal_nnz", devices=4):
    plan = build_plan(t, devices, strategy=strategy)
    return plan, plan.modes[0]


def test_migrations_block_granular_and_budgeted():
    t = skewed_tensor()
    _, part = _plan_and_part(t)
    # slow member 3, fast member 0
    times = np.array([1.0, 2.0, 2.0, 8.0])
    migs = plan_group_migrations(part, times, migration_budget=0.25)
    assert len(migs) == 1
    m = migs[0]
    total = sum(m.nnz_before)
    assert sum(m.nnz_target) == total
    deltas = np.array(m.nnz_target) - np.array(m.nnz_before)
    assert (deltas % part.block_p == 0).all()
    assert m.moved_nnz <= 0.25 * total + part.block_p
    # work flows away from the slow member toward the fast one
    assert m.nnz_target[3] < m.nnz_before[3]
    assert m.nnz_target[0] > m.nnz_before[0]


def test_no_migration_when_balanced_or_r1():
    t = skewed_tensor()
    _, part = _plan_and_part(t)
    assert plan_group_migrations(part, np.ones(4),
                                 migration_budget=0.25) == []
    plan_r1 = build_plan(t, 4, strategy="amped_cdf", replication=1)
    assert plan_group_migrations(plan_r1.modes[0], np.array([1, 2, 3, 4.0]),
                                 migration_budget=0.25) == []


# -- incremental apply --------------------------------------------------------

def _nonzero_multiset(part):
    out = []
    mask = part.values != 0
    for d in range(part.num_devices):
        for k in np.nonzero(mask[d])[0]:
            out.append((tuple(part.indices[d, k]), float(part.values[d, k])))
    return sorted(out)


def _group_ec_oracle(part, factors, rank):
    """Per-group EC output via numpy: sum of every member's
    val·prod(input rows) accumulated at its local row."""
    outs = np.zeros((part.n_groups, part.rows_max, rank), np.float64)
    nmodes = part.indices.shape[2]
    for dev in range(part.num_devices):
        g = dev // part.r
        mask = part.values[dev] != 0
        rows = part.local_rows[dev][mask]
        contrib = part.values[dev][mask][:, None].astype(np.float64)
        for w in range(nmodes):
            if w == part.mode:
                continue
            contrib = contrib * factors[w][part.indices[dev][mask][:, w]]
        np.add.at(outs[g], rows, contrib)
    return outs


def _decision(plan, migs):
    return ReplanDecision(epoch=plan.rebalance_epoch, sweep=1,
                          triggered=bool(migs), imbalance={},
                          modelled_imbalance={}, migrations=tuple(migs))


def test_apply_rebalance_preserves_semantics():
    t = skewed_tensor()
    plan, part = _plan_and_part(t)
    migs = plan_group_migrations(part, np.array([1.0, 2.0, 2.0, 8.0]),
                                 migration_budget=0.3)
    assert migs
    new_plan, applied = apply_rebalance(plan, _decision(plan, migs))
    assert new_plan.rebalance_epoch == plan.rebalance_epoch + 1
    new_part = new_plan.modes[0]
    # shapes are static: the jitted updates stay valid
    for f in ("indices", "values", "local_rows", "block_to_tile",
              "tile_visited"):
        assert getattr(new_part, f).shape == getattr(part, f).shape
    # exact cover: same nonzero multiset, just redistributed
    assert _nonzero_multiset(new_part) == _nonzero_multiset(part)
    # ownership untouched: every entry still lands in its group's row range
    # (its owned rows of the packed layout)
    mask = new_part.values != 0
    offsets = packed_offsets(new_part.rows_owned)
    for dev in range(4):
        g = dev // new_part.r
        rows = new_part.indices[dev][mask[dev]][:, 0]
        assert ((rows >= offsets[g]) &
                (rows < offsets[g] + new_part.rows_owned[g])).all()
    # blocking contract: no block straddles a tile
    p, tile = new_part.block_p, new_part.tile
    for dev in range(4):
        tiles = new_part.local_rows[dev] // tile
        blk = np.arange(new_part.nnz_max) // p
        for b in range(new_part.nblocks):
            assert (tiles[blk == b] == new_part.block_to_tile[dev, b]).all()
    # bookkeeping matches the arrays
    for dev in range(4):
        assert new_part.nnz_true[dev] == int(mask[dev].sum())
    moved = sum(a["moved_nnz"] for a in applied)
    assert moved > 0
    # EC semantics: per-group outputs identical (order-independent oracle)
    rng = np.random.default_rng(0)
    rank = 4
    factors = [rng.normal(size=(plan.modes[w].padded_rows, rank))
               for w in range(3)]
    np.testing.assert_allclose(_group_ec_oracle(part, factors, rank),
                               _group_ec_oracle(new_part, factors, rank),
                               rtol=1e-10)


def test_apply_rebalance_rejects_stale_epoch():
    t = skewed_tensor()
    plan, part = _plan_and_part(t)
    migs = plan_group_migrations(part, np.array([1.0, 2.0, 2.0, 8.0]),
                                 migration_budget=0.3)
    new_plan, _ = apply_rebalance(plan, _decision(plan, migs))
    with pytest.raises(ValueError, match="epoch"):
        apply_rebalance(new_plan, _decision(plan, migs))


def test_apply_rebalance_respects_headroom():
    """A migration that cannot fit the existing nnz_max is skipped, not
    misapplied — arrays still cover the tensor exactly."""
    t = skewed_tensor()
    plan, part = _plan_and_part(t)
    r = part.r
    n = part.nnz_true.astype(int)
    # pathological intent: shove everything onto member 0
    total = int(n.sum())
    p = part.block_p
    tgt = [(total // p) * p, 0, 0, total - (total // p) * p]
    from repro.schedule.rebalance import GroupMigration
    mig = GroupMigration(mode=0, group=0, nnz_before=tuple(int(x) for x in n),
                         nnz_target=tuple(tgt), moved_nnz=0)
    new_plan, applied = apply_rebalance(plan, _decision(plan, [mig]))
    assert _nonzero_multiset(new_plan.modes[0]) == _nonzero_multiset(part)


# -- config + signature wiring ------------------------------------------------

def test_schedule_config_validation_and_overrides():
    cfg = api.paper()
    assert cfg.schedule.rebalance == "off"
    assert not cfg.schedule.telemetry_enabled
    on = cfg.with_overrides({"schedule.rebalance": "on",
                             "schedule.cadence": 3})
    assert on.schedule.migrations_enabled and on.schedule.cadence == 3
    with pytest.raises(ValueError):
        cfg.with_overrides({"schedule.rebalance": "sometimes"})
    for bad in ({"schedule.cadence": 0}, {"schedule.ewma_alpha": 1.5},
                {"schedule.ewma_alpha": 0.0}, {"schedule.migration_budget": 2.0},
                {"schedule.imbalance_threshold": 0.5},
                {"schedule.probe_repeats": 0}):
        with pytest.raises(ValueError):
            cfg.with_overrides(bad)
    rt = api.DecomposeConfig.from_dict(on.to_dict())
    assert rt == on


def test_schedule_policy_overrides_strategy(small_tensor):
    cfg = api.paper()
    assert cfg.resolved_policy() == "amped_cdf"
    cfg2 = cfg.with_overrides({"schedule.policy": "uniform_index"})
    assert cfg2.resolved_policy() == "uniform_index"
    s1 = api.plan_signature(small_tensor, cfg, num_devices=2)
    s2 = api.plan_signature(small_tensor, cfg2, num_devices=2)
    assert s1 != s2
    # and the plan actually uses the override
    p = api.plan(small_tensor, cfg2, num_devices=2)
    q = build_plan(small_tensor, 2, strategy="uniform_index")
    np.testing.assert_array_equal(p.modes[0].values, q.modes[0].values)


def test_signature_extends_with_rebalance_epoch(small_tensor):
    cfg = api.paper()
    s0 = api.plan_signature(small_tensor, cfg, num_devices=2)
    s1 = api.plan_signature(small_tensor, cfg, num_devices=2,
                            rebalance_epoch=1)
    assert s0 != s1


def test_rebalanced_plan_roundtrips(tmp_path):
    t = skewed_tensor()
    plan, part = _plan_and_part(t)
    migs = plan_group_migrations(part, np.array([1.0, 2.0, 2.0, 8.0]),
                                 migration_budget=0.3)
    new_plan, _ = apply_rebalance(plan, _decision(plan, migs))
    api.save_plan(new_plan, str(tmp_path / "p"), signature="sig-e1")
    loaded = api.load_plan(str(tmp_path / "p"), expect_signature="sig-e1")
    assert loaded.rebalance_epoch == new_plan.rebalance_epoch
    for w in range(3):
        np.testing.assert_array_equal(loaded.modes[w].blocks_true,
                                      new_plan.modes[w].blocks_true)
        np.testing.assert_array_equal(loaded.modes[w].values,
                                      new_plan.modes[w].values)
