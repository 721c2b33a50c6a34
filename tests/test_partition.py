"""Property tests for the AMPED partitioning invariants (paper §3)."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.coo import random_sparse
from repro.core.partition import (auto_replication, build_plan,
                                  packed_offsets, partition_mode)

STRATEGIES = ["amped_cdf", "amped_lpt", "uniform_index", "equal_nnz"]


def _nonzero_multiset(part):
    """(original indices, value) pairs of all non-padding entries."""
    out = []
    mask = part.values != 0
    for d in range(part.num_devices):
        for k in np.nonzero(mask[d])[0]:
            out.append((tuple(part.indices[d, k]), float(part.values[d, k])))
    return sorted(out)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_exact_cover(small_tensor, strategy):
    """Every nonzero lands on exactly one device (paper: task-independent
    partitions)."""
    t = small_tensor
    part, g2p, _ = partition_mode(t, 0, 8, strategy=strategy)
    got = _nonzero_multiset(part)
    want = sorted((tuple(i), float(v)) for i, v in zip(t.indices, t.values)
                  if v != 0)
    assert got == want


@pytest.mark.parametrize("strategy", ["amped_cdf", "amped_lpt", "uniform_index"])
def test_output_rows_disjoint_across_groups(small_tensor, strategy):
    """The AMPED invariant: all nonzeros with the same output index live in
    the same group → no cross-group write conflicts."""
    t = small_tensor
    for mode in range(t.nmodes):
        part, g2p, p2g = partition_mode(t, mode, 8, strategy=strategy)
        r = part.r
        owner_of_index = {}
        mask = part.values != 0
        for dev in range(part.num_devices):
            g = dev // r
            for k in np.nonzero(mask[dev])[0]:
                oi = int(part.indices[dev, k, mode])
                assert owner_of_index.setdefault(oi, g) == g


def test_local_rows_consistent(small_tensor):
    """local_row + group offset == packed row of the output index."""
    t = small_tensor
    part, g2p, _ = partition_mode(t, 1, 8, strategy="amped_cdf")
    offsets = packed_offsets(part.rows_owned)
    mask = part.values != 0
    for dev in range(8):
        g = dev // part.r
        for k in np.nonzero(mask[dev])[0]:
            oi = int(part.indices[dev, k, 1])
            assert g2p[oi] == offsets[g] + part.local_rows[dev, k]


def test_blocks_tile_coherent(small_tensor):
    """No kernel block straddles an output row tile (kernel precondition)."""
    t = small_tensor
    for strategy in STRATEGIES:
        part, _, _ = partition_mode(t, 0, 8, strategy=strategy)
        p, tile = part.block_p, part.tile
        for dev in range(8):
            tiles = part.local_rows[dev] // tile
            blk = np.arange(part.nnz_max) // p
            for b in range(part.nblocks):
                sel = tiles[blk == b]
                assert (sel == part.block_to_tile[dev, b]).all()


def test_padding_is_noop(small_tensor):
    part, _, _ = partition_mode(small_tensor, 2, 8)
    mask = part.values == 0
    assert mask.sum() > 0  # padding exists
    # padded entries have local rows inside the block's tile (checked above)
    # and contribute value 0 — nothing else to assert structurally


def test_equal_nnz_balances_perfectly(small_tensor):
    part, _, _ = partition_mode(small_tensor, 0, 8, strategy="equal_nnz")
    stats = part.balance_stats()
    assert stats["nnz_max"] - stats["nnz_min"] <= 1
    assert part.r == 8


def test_cdf_beats_uniform_on_skew():
    t = random_sparse((100, 50, 40), 3000, seed=11, distribution="zipf",
                      zipf_a=1.2)
    cdf, _, _ = partition_mode(t, 0, 8, strategy="amped_cdf", replication=1)
    uni, _, _ = partition_mode(t, 0, 8, strategy="uniform_index",
                               replication=1)
    # paper Fig. 6 mechanism: CDF split balances what uniform index ranges
    # cannot on skewed tensors
    assert cdf.balance_stats()["nnz_max"] <= uni.balance_stats()["nnz_max"]


def test_auto_replication_rules():
    # tiny mode (Patents mode 0: 46 indices, 256 devices) → r grows
    hist = np.ones(46, np.int64) * 1000
    r = auto_replication(hist, 256)
    assert 256 // r <= 46
    # single hot index → r grows to split it
    hist = np.ones(1000, np.int64)
    hist[0] = 100_000
    r = auto_replication(hist, 8)
    assert r >= 4
    # uniform big mode → r == 1 (paper scheme)
    assert auto_replication(np.ones(10_000, np.int64), 8) == 1


@given(st.integers(0, 10_000), st.sampled_from(STRATEGIES),
       st.sampled_from([1, 2, 4]))
@settings(max_examples=15, deadline=None)
def test_plan_cover_property(seed, strategy, repl):
    t = random_sparse((23, 17, 11), 150, seed=seed)
    if strategy == "equal_nnz":
        repl = None
    plan = build_plan(t, 4, strategy=strategy, replication=repl)
    for mode in range(3):
        part = plan.modes[mode]
        mask = part.values != 0
        assert mask.sum() == np.count_nonzero(t.values)
        # translated output indices land in the owning group's packed range
        offsets = packed_offsets(part.rows_owned)
        for dev in range(4):
            g = dev // part.r
            rows = part.indices[dev][mask[dev]][:, mode]
            assert ((rows >= offsets[g]) &
                    (rows < offsets[g] + part.rows_owned[g])).all()


def test_padded_to_global_inverse(small_tensor):
    plan = build_plan(small_tensor, 8)
    for w in range(3):
        g2p, p2g = plan.global_to_padded[w], plan.padded_to_global[w]
        idx = np.arange(small_tensor.shape[w])
        assert (p2g[g2p[idx]] == idx).all()
        pad_rows = p2g < 0
        assert pad_rows.sum() == p2g.size - idx.size


def test_validate_plan_rejects_nondivisible_rows(small_tensor):
    """Regression: a plan whose padded row count does not split evenly
    across the replication group used to flow straight into the intra-group
    reduce-scatter and silently corrupt row ownership. It must now fail at
    plan time with a clear ValueError — both from validate_plan directly
    and from api.compile on a hand-altered/stale plan artifact."""
    import dataclasses

    import repro.api as api
    from repro.core.partition import validate_plan

    plan = build_plan(small_tensor, 2, replication=2)
    assert validate_plan(plan) is plan  # a healthy plan passes through

    part0 = plan.modes[0]
    assert part0.r == 2
    bad_part = dataclasses.replace(part0, rows_max=part0.rows_max + 1)
    bad_plan = dataclasses.replace(plan, modes=(bad_part,) + plan.modes[1:])
    with pytest.raises(ValueError, match="not divisible by replication"):
        validate_plan(bad_plan)
    with pytest.raises(ValueError, match="not divisible by replication"):
        api.compile(bad_plan, api.paper({"rank": 4}))


def test_validate_plan_rejects_inconsistent_device_grid(small_tensor):
    import dataclasses

    from repro.core.partition import validate_plan

    plan = build_plan(small_tensor, 2, replication=2)
    bad_part = dataclasses.replace(plan.modes[0], n_groups=2)  # 2*2 != 2
    bad_plan = dataclasses.replace(plan, modes=(bad_part,) + plan.modes[1:])
    with pytest.raises(ValueError, match="device grid"):
        validate_plan(bad_plan)


def test_partition_report_of_a_four_device_plan():
    """What the static partition costs, read from a 4-device paper-preset
    plan built in this one-device process (planning needs no devices):
    CDF ownership pads a Zipf mode's factor to several times its rows, and
    every ratio of a max to a mean is at least 1."""
    from repro import api
    from repro.api.solver import partition_report
    cfg = api.preset("paper", {"rank": 8, "runtime.num_devices": 4})
    t = random_sparse((300, 120, 90), 6000, seed=0, distribution="zipf")
    plan = api.plan(t, cfg)
    rep = partition_report(plan, 8)
    assert rep["num_devices"] == 4
    for d, mode in rep["per_mode"].items():
        part = plan.modes[d]
        assert mode["padded_rows_over_rows"] == \
            part.n_groups * part.rows_max / plan.shape[d] > 1.0
        assert mode["slots_max_over_mean"] >= 1.0
        assert mode["nnz_max_over_mean"] == pytest.approx(
            part.nnz_true.max() / part.nnz_true.mean())


# -- packed ownership layout --------------------------------------------------

def _zipf_hist(size, a=1.1, total=200_000):
    """An nnz histogram whose heavy rows follow Zipf(a), seeded."""
    rng = np.random.default_rng(0)
    draws = rng.zipf(a, total)
    return np.bincount(draws[draws <= size] - 1,
                       minlength=size).astype(np.int64)


@pytest.mark.parametrize("devices,replication", [(1, 1), (4, 4)])
def test_packed_layout_of_one_group_is_the_slab(devices, replication):
    """With one group the packed layout is the slab layout: every index at
    its own row, and the factor is the group's ``rows_max`` slab."""
    from repro.core.partition import mode_layout
    hist = _zipf_hist(1000)
    lay = mode_layout(hist, 0, devices, replication=replication)
    assert lay.n_groups == 1
    np.testing.assert_array_equal(lay.global_to_padded, np.arange(1000))
    assert lay.padded_rows == lay.rows_max
    np.testing.assert_array_equal(lay.padded_to_global[:1000],
                                  np.arange(1000))
    assert (lay.padded_to_global[1000:] == -1).all()


def test_packed_factor_rows_on_a_zipf_cdf_split():
    """CDF ownership of a Zipf(1.1) mode over 4 groups: the last group owns
    most rows, so the slabs the wire carries are several times the rows,
    while the packed factor holds at most the rows plus the last group's
    pad rows."""
    from repro.core.partition import mode_layout
    size = 20_000
    lay = mode_layout(_zipf_hist(size), 0, 4, strategy="amped_cdf",
                      replication=1)
    assert lay.n_groups == 4
    assert lay.padded_rows <= size + lay.rows_max - lay.rows_owned[-1]
    assert lay.n_groups * lay.rows_max > 2 * lay.padded_rows
    np.testing.assert_array_equal(
        lay.offsets, np.concatenate([[0], np.cumsum(lay.rows_owned)[:-1]]))


@pytest.mark.parametrize("strategy", ["amped_cdf", "amped_lpt"])
def test_packing_the_slabs_puts_every_row_at_its_packed_row(strategy):
    """Slabs of ``rows_max`` rows per group, each row at its local row (as
    the exchange gathers them), packed by ``mttkrp.pack_slabs``: every
    global row lands where ``global_to_padded`` says, the pad rows stay
    zero, and the owner groups are recovered from the packed rows."""
    from repro.core.mttkrp import pack_slabs
    from repro.core.partition import mode_layout, owner_from_packed
    size = 3000
    lay = mode_layout(_zipf_hist(size), 0, 4, strategy=strategy,
                      replication=1)
    g2p, owner = lay.global_to_padded, lay.owner
    vals = np.arange(1, size + 1, dtype=np.float32)[:, None] * [1.0, -1.0]
    slabs = np.zeros((lay.n_groups * lay.rows_max, 2), np.float32)
    slabs[owner * lay.rows_max + g2p - lay.offsets[owner]] = vals
    packed = np.asarray(pack_slabs(slabs, lay.rows_owned, lay.rows_max))
    assert packed.shape == (lay.padded_rows, 2)
    np.testing.assert_array_equal(packed[g2p], vals)
    assert (packed[lay.padded_to_global < 0] == 0).all()
    np.testing.assert_array_equal(owner_from_packed(g2p, lay.rows_owned),
                                  owner)


@pytest.mark.parametrize("devices,rows0", [
    pytest.param(1, 250_000, id="1"), pytest.param(4, 250_000, id="4"),
    pytest.param(4, 1_000_000, id="4-unrolled")])
def test_partition_report_reads_the_packed_factors(devices, rows0):
    """The report's ``factor_rows_over_rows``, ``ec_rows_per_lane_row``
    and ``ec_loop`` on a 4-device and a 1-device plan at rank 32: packed
    factors hold about their true rows where the wire carries several times
    them; the ``ref`` EC loops exactly where its gather sources fit
    ``REF_VMEM_FACTOR_BYTES``, and gathers from lane-packed copies of its
    input factors, four rows to a 128-lane row, where they fit only so.
    Mode 0 reads two small factors (lane-padded: it loops); modes 1 and 2
    read mode 0's, 250,000 rows (128 MB lane-padded, 32 MB packed: they loop
    packed) or 1,000,000 (128 MB packed: unrolled, lane-padded)."""
    from repro import api
    from repro.api.solver import partition_report
    from repro.kernels import ops as kops
    cfg = api.preset("paper", {"rank": 32, "runtime.num_devices": devices})
    t = random_sparse((rows0, 300, 200), 6000, seed=0, distribution="zipf")
    plan = api.plan(t, cfg)
    rep = partition_report(plan, 32)
    loops = []
    for d, mode in rep["per_mode"].items():
        part = plan.modes[d]
        assert mode["factor_rows_over_rows"] == part.padded_rows / t.shape[d]
        slack = (part.rows_max - part.rows_owned[-1]) / t.shape[d]
        assert mode["factor_rows_over_rows"] <= 1.0 + slack
        if devices == 1:
            assert mode["factor_rows_over_rows"] == \
                mode["padded_rows_over_rows"]
        else:
            assert mode["factor_rows_over_rows"] < \
                mode["padded_rows_over_rows"]
        inputs = [p.padded_rows for w, p in enumerate(plan.modes) if w != d]
        padded = sum(inputs) * 128 * 4
        # whole 8-row sublane tiles of 128-lane rows, four factor rows each
        packed = sum(-(-n // 32) * 8 for n in inputs) * 128 * 4
        k = 4 if packed <= kops.REF_VMEM_FACTOR_BYTES < padded else 1
        assert mode["ec_rows_per_lane_row"] == k
        assert mode["ec_input_bytes"] == (packed if k == 4 else padded)
        assert mode["ec_loop"] == (mode["ec_input_bytes"]
                                   <= kops.REF_VMEM_FACTOR_BYTES)
        loops.append((k, mode["ec_loop"]))
    big = (4, True) if rows0 < 1_000_000 else (1, False)
    assert loops == [(1, True), big, big]
