"""The generator: truncated Zipf draws, exact dedup, no overflow, and the
same coordinates for every seed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import gen

TWITCH_0_1 = (1552431, 616167, 78386, 610, 610)


def _draws(seed, shape, n, a):
    thr = tuple(jnp.asarray(gen.zipf_thresholds(s, a)) for s in shape)
    cols = gen.draw(jax.random.key(seed), thr, n)
    return cols, np.stack([np.asarray(c) for c in cols], 1)


@pytest.mark.parametrize("a", [1.1, 1.4])
@pytest.mark.parametrize("size", [40, 5000])
def test_histogram_follows_truncated_zipf(a, size):
    n = 400_000
    _, raw = _draws(3, (size,), n, a)
    counts = np.bincount(raw[:, 0], minlength=size)
    assert counts.size == size
    expect = n * gen.zipf_pmf(size, a)
    sd = np.sqrt(expect * (1 - expect / n))
    head = slice(0, 20)
    assert np.all(np.abs(counts[head] - expect[head]) < 5 * sd[head] + 1)
    # no fold: the last index holds its own small share, not the tail's
    assert counts[-1] < expect[-1] + 5 * sd[-1] + 2
    # the tail beyond the head holds its mass
    tail = expect[20:].sum()
    assert abs(counts[20:].sum() - tail) < 5 * np.sqrt(tail) + 1


def test_dedup_sums_duplicates_exactly_once():
    shape = (12, 5, 3)
    cols, raw = _draws(5, shape, 20_000, 1.1)
    vals = jax.random.normal(jax.random.key(9), (20_000,), jnp.float32)
    uniq, vsum, count = gen.dedup(cols, vals, shape)
    n = int(count)
    uniq, vsum = np.asarray(uniq)[:n], np.asarray(vsum)[:n]
    want, inv = np.unique(raw, axis=0, return_inverse=True)
    sums = np.zeros(len(want))
    np.add.at(sums, inv.ravel(), np.asarray(vals, np.float64))
    assert np.array_equal(uniq, want)       # sorted, each coordinate once
    np.testing.assert_allclose(vsum, sums, rtol=1e-5, atol=1e-4)


def test_twitch_shape_at_scale_0_1_does_not_overflow():
    assert gen.digit_groups(TWITCH_0_1) == [(3, 4), (2,), (1,), (0,)]
    idx, val = gen.generate(2 ** 33 + 11, TWITCH_0_1, 50_000, 1.4)
    assert idx.dtype == np.int32 and idx.shape[1] == 5
    assert np.all(idx >= 0) and np.all(idx < np.array(TWITCH_0_1))
    assert len(np.unique(idx, axis=0)) == len(idx)
    assert np.all(np.isfinite(val))


def test_every_seed_gets_the_same_work():
    shape, draws = (3000, 300, 77), 60_000
    a = gen.generate(1, shape, draws, 1.1)
    b = gen.generate(2 ** 40 + 3, shape, draws, 1.1)
    assert np.array_equal(a[0], b[0])           # the same coordinates
    assert not np.allclose(a[1], b[1])          # other values


def test_same_seed_same_tensor():
    a = gen.generate(7, (300, 200, 10), 5000, 1.1)
    b = gen.generate(7, (300, 200, 10), 5000, 1.1)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert abs(a[1].mean()) < 0.1 and 0.9 < a[1].std() < 1.3
