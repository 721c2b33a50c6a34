"""The benchmark's own sparse-tensor generator, run on the device.

Each mode draws its coordinates from a *truncated* Zipf(a) law over
``[0, size)``: ``P(k) ∝ (k + 1) ** -a``, with no probability folded onto
the last index. A draw is an inverse CDF: 32 random bits per coordinate are
looked up in a table of the cumulative probabilities, scaled to ``2**32``
and computed on the host in float64, so the law keeps its tail down to
about ``2**-32`` per index. Values are N(0, 1) in float32.

Duplicate coordinates are summed. The nonzeros are put in lexicographic
order by a least-significant-digit radix sort: each pass is one stable
sort on an int32 digit that packs as many trailing modes as fit under
``2**31``, carrying the permutation. No flat index is ever formed (one over
twitch's five modes overflows int64 at scale 0.02), and every pass calls
the same compiled sort. Equal neighbours are then summed.

Every seed gets the same work. The coordinates are drawn from one fixed
key, so every seed has the same nonzeros at the same coordinates; the seed
draws their values. (Drawn from the seed itself, the padded nonzero counts
differed by seed: each seed compiled its own mode updates, and seconds per
sweep differed by 1.8% between seeds. With the rows of each mode relabelled
by a seed-drawn permutation of whole 128-row blocks, the padded shapes were
the same, but the order of the rows still moved seconds per sweep: by
0.14% between two twitch seeds, each of which repeated to 0.005%.)

The result comes back to the host as the ``(indices, values)`` COO that
``repro.api.plan`` takes.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["zipf_pmf", "zipf_thresholds", "digit_groups", "draw", "dedup",
           "generate"]

_DIGIT_LIMIT = 1 << 31
_STRUCTURE_KEY = 0


def zipf_pmf(size: int, a: float) -> np.ndarray:
    """Truncated Zipf(a) probabilities of indices ``0 .. size-1`` (float64)."""
    p = np.arange(1, size + 1, dtype=np.float64) ** -float(a)
    return p / p.sum()


def zipf_thresholds(size: int, a: float) -> np.ndarray:
    """uint32 thresholds ``T`` with ``T[k] = floor(CDF(k) * 2**32)`` for
    ``k < size - 1``: a draw ``u`` of 32 random bits maps to the number of
    thresholds ``<= u``, which lies in ``[0, size)``."""
    cdf = np.cumsum(zipf_pmf(size, a))[:-1]
    return np.minimum(np.floor(cdf * 2.0 ** 32), 2.0 ** 32 - 1).astype(np.uint32)


def digit_groups(shape) -> list[tuple[int, ...]]:
    """Modes packed into int32 radix digits, least significant first: each
    group is a run of consecutive modes whose sizes multiply to under
    ``2**31``."""
    groups, cur, prod = [], [], 1
    for w in reversed(range(len(shape))):
        if cur and prod * shape[w] >= _DIGIT_LIMIT:
            groups.append(tuple(reversed(cur)))
            cur, prod = [], 1
        cur.append(w)
        prod *= shape[w]
    groups.append(tuple(reversed(cur)))
    return groups


@functools.partial(jax.jit, static_argnames=("draws",))
def draw(key, thresholds: tuple, draws: int):
    """``draws`` coordinates per mode (int32)."""
    keys = jax.random.split(key, len(thresholds))
    return tuple(
        jnp.searchsorted(thr, jax.random.bits(keys[w], (draws,), jnp.uint32),
                         side="right").astype(jnp.int32)
        for w, thr in enumerate(thresholds))


@functools.partial(jax.jit, static_argnames=("sizes",))
def _digit(cols: tuple, perm, sizes: tuple):
    d = jnp.zeros_like(perm)
    for c, s in zip(cols, sizes):
        d = d * s + c[perm]
    return d


@jax.jit
def _sort_pass(digit, perm):
    return jax.lax.sort((digit, perm), num_keys=1, is_stable=True)[1]


@jax.jit
def _sum_runs(cols: tuple, vals, perm):
    cols = tuple(c[perm] for c in cols)
    vals = vals[perm]
    n = vals.shape[0]
    differs = functools.reduce(jnp.logical_or,
                               [c[1:] != c[:-1] for c in cols])
    new = jnp.concatenate([jnp.ones((1,), bool), differs])
    seg = jnp.cumsum(new.astype(jnp.int32)) - 1
    vsum = jax.ops.segment_sum(vals, seg, num_segments=n,
                               indices_are_sorted=True)
    first = jnp.nonzero(new, size=n, fill_value=0)[0]
    return jnp.stack([c[first] for c in cols], axis=1), vsum, seg[-1] + 1


def dedup(cols: tuple, vals, shape):
    """Lexicographically sorted unique coordinates ``(draws, nmodes)``,
    their summed values ``(draws,)``, and how many of the rows are used."""
    perm = jnp.arange(vals.shape[0], dtype=jnp.int32)
    for group in digit_groups(tuple(int(s) for s in shape)):
        digit = _digit(tuple(cols[w] for w in group), perm,
                       tuple(int(shape[w]) for w in group))
        perm = _sort_pass(digit, perm)
    return _sum_runs(tuple(cols), vals, perm)


def generate(seed: int, shape, draws: int, a: float, timings=None):
    """``draws`` nonzeros of the given shape, duplicates summed, on the
    default device: the coordinates every seed shares, given values by
    ``seed``. Returns host arrays ``(indices int32 (nnz,
    nmodes), values float32 (nnz,))``. Seconds spent drawing,
    deduplicating and copying to the host go into ``timings`` when it is
    given."""
    timings = {} if timings is None else timings
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0xFFFFFFFF)
    shape = tuple(int(s) for s in shape)
    thr = tuple(jnp.asarray(zipf_thresholds(s, a)) for s in shape)
    t0 = time.perf_counter()
    cols = draw(jax.random.key(_STRUCTURE_KEY), thr, int(draws))
    vals = jax.block_until_ready(
        jax.random.normal(key, (int(draws),), jnp.float32))
    t1 = time.perf_counter()
    uniq, vsum, count = jax.block_until_ready(dedup(cols, vals, shape))
    t2 = time.perf_counter()
    n = int(count)
    out = np.asarray(uniq)[:n], np.asarray(vsum)[:n]
    timings.update(draw_s=t1 - t0, dedup_s=t2 - t1,
                   to_host_s=time.perf_counter() - t2)
    return out
