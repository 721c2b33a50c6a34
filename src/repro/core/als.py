"""CP-ALS on top of distributed MTTKRP (paper Algorithm 1 + §2.1.4).

One ALS sweep updates every mode in sequence:
    M_d   = MTTKRP(X_(d), {F_w}_{w≠d})          (distributed, the paper's core)
    V_d   = ⊛_{w≠d} (F_wᵀ F_w)                  (R×R Hadamard of grams)
    F_d   = M_d V_d⁺,  λ = colnorms(F_d),  F_d /= λ
with the fit computed from the standard norm identity (no residual tensor is
ever materialised):
    ||X̂||² = λᵀ (⊛_w G_w) λ,   ⟨X, X̂⟩ = Σ (M_last ⊛ F_last) λ
Grams are cached across modes and only the updated mode's gram is recomputed
(beyond-paper: removes (N−1)/N of gram FLOPs; see EXPERIMENTS.md §Perf).

Mode updates are jitted with the replaced factor buffer donated (off-CPU),
and the per-sweep fit stays a device scalar — a sweep enqueues no host sync;
callers block only when they actually read ``state.fits``.

Factor matrices live in the packed ownership layout of their mode (see
core/partition.py: each group's owned rows back to back, the last group's
pad rows at the end; the exchange packs the gathered slabs into it, see
core/mttkrp.py); padding rows are zero and stay zero through sweeps
(MTTKRP writes zeros there; the solve is row-wise).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import mttkrp as dmttkrp
from repro.core.partition import CPPlan
from repro.obs import profiler as obs_profiler
from repro.obs import trace as obs_trace

__all__ = ["ALSState", "init_factors", "matmul", "gram", "make_mode_update",
           "make_sweep_updates", "als_sweep", "fit_from_stats",
           "unpad_factors", "StreamingModeUpdate",
           "make_streaming_mode_update", "make_streaming_sweep_updates",
           "als_streaming_sweep"]


@dataclasses.dataclass
class ALSState:
    factors: list[jax.Array]       # per mode, packed layout, replicated
    lam: jax.Array                 # (R,) column scales
    grams: list[jax.Array]         # per mode, (R, R) = F_wᵀ F_w
    sweep: int = 0
    # Device scalars (or floats after a host read) — reading an entry blocks.
    fits: list = dataclasses.field(default_factory=list)


def init_factors(plan: CPPlan, rank: int, seed: int = 0) -> list[jax.Array]:
    """Random factors in the packed ownership layout (``padded_rows`` rows,
    global row ``i`` at ``global_to_padded[i]``); the last group's pad rows
    exactly zero."""
    rng = np.random.default_rng(seed)
    out = []
    for w in range(plan.nmodes):
        rows = plan.modes[w].padded_rows
        f = np.zeros((rows, rank), np.float32)
        g2p = plan.global_to_padded[w]
        f[g2p] = rng.uniform(0.1, 1.0, size=(plan.shape[w], rank)).astype(np.float32)
        out.append(jnp.asarray(f))
    return out


def matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """f32 matmul for the ALS algebra (solve, Grams, fit). A TPU's default
    f32 precision is one bf16 pass, whose rounding the Gram pseudo-inverse
    amplifies into fit differences far above the EC's own; ``HIGHEST``
    keeps f32 on every backend, at R×R and rows×R×R cost."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def gram(f: jax.Array) -> jax.Array:
    """``Fᵀ F`` in f32."""
    return matmul(f.T, f)


def _pinv_psd(v: jax.Array, rcond: float = 1e-8) -> jax.Array:
    """Pseudo-inverse of a symmetric PSD R×R matrix via eigh (stable, tiny)."""
    w, u = jnp.linalg.eigh(v)
    w_inv = jnp.where(w > rcond * jnp.max(jnp.abs(w)), 1.0 / w, 0.0)
    return matmul(u * w_inv[None, :], u.T)


def _solve(m: jax.Array, grams: Sequence[jax.Array], mode: int):
    """The ALS algebra of one mode update, under the ``als_solve`` device
    scope: ``V`` (the Hadamard of the other modes' Grams), ``F = M V⁺``,
    λ = colnorms(F), ``F /= λ`` and ``F``'s new Gram. Returns
    ``(F, G, λ)``."""
    with obs_profiler.device_scope("als_solve"):
        v = functools.reduce(
            lambda a, b: a * b,
            [g for w, g in enumerate(grams) if w != mode])    # (R, R)
        f_new = matmul(m, _pinv_psd(v))
        lam = jnp.linalg.norm(f_new, axis=0)
        lam = jnp.where(lam > 0, lam, 1.0)
        f_new = f_new / lam[None, :]
        g_new = gram(f_new)
    return f_new, g_new, lam


def make_mode_update(plan: CPPlan, mode: int, mesh: Mesh, **mttkrp_kw) -> Callable:
    """Jitted ``(F_d_old, dev_arrays, other_factors, grams) ->
    (F_d, G_d, M_d, lam)``.

    ``other_factors`` is the factor list *without* mode ``mode``; the old
    output-mode factor is passed separately so its buffer can be donated
    (``F_d`` has the same shape — XLA aliases it in place, saving one
    padded_d×R allocation per update). Donation is skipped on CPU, where jax
    does not implement it.
    """
    mfn = dmttkrp.make_mttkrp_fn(plan.modes[mode], mesh, **mttkrp_kw)

    def update(f_old: jax.Array, dev, other_factors: Sequence[jax.Array],
               grams: Sequence[jax.Array]):
        factors = list(other_factors[:mode]) + [f_old] + \
            list(other_factors[mode:])
        m = mfn(dev, factors)                             # (padded_d, R)
        f_new, g_new, lam = _solve(m, grams, mode)
        return f_new, g_new, m, lam

    donate = (0,) if jax.default_backend() != "cpu" else ()
    return jax.jit(update, donate_argnums=donate)


def make_sweep_updates(plan: CPPlan, mesh: Mesh, **mttkrp_kw) -> list[Callable]:
    """The jitted per-mode update list a multi-sweep caller needs: one
    :func:`make_mode_update` closure per mode, sharing ``mttkrp_kw`` (kernel
    variant, num_buffers, ``exchange_spec`` — the
    :class:`repro.comm.ExchangeSpec` selecting gather/merge schedule, overlap
    chunking and wire dtype — or the legacy ``ring`` flag). Build once, pass
    to every :func:`als_sweep` — this is what :class:`repro.api.CPSolver`
    owns. With an ``overlap`` exchange spec, each update's tail chunks are
    still in flight when the next mode's update is enqueued — the same
    async-dispatch pipelining the shard streamer applies to H2D transfers."""
    return [make_mode_update(plan, d, mesh, **mttkrp_kw)
            for d in range(plan.nmodes)]


# -- epoch streaming: super-shard partial accumulation ------------------------

_STREAM_KERNEL_KEYS = ("use_kernel", "variant", "num_buffers", "interpret")
_STREAM_EXCHANGE_KEYS = ("ring", "exchange_spec")
_STREAM_AXIS_KEYS = ("group_axes", "sub_axis")


@dataclasses.dataclass(frozen=True)
class StreamingModeUpdate:
    """The jitted triple one mode's epoch-streaming update runs:
    ``init_acc()`` → ``accumulate(acc, dev, factors)`` per super-shard →
    ``finish(f_old, acc, other_factors, grams)``. ``accumulate`` compiles
    once per mode (all super-shards share the stream plan's static shapes)
    and is where transfer overlap pays off: while it computes super-shard
    k, the streamer's background thread places super-shard k+1."""

    init_acc: Callable[[], jax.Array]
    accumulate: Callable
    finish: Callable


def make_streaming_mode_update(plan: CPPlan, mode: int, mesh: Mesh, *,
                               rank: int, **mttkrp_kw) -> StreamingModeUpdate:
    """Streaming twin of :func:`make_mode_update`: the MTTKRP is split into
    a per-super-shard partial accumulation (EC only, no collectives) and a
    one-shot finish (merge + exchange + solve). Folding each super-shard's
    masked EC into a zero accumulator reproduces the resident partial
    bit-for-bit (tile-boundary splitting: every output row is computed by
    exactly one super-shard), so fits match the resident path bitwise at
    fp32. Takes the same ``mttkrp_kw`` as :func:`make_mode_update`."""
    unknown = set(mttkrp_kw) - set(_STREAM_KERNEL_KEYS
                                   + _STREAM_EXCHANGE_KEYS
                                   + _STREAM_AXIS_KEYS)
    if unknown:
        raise TypeError(f"unknown mttkrp kwargs for streaming update: "
                        f"{sorted(unknown)}")
    axis_kw = {k: v for k, v in mttkrp_kw.items() if k in _STREAM_AXIS_KEYS}
    kernel_kw = {k: v for k, v in mttkrp_kw.items()
                 if k in _STREAM_KERNEL_KEYS}
    finish_kw = {k: v for k, v in mttkrp_kw.items()
                 if k in _STREAM_EXCHANGE_KEYS}
    part = plan.modes[mode]
    pfn = dmttkrp.make_partial_mttkrp_fn(part, mesh, **axis_kw, **kernel_kw)
    ffn = dmttkrp.make_streaming_finish_fn(part, mesh, **axis_kw,
                                           **finish_kw)

    def init_acc():
        return dmttkrp.zero_partials(part, mesh, rank, **axis_kw)

    def accumulate(acc, dev, factors: Sequence[jax.Array]):
        return pfn(acc, dev, list(factors))

    def finish(f_old: jax.Array, acc, other_factors: Sequence[jax.Array],
               grams: Sequence[jax.Array]):
        m = ffn(acc)                                       # (padded_d, R)
        f_new, g_new, lam = _solve(m, grams, mode)
        return f_new, g_new, m, lam

    donate = jax.default_backend() != "cpu"
    return StreamingModeUpdate(
        init_acc=init_acc,
        accumulate=jax.jit(accumulate,
                           donate_argnums=(0,) if donate else ()),
        finish=jax.jit(finish, donate_argnums=(0,) if donate else ()),
    )


def make_streaming_sweep_updates(plan: CPPlan, mesh: Mesh, *, rank: int,
                                 **mttkrp_kw) -> list[StreamingModeUpdate]:
    """One :func:`make_streaming_mode_update` per mode — what
    :class:`repro.api.CPSolver` owns in streaming mode."""
    return [make_streaming_mode_update(plan, d, mesh, rank=rank, **mttkrp_kw)
            for d in range(plan.nmodes)]


def als_streaming_sweep(plan: CPPlan, mesh: Mesh, streamer, stream_plans,
                        state: ALSState,
                        updates: Sequence[StreamingModeUpdate]) -> ALSState:
    """One full epoch-streaming sweep: per mode, iterate that mode's
    super-shards through the double-buffered streamer, folding each
    partial MTTKRP into the accumulator, then merge/exchange/solve once.
    Fits are bitwise identical to :func:`als_sweep` on the resident shards.

    ``streamer.get(d, k)`` returns super-shard k's arrays and dispatches
    k+1's host→device transfer in the background — the enqueued
    ``accumulate`` compute is what hides it. The host only blocks when a
    transfer outlives the compute it was hidden behind (recorded by the
    streamer as exposed time)."""
    n = plan.nmodes
    tracer = obs_trace.get_tracer()
    factors, grams = list(state.factors), list(state.grams)
    m_last = f_last = lam = None
    for d in range(n):
        with tracer.span("mode_update", mode=d):
            upd = updates[d]
            acc = upd.init_acc()
            for k in range(stream_plans[d].num_shards):
                with tracer.span("h2d_window", mode=d, shard=k):
                    dev = streamer.get(d, k)
                with tracer.span("ec", mode=d, shard=k):
                    acc = upd.accumulate(acc, dev, factors)
                    # double-buffer barrier: shard k+1's compute
                    # data-depends on this accumulator, so waiting costs
                    # the pipeline nothing — and it keeps the streamer's
                    # exposed-time metric honest (time get() blocks =
                    # transfer NOT hidden behind compute, rather than
                    # host queue-ahead racing the async dispatch)
                    jax.block_until_ready(acc)
            others = [factors[w] for w in range(n) if w != d]
            with tracer.span("exchange", mode=d):
                f_d, g_d, m_d, lam = upd.finish(factors[d], acc, others,
                                                grams)
            factors[d], grams[d] = f_d, g_d
            m_last, f_last = m_d, f_d
    fit = fit_from_stats(plan.norm, m_last, f_last, lam, grams)
    return ALSState(factors=factors, lam=lam, grams=grams,
                    sweep=state.sweep + 1, fits=state.fits + [fit])


@jax.jit
def fit_from_stats(norm_x: float, m_last, f_last, lam, grams) -> jax.Array:
    """fit = 1 - ||X - X̂||_F / ||X||_F via the norm identity (one small
    jitted program per sweep, not a dozen eager dispatches), under the
    ``als_solve`` device scope."""
    with obs_profiler.device_scope("als_solve"):
        inner = jnp.sum(jnp.sum(m_last * f_last, axis=0) * lam)
        gall = functools.reduce(lambda a, b: a * b, grams)
        model_sq = matmul(lam, matmul(gall, lam))
        resid_sq = jnp.maximum(norm_x ** 2 - 2.0 * inner + model_sq, 0.0)
        return 1.0 - jnp.sqrt(resid_sq) / norm_x


def als_sweep(plan: CPPlan, mesh: Mesh, dev_arrays: Sequence, state: ALSState,
              updates: Sequence[Callable] | None = None,
              **mttkrp_kw) -> ALSState:
    """One full sweep over all modes (Algorithm 1). Multi-sweep callers MUST
    pass ``updates`` (the jitted list from :func:`make_mode_update`, one per
    mode) — the ``updates=None`` convenience builds fresh jit closures whose
    traces are not shared across calls, recompiling every sweep.

    Fully async: the sweep only enqueues device work; the fit is appended as
    a device scalar and forces a host sync only when read (off CPU the
    updated factor overwrites the donated old buffer, so do not read factors
    of a pre-sweep ALSState afterwards). Each mode's dispatch is a
    ``mode_update`` span (attribute ``mode``); the span waits for nothing,
    so with the tracer on the sweep runs exactly as with it off."""
    n = plan.nmodes
    if updates is None:
        updates = [make_mode_update(plan, d, mesh, **mttkrp_kw) for d in range(n)]
    tracer = obs_trace.get_tracer()
    factors, grams = list(state.factors), list(state.grams)
    m_last = f_last = lam = None
    for d in range(n):
        with tracer.span("mode_update", mode=d):
            others = [factors[w] for w in range(n) if w != d]
            f_d, g_d, m_d, lam = updates[d](factors[d], dev_arrays[d],
                                            others, grams)
        factors[d], grams[d] = f_d, g_d
        m_last, f_last = m_d, f_d
    fit = fit_from_stats(plan.norm, m_last, f_last, lam, grams)
    return ALSState(factors=factors, lam=lam, grams=grams,
                    sweep=state.sweep + 1, fits=state.fits + [fit])


def unpad_factors(plan: CPPlan, factors: Sequence[jax.Array]) -> list[np.ndarray]:
    """Packed ownership layout → global row order (I_w, R)."""
    return [np.asarray(f)[plan.global_to_padded[w]]
            for w, f in enumerate(factors)]
