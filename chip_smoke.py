#!/usr/bin/env python3
"""Smoke test of the CP-ALS main path on TPU chips, at a real data size.

    python chip_smoke.py              # one chip: ref / fused / sorted
    python chip_smoke.py --chips 4    # only the sharded 4-chip phase

One chip (the default): an ``amazon``-profile tensor (paper Table 3: three
modes, Zipf 1.1, linearly scaled by ``--scale``) is generated from a seed,
planned once with pinned geometry (tile 16, block_p 128, row-sorted layout,
the paper's r=1 partitioning) and decomposed at rank 32 through the public
path a user calls (``api.plan -> api.compile -> solver.run``) with the
``ref``, ``fused`` and ``sorted`` EC variants, all from the same initial
factors. It checks that

  * every fit is finite and the fits rise over the sweeps,
  * each Pallas variant's per-sweep fit is within ``FIT_ATOL`` of ``ref``'s,
  * one EC call per Pallas variant (``fused`` and ``sorted`` on the whole
    mode-0 shard, ``blocked`` on its first ``SLICE_BLOCKS`` blocks, which
    is what fits its gathered intermediate) is within ``EC_RTOL`` relative
    Frobenius error of ``ref``'s MTTKRP, and on those blocks every
    variant, ``ref`` included, is within ``EC_RTOL`` of an f64 MTTKRP on
    the host,
  * every compiled Pallas program holds ``tpu_custom_call`` (the kernels ran
    compiled, not interpreted).

``--chips 4`` runs only the sharded phase: the same tensor decomposed with
the ``fused`` EC on four chips, then on one chip in the same process; the
fits must agree within ``FIT_ATOL``, and each device's shard bytes are
printed.

Times are printed for information (``plan | compile | execute``; compile is
set-up: device placement and the ahead-of-time compile of every mode
update). The compilation cache is ``JAX_COMPILATION_CACHE_DIR`` if set, else
``<repo>/.jax_cache``. Everything runs in this one process.

The last line of stdout is one JSON object, ``{"ok": true, "device":
{...}}``, printed only when every check passed. Off-TPU, or on any failed
check, the script exits nonzero without it.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

RANK = 32
TILE, BLOCK_P, NUM_BUFFERS = 16, 128, 2
SEED = 0
VARIANTS = ("ref", "fused", "sorted")
SHARDED_VARIANT = "fused"
SLICE_BLOCKS = 8192   # 1 M nonzeros: blocked's (nnz, R) gather fits
# The variants sum in different f32 orders (MXU one-hot commit per block vs
# sequential segment adds vs XLA's scatter), on rows that sum up to about a
# million products at Zipf 1.1: their MTTKRPs differ by about 2e-5
# relative at 42 M nonzeros (v5e). EC_RTOL bounds that rounding, far below
# a kernel bug. Three ALS sweeps from a random start turn those EC
# differences into fit differences of up to about 3e-4 (v5e, fused vs ref),
# so the fit check is the coarse one and FIT_ATOL sits above that.
EC_RTOL = 1e-4
FIT_ATOL = 1e-3


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def _now() -> float:
    return time.perf_counter()


def _config(api, variant: str, num_devices: int):
    return api.preset("paper", {
        "rank": RANK,
        "partition.tile": TILE,
        "partition.block_p": BLOCK_P,
        "partition.layout": "sorted",
        "kernel.variant": variant,
        "kernel.num_buffers": NUM_BUFFERS,
        "runtime.num_devices": num_devices,
        "runtime.tol": 0.0,
        "runtime.seed": SEED,
    })


def _tensor(scale: float):
    from repro.sparse.io import make_profile_tensor
    t0 = _now()
    t = make_profile_tensor("amazon", scale=scale, seed=SEED)
    log(f"tensor: amazon scale={scale} shape={t.shape} nnz={t.nnz} "
        f"(generated in {_now() - t0:.1f}s)")
    return t


class _CompileCounter:
    """Counts XLA backend compilations (jax.monitoring), so the timed
    window can report the compilations it contains."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name.endswith("backend_compile_duration"):
            self.count += 1


def _decompose(api, jax, plan, cfg, sweeps: int, counter: _CompileCounter):
    """compile + run one solver; returns (solver, info)."""
    t0 = _now()
    solver = api.compile(plan, cfg)
    state, dev = solver.state, solver.dev_arrays
    texts = []
    for d, update in enumerate(solver.updates):
        others = [state.factors[w] for w in range(plan.nmodes) if w != d]
        texts.append(update.lower(state.factors[d], dev[d], others,
                                  state.grams).compile().as_text())
    jax.block_until_ready(dev)
    t_compile = _now() - t0
    before = counter.count
    t1 = _now()
    res = solver.run(sweeps)
    t_exec = _now() - t1
    fits = [float(f) for f in res.fits]
    check(len(fits) == sweeps, f"{cfg.kernel.variant}: {len(fits)} sweeps "
          f"ran, expected {sweeps}")
    check(all(math.isfinite(f) for f in fits),
          f"{cfg.kernel.variant}: non-finite fit in {fits}")
    check(all(b > a for a, b in zip(fits, fits[1:])),
          f"{cfg.kernel.variant}: fits do not rise: {fits}")
    if cfg.kernel.variant != "ref":
        check(all("tpu_custom_call" in t for t in texts),
              f"{cfg.kernel.variant}: a compiled mode update holds no "
              f"tpu_custom_call (kernel not compiled for the chip)")
    info = dict(compile_s=t_compile, execute_s=t_exec, fits=fits,
                compiles_in_execute=counter.count - before)
    return solver, info


def _resident_bytes(dev_arrays) -> int:
    import jax
    return sum(leaf.nbytes for d in dev_arrays
               for leaf in jax.tree_util.tree_leaves(d))


def _ec_exact(solver, n: int):
    """Mode 0's MTTKRP over its shard's first ``n`` nonzeros, in f64 on the
    host, from the solver's current factors."""
    import numpy as np
    dev = solver.dev_arrays[0]
    idx = np.asarray(dev.indices[0, 0, :n])
    prod = np.asarray(dev.values[0, 0, :n], np.float64)[:, None]
    for w in range(1, solver.plan.nmodes):
        prod = prod * np.asarray(solver.state.factors[w], np.float64)[idx[:, w]]
    out = np.zeros((solver.plan.modes[0].rows_max, prod.shape[1]))
    np.add.at(out, np.asarray(dev.local_rows[0, 0, :n]), prod)
    return out


def _ec_checks(jax, solver) -> dict:
    """EC calls on mode 0's shard from the solver's initial factors:
    ``fused`` and ``sorted`` on the whole shard against ``ref``; on its
    first ``SLICE_BLOCKS`` blocks (what ``blocked``'s gathered intermediate
    fits in) ``blocked`` against ``ref``, and every variant against an f64
    host MTTKRP, which shows how near each f32 summation order lands to
    the exact sums. Returns ``{what: relative Frobenius error}``."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops as kops
    part = solver.plan.modes[0]
    dev = solver.dev_arrays[0]
    factors = solver.state.factors
    n = min(SLICE_BLOCKS, part.nblocks) * part.block_p

    def ec(variant, nnz=None):
        def fn(dev, factors):
            def cut(x, per_block=False):
                x = x[0, 0]
                if nnz is None:
                    return x
                return x[:nnz // part.block_p] if per_block else x[:nnz]
            return kops.mttkrp_local(
                cut(dev.indices), cut(dev.values), cut(dev.local_rows),
                cut(dev.block_to_tile, True), factors, mode=0,
                num_rows=part.rows_max, tile=part.tile, block_p=part.block_p,
                variant=variant, num_buffers=NUM_BUFFERS,
                tile_mask=dev.tile_visited[0, 0] if nnz is None else None,
                seg_starts=cut(dev.seg_starts, True),
                seg_rows=cut(dev.seg_rows, True))
        compiled = jax.jit(fn).lower(dev, factors).compile()
        if variant != "ref":
            check("tpu_custom_call" in compiled.as_text(),
                  f"EC {variant}: compiled program holds no tpu_custom_call")
        return compiled(dev, factors)

    def rel_err(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    def rel_err64(a, exact):
        a = np.asarray(a, np.float64)
        return float(np.linalg.norm(a - exact) / np.linalg.norm(exact))

    errs = {}
    ref_full = ec("ref")
    for variant in ("fused", "sorted"):
        errs[f"{variant} vs ref, whole shard"] = rel_err(ec(variant), ref_full)
    del ref_full
    exact = _ec_exact(solver, n)
    out = {v: ec(v, n) for v in ("ref", "blocked", "fused", "sorted")}
    errs[f"blocked vs ref, first {n} nonzeros"] = rel_err(out["blocked"],
                                                          out["ref"])
    for variant, o in out.items():
        errs[f"{variant} vs f64, first {n} nonzeros"] = rel_err64(o, exact)
    for what, err in errs.items():
        check(math.isfinite(err) and err <= EC_RTOL,
              f"EC {what}: relative error {err:.3e} exceeds {EC_RTOL:.0e}")
    return errs


def _close(solver) -> None:
    solver.close()
    del solver
    gc.collect()


def one_chip(api, jax, scale: float, sweeps: int) -> None:
    counter = _CompileCounter()
    t = _tensor(scale)
    t0 = _now()
    plan = api.plan(t, _config(api, "ref", 1))
    t_plan = _now() - t0
    del t
    log(f"plan: {t_plan:.1f}s nnz_max/mode="
        f"{[p.nnz_max for p in plan.modes]} rows_max/mode="
        f"{[p.rows_max for p in plan.modes]}")
    fits = {}
    for variant in VARIANTS:
        solver, info = _decompose(api, jax, plan, _config(api, variant, 1),
                                  sweeps, counter)
        if variant == "ref":
            log(f"resident device bytes: "
                f"{_resident_bytes(solver.dev_arrays)}")
            solver.reset()
            for what, err in _ec_checks(jax, solver).items():
                log(f"EC {what}: relative error {err:.3e} "
                    f"(limit {EC_RTOL:.0e})")
        fits[variant] = info["fits"]
        log(f"{variant}: plan {t_plan:.1f}s | compile {info['compile_s']:.1f}s"
            f" | execute {info['execute_s']:.1f}s ({sweeps} sweeps, "
            f"{info['compiles_in_execute']} compilations inside) "
            f"[times informational]")
        log(f"{variant}: fits " + " ".join(f"{f:.7f}" for f in info["fits"]))
        _close(solver)
    for variant in VARIANTS[1:]:
        diff = max(abs(a - b) for a, b in zip(fits[variant], fits["ref"]))
        log(f"{variant}: max |fit - ref fit| = {diff:.3e} "
            f"(limit {FIT_ATOL:.0e})")
        check(diff <= FIT_ATOL, f"{variant}: fits {fits[variant]} differ "
              f"from ref {fits['ref']} by {diff:.3e}")


def four_chips(api, jax, scale: float, sweeps: int) -> None:
    check(len(jax.devices()) >= 4,
          f"--chips 4 needs 4 devices, JAX sees {len(jax.devices())}")
    counter = _CompileCounter()
    t = _tensor(scale)
    fits = {}
    for nd in (4, 1):
        cfg = _config(api, SHARDED_VARIANT, nd)
        t0 = _now()
        plan = api.plan(t, cfg)
        t_plan = _now() - t0
        solver, info = _decompose(api, jax, plan, cfg, sweeps, counter)
        if nd == 4:
            per_dev: dict = {}
            for d in solver.dev_arrays:
                for leaf in jax.tree_util.tree_leaves(d):
                    for shard in leaf.addressable_shards:
                        per_dev[shard.device.id] = \
                            per_dev.get(shard.device.id, 0) + shard.data.nbytes
            log("shard bytes per device: " + " ".join(
                f"dev{k}={v}" for k, v in sorted(per_dev.items())))
            log("real nonzeros per device, per mode: " + " ".join(
                str(p.nnz_true.tolist()) for p in plan.modes))
            check(len(per_dev) == 4 and min(per_dev.values()) > 0,
                  f"shards did not spread over 4 devices: {per_dev}")
        fits[nd] = info["fits"]
        log(f"{nd} chip(s) {SHARDED_VARIANT}: plan {t_plan:.1f}s | compile "
            f"{info['compile_s']:.1f}s | execute {info['execute_s']:.1f}s "
            f"[times informational]")
        log(f"{nd} chip(s): fits " + " ".join(f"{f:.7f}" for f in fits[nd]))
        _close(solver)
        del plan
    diff = max(abs(a - b) for a, b in zip(fits[4], fits[1]))
    log(f"4 vs 1 chip: max |fit difference| = {diff:.3e} "
        f"(limit {FIT_ATOL:.0e})")
    check(diff <= FIT_ATOL, f"4-chip fits {fits[4]} differ from 1-chip "
          f"fits {fits[1]} by {diff:.3e}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=float, default=0.06,
                    help="linear scale of the amazon profile")
    ap.add_argument("--sweeps", type=int, default=3)
    args = ap.parse_args()

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()

    import jax
    import repro.api as api
    devices = jax.devices()
    d0 = devices[0]
    warm = len(os.listdir(cache_dir)) if cache_dir and \
        os.path.isdir(cache_dir) else 0
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)} compile_cache={cache_dir} ({warm} entries "
        f"at start)")
    try:
        check(d0.platform == "tpu", f"JAX found no TPU (platform "
              f"{d0.platform!r})")
        if args.chips == 4:
            four_chips(api, jax, args.scale, args.sweeps)
        else:
            one_chip(api, jax, args.scale, args.sweeps)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
