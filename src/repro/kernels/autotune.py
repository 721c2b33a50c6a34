"""Autotuner for the MTTKRP EC kernel: sweep (tile, block_p, num_buffers).

The EC's throughput depends on three launch parameters that are baked in at
partition time (tile, block_p — they shape the blocking done by
core/partition.py) or at kernel-build time (num_buffers — the DMA ring
depth of the fused/sorted variants). The best point depends on (nmodes, R)
and on the backend, not on the particular tensor: the kernel streams
fixed-size (block_p, R) slabs whatever the sparsity pattern. So the tuner
times each candidate on a small *representative shard* (a synthetic zipf
tensor run through the real partitioner, in the variant's block layout) and
caches the winner per ``(nmodes, rank, dtype, backend, device kind,
variant)``.

Cache format v3 (JSON, see EXPERIMENTS.md §Autotuner):

    {"_format": 3,
     "<nmodes>m_r<rank>_<dtype>_<backend>_<kind>_<variant>":
        {"tile": 8, "block_p": 128, "num_buffers": 2,
         "grid": {"nnz": 4096, "tiles": [8, 16], ...},
         "timings": {"t8_p128_b2": 0.0012, ...}}}

The key is backend-aware twice over: ``backend`` is the platform
(``cpu``/``gpu``/``tpu``) and ``kind`` the sanitized
``jax.devices()[0].device_kind`` (e.g. ``tpu-v4``) — winners tuned on one
accelerator generation never replay on another. The factor dtype is part of
the key too: a bf16 sweep and an fp32 sweep (or different ranks) must never
replay each other's tile/block_p winners. Loading an older cache migrates
its entries in place and idempotently: v1 keys (no dtype slot; always timed
at fp32) gain a ``float32`` segment, v2 keys (no device-kind slot) gain a
kind equal to their backend segment — the best available stand-in, and
exact on CPU where the kind IS ``cpu``; ``xchg_...`` exchange entries pass
through untouched; unrecognizable keys are dropped.

An entry is only reused when its ``grid`` matches the requested sweep —
asking for a different candidate grid re-tunes instead of silently
returning a winner from a grid that never contained your candidates.

The same file also stores the exchange chunk-size winners of
:mod:`repro.comm.autotune` under ``xchg_...`` keys.

Default location ``~/.cache/amped/autotune.json``; override with the
``AMPED_AUTOTUNE_CACHE`` environment variable (empty string disables the
on-disk cache; an in-process dict always memoizes).
"""
from __future__ import annotations

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.obs import clock
from repro.kernels import ops as kops

__all__ = ["ECConfig", "autotune_ec", "cache_path", "representative_shard",
           "device_kind_tag", "CACHE_FORMAT_VERSION", "DEFAULT_TILES",
           "DEFAULT_BLOCK_PS", "DEFAULT_NUM_BUFFERS"]

ENV_CACHE = "AMPED_AUTOTUNE_CACHE"
CACHE_FORMAT_VERSION = 3  # v3: device kind in the entry key

DEFAULT_TILES = (8, 16)
DEFAULT_BLOCK_PS = (64, 128)
DEFAULT_NUM_BUFFERS = (2, 3)

# v1 entry key: "<nmodes>m_r<rank>_<backend>_<variant>" (no dtype slot);
# v2 adds a dtype segment between rank and backend (5 segments total);
# v3 adds a device-kind segment between backend and variant (6 segments).
_V1_KEY_RE = re.compile(r"^(\d+m_r\d+)_([a-z]+)_(ref|blocked|fused)$")
_V2_KEY_RE = re.compile(
    r"^(\d+m_r\d+_[a-z]+\d+)_([a-z]+)_(ref|blocked|fused|sorted)$")
_V3_KEY_RE = re.compile(
    r"^\d+m_r\d+_[a-z]+\d+_[a-z]+_[a-z0-9.-]+_(ref|blocked|fused|sorted)$")

_MEMO: dict[str, tuple[dict, "ECConfig"]] = {}  # key -> (grid, winner)


@dataclasses.dataclass(frozen=True)
class ECConfig:
    tile: int
    block_p: int
    num_buffers: int
    timings: dict = dataclasses.field(default_factory=dict, compare=False)


def cache_path() -> str | None:
    p = os.environ.get(ENV_CACHE)
    if p == "":
        return None
    return p or os.path.expanduser("~/.cache/amped/autotune.json")


def _dtype_tag(dtype) -> str:
    return np.dtype(dtype).name  # "float32", "bfloat16", ...


def device_kind_tag() -> str:
    """Sanitized ``jax.devices()[0].device_kind`` — the accelerator
    generation slot of the v3 cache key (e.g. ``cpu``, ``tpu-v4``)."""
    kind = jax.devices()[0].device_kind.strip().lower()
    kind = re.sub(r"[\s_]+", "-", kind)
    return re.sub(r"[^a-z0-9.-]", "", kind) or "unknown"


def _cache_key(nmodes: int, rank: int, backend: str, variant: str,
               dtype=jnp.float32, kind: str | None = None) -> str:
    kind = device_kind_tag() if kind is None else kind
    return (f"{nmodes}m_r{rank}_{_dtype_tag(dtype)}_{backend}_{kind}_"
            f"{variant}")


def _migrate_cache(cache: dict) -> dict:
    """Re-key an older cache to v3. v1 winners were always timed with fp32
    factors, so ``3m_r8_cpu_fused`` first becomes
    ``3m_r8_float32_cpu_fused``; any v2 key then gains a device-kind
    segment equal to its backend segment (``..._cpu_fused`` →
    ``..._cpu_cpu_fused``) — exact on CPU, the best stand-in elsewhere.
    Keys already in v3 form and ``xchg_...`` exchange entries pass through
    unchanged — the migration is idempotent; keys matching no known format
    are stale and dropped rather than replayed."""
    out: dict = {"_format": CACHE_FORMAT_VERSION}
    for key, entry in cache.items():
        if key.startswith("_"):
            continue
        if key.startswith("xchg_") or _V3_KEY_RE.match(key):
            out[key] = entry
            continue
        m = _V1_KEY_RE.match(key)
        if m:  # v1 → v2 form, then fall through to the v2 → v3 step
            key = f"{m.group(1)}_float32_{m.group(2)}_{m.group(3)}"
        m = _V2_KEY_RE.match(key)
        if m:
            out[f"{m.group(1)}_{m.group(2)}_{m.group(2)}_{m.group(3)}"] = \
                entry
    return out


# Historical name (the v1→v2 migration); now the full chain migration.
_migrate_v1 = _migrate_cache


def _load_cache(path: str | None) -> dict:
    if path and os.path.exists(path):
        try:
            with open(path) as f:
                cache = json.load(f)
        except (OSError, json.JSONDecodeError):
            return {}
        if cache.get("_format") != CACHE_FORMAT_VERSION:
            cache = _migrate_cache(cache)
            _store_cache(path, cache)  # persist once; later loads are v3
        return cache
    return {}


def _store_cache(path: str | None, cache: dict) -> None:
    if not path:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(cache, f, indent=1, sort_keys=True)
    except OSError:
        pass  # read-only filesystems: the in-process memo still applies


def representative_shard(nmodes: int, nnz: int, tile: int | None = None,
                         block_p: int | None = None, seed: int = 0,
                         layout: str = "blocked"):
    """A zipf-skewed synthetic tensor run through the real partitioner, so
    candidates are timed on exactly the blocking they would produce
    (``layout`` selects the pad-row placement — ``"sorted"`` for the
    row-sorted hierarchical-COO variant). Returns (tensor, single-device
    ModePartition for mode 0). Shared by the tuner and
    benchmarks/bench_mttkrp.py."""
    from repro.core.coo import random_sparse
    from repro.core.partition import partition_mode
    dim = max(16, int(round(nnz ** (1.0 / nmodes))) * 2)
    t = random_sparse((dim,) * nmodes, nnz, seed=seed, distribution="zipf")
    kw = {}
    if tile is not None:
        kw.update(tile=tile, block_p=block_p)
    part, _, _ = partition_mode(t, 0, 1, strategy="amped_cdf", replication=1,
                                layout=layout, **kw)
    return t, part


def _time_candidate(t, part, rank: int, variant: str, num_buffers: int,
                    interpret: bool, repeats: int, seed: int = 0,
                    dtype=jnp.float32) -> float:
    rng = np.random.default_rng(seed)
    factors = [jnp.asarray(rng.normal(size=(s, rank))).astype(dtype)
               for s in t.shape]
    args = (jnp.asarray(part.indices[0]), jnp.asarray(part.values[0]),
            jnp.asarray(part.local_rows[0]),
            jnp.asarray(part.block_to_tile[0]))
    mask = jnp.asarray(part.tile_visited[0])
    seg_kw = {}
    if variant == "sorted":
        from repro.core.partition import block_segment_descriptors
        ss, sr = block_segment_descriptors(part.local_rows[0],
                                           tile=part.tile,
                                           block_p=part.block_p)
        seg_kw = dict(seg_starts=jnp.asarray(ss), seg_rows=jnp.asarray(sr))

    @jax.jit
    def run(indices, values, local_rows, block_to_tile, facs):
        return kops.mttkrp_local(
            indices, values, local_rows, block_to_tile, facs,
            mode=0, num_rows=part.rows_max, tile=part.tile,
            block_p=part.block_p, variant=variant, num_buffers=num_buffers,
            interpret=interpret, tile_mask=mask, **seg_kw)

    run(*args, factors).block_until_ready()  # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = clock.now()
        run(*args, factors).block_until_ready()
        best = min(best, clock.now() - t0)
    return best


def autotune_ec(
    nmodes: int,
    rank: int,
    *,
    variant: str = "fused",
    nnz: int = 4096,
    tiles=DEFAULT_TILES,
    block_ps=DEFAULT_BLOCK_PS,
    num_buffers_grid=DEFAULT_NUM_BUFFERS,
    repeats: int = 3,
    interpret: bool | None = None,
    force: bool = False,
    dtype=jnp.float32,
) -> ECConfig:
    """Sweep the candidate grid on a representative shard; return (and
    cache) the fastest ``ECConfig`` for
    ``(nmodes, rank, dtype, backend, device kind, variant)``. ``dtype`` is
    the factor dtype the candidates are timed with — part of the cache key,
    so fp32 and bf16 sweeps never replay each other's winners.

    Variants without a DMA ring (``ref``, ``blocked``) collapse the
    ``num_buffers`` axis; ``sorted`` candidates are timed on the row-sorted
    layout they require.
    """
    variant = kops.resolve_variant(variant)
    backend = jax.default_backend()
    if interpret is None:
        interpret = kops.default_interpret()
    if variant not in ("fused", "sorted"):
        num_buffers_grid = (2,)  # no DMA ring: the axis is meaningless
    layout = "sorted" if variant == "sorted" else "blocked"
    key = _cache_key(nmodes, rank, backend, variant, dtype)
    # A cached winner is only valid for the grid that produced it.
    grid = {"nnz": nnz, "tiles": list(tiles), "block_ps": list(block_ps),
            "num_buffers_grid": list(num_buffers_grid)}

    if not force:
        memo = _MEMO.get(key)
        if memo is not None and memo[0] == grid:
            obs.get_registry().inc("autotune.ec.memo_hits")
            return memo[1]
        disk = _load_cache(cache_path()).get(key)
        if disk is not None and disk.get("grid") == grid:
            obs.get_registry().inc("autotune.ec.cache_hits")
            cfg = ECConfig(int(disk["tile"]), int(disk["block_p"]),
                           int(disk["num_buffers"]),
                           dict(disk.get("timings", {})))
            _MEMO[key] = (grid, cfg)
            return cfg
    obs.get_registry().inc("autotune.ec.misses")

    timings: dict[str, float] = {}
    best, best_t = None, float("inf")
    for tile in tiles:
        for block_p in block_ps:
            t, part = representative_shard(nmodes, nnz, tile, block_p,
                                           layout=layout)
            for nb in num_buffers_grid:
                dt = _time_candidate(t, part, rank, variant, nb,
                                     interpret, repeats, dtype=dtype)
                timings[f"t{tile}_p{block_p}_b{nb}"] = dt
                if dt < best_t:
                    best_t, best = dt, (tile, block_p, nb)

    assert best is not None
    best_cfg = ECConfig(*best, dict(timings))
    _MEMO[key] = (grid, best_cfg)
    path = cache_path()
    cache = _load_cache(path)
    cache["_format"] = CACHE_FORMAT_VERSION
    cache[key] = {"tile": best_cfg.tile, "block_p": best_cfg.block_p,
                  "num_buffers": best_cfg.num_buffers, "grid": grid,
                  "timings": timings}
    _store_cache(path, cache)
    return best_cfg
