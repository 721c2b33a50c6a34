"""Plan layer: preprocessing once, reuse everywhere.

AMPED's pipeline is staged — partition/preprocess once, then many MTTKRP+ALS
sweeps — and at billion scale the preprocessing is minutes of host work. This
module makes that stage a first-class, serializable artifact:

    cfg  = api.preset("paper")
    plan = api.plan(tensor, cfg, cache_dir="plans/")   # built once
    plan = api.plan(tensor, cfg, cache_dir="plans/")   # cache hit, no repartition

``plan()`` keys the on-disk cache by a **content signature** of the tensor
(shape, nnz, a strided sample digest of indices/values) and of every
partition-relevant config field (strategy, replication, resolved tile /
block_p, device count) — the same discipline ``kernels/autotune.py`` applies
to its winner cache: an entry is only reused when the signature that produced
it matches exactly; anything else rebuilds. ``save_plan``/``load_plan`` are
the underlying serialization (npz arrays + JSON manifest) and can also be
used directly to ship a plan between processes or hosts.
"""
from __future__ import annotations

import hashlib
import json
import os
import sys

import jax
import numpy as np

from repro import obs
from repro.obs import trace as obs_trace
from repro.api.config import DecomposeConfig
from repro.core import partition as partition_mod
from repro.core.coo import SparseTensor
from repro.core.partition import CPPlan, ModeLayout, ModePartition
from repro.store import TensorStore
from repro.store import plan as store_plan_mod

__all__ = ["plan", "plan_signature", "save_plan", "load_plan",
           "PlanSignatureError", "CACHE_STATS", "reset_cache_stats"]

# v2: ModePartition.blocks_true + rebalance_epoch; v3: lazy (out-of-core)
# plans — store-backed manifests carry a store path + digest instead of the
# O(nnz) arrays; v4: the packed ownership layout (core/partition.py) — the
# row translations address packed factors, and each mode's manifest entry
# carries its groups' ``rows_owned``.
PLAN_FORMAT_VERSION = 4
_SAMPLE_CAP = 65536  # strided digest sample size (cheap at billion scale)

# Observability for tests and ops dashboards: how often plan() rebuilt vs
# reused. Process-wide; reset with reset_cache_stats().
CACHE_STATS = {"hits": 0, "misses": 0}


def reset_cache_stats() -> None:
    CACHE_STATS["hits"] = 0
    CACHE_STATS["misses"] = 0


class PlanSignatureError(ValueError):
    """A stored plan's signature does not match the requesting problem."""


def _tensor_digest(t) -> str:
    """Cheap content digest: shape/nnz plus a strided sample of coordinates
    and values. O(min(nnz, _SAMPLE_CAP)) — never a full scan at billion
    scale, yet any nnz/shape change and almost any data change re-keys.

    An out-of-core :class:`~repro.store.TensorStore` is keyed by its
    manifest digest instead — zero data reads; the manifest already hashes
    shape, nnz, dtypes and every chunk's statistics."""
    if isinstance(t, TensorStore):
        return f"store:{t.digest}"
    h = hashlib.sha256()
    h.update(repr((tuple(int(s) for s in t.shape), int(t.nnz))).encode())
    if t.nnz:
        step = max(1, t.nnz // _SAMPLE_CAP)
        h.update(np.ascontiguousarray(t.indices[::step]).tobytes())
        h.update(np.ascontiguousarray(t.values[::step]).tobytes())
    return h.hexdigest()


def _resolve_geometry(tensor_nmodes: int, config: DecomposeConfig
                      ) -> tuple[int | None, int | None]:
    """Resolve (tile, block_p) the way ``cp_decompose`` historically did:
    explicit partition config > autotuned winner > partitioner defaults
    (returned as None so the partitioner applies them)."""
    tile, block_p = config.partition.tile, config.partition.block_p
    if config.kernel.autotune:
        variant = config.kernel.resolved_variant()
        if variant != "ref":  # the grid times the Pallas variants only
            from repro.kernels.autotune import autotune_ec
            tuned = autotune_ec(tensor_nmodes, config.rank, variant=variant)
            if tile is None:
                tile = tuned.tile
            if block_p is None:
                block_p = tuned.block_p
    return tile, block_p


def _resolve_num_devices(config: DecomposeConfig,
                         num_devices: int | None) -> int:
    if num_devices is not None:
        return num_devices
    if config.runtime.num_devices is not None:
        return config.runtime.num_devices
    return len(jax.devices())


def plan_signature(tensor: SparseTensor | TensorStore,
                   config: DecomposeConfig, *,
                   num_devices: int | None = None,
                   rebalance_epoch: int = 0) -> str:
    """Content signature keying the plan cache: tensor identity + every
    config field that changes the partition output. The strategy is the
    *resolved* scheduling policy (``schedule.policy`` overrides
    ``partition.strategy``). ``rebalance_epoch`` extends the signature for
    plans evolved by the dynamic rebalancer — epoch k+1 never aliases the
    epoch-k plan it migrated from."""
    nd = _resolve_num_devices(config, num_devices)
    tile, block_p = _resolve_geometry(tensor.nmodes, config)
    payload = {
        "format": PLAN_FORMAT_VERSION,
        "tensor": _tensor_digest(tensor),
        "num_devices": nd,
        "strategy": config.resolved_policy(),
        "replication": config.partition.replication,
        "tile": tile,
        "block_p": block_p,
        "layout": config.partition.layout,
        "rebalance_epoch": int(rebalance_epoch),
    }
    return hashlib.sha256(
        json.dumps(payload, sort_keys=True).encode()).hexdigest()


# -- serialization ------------------------------------------------------------

def save_plan(p: CPPlan, path: str, *, signature: str | None = None) -> str:
    """Write a plan to ``path`` (a directory): ``manifest.json`` with all
    scalar metadata (+ optional signature) and ``arrays.npz`` with every
    ModePartition array plus the global↔padded translations, bit-exact.

    Lazy (store-backed) plans persist only the layout — the manifest
    records the tensor store's path and digest instead of the O(nnz)
    arrays, and :func:`load_plan` rebinds to the store (refusing a store
    whose digest changed)."""
    os.makedirs(path, exist_ok=True)
    lazy = bool(getattr(p.modes[0], "lazy", False)) if p.modes else False
    arrays: dict[str, np.ndarray] = {}
    manifest = {
        "format_version": PLAN_FORMAT_VERSION,
        "signature": signature,
        "shape": [int(s) for s in p.shape],
        "num_devices": int(p.num_devices),
        "norm": float(p.norm),
        "rebalance_epoch": int(p.rebalance_epoch),
        "lazy": lazy,
        "modes": [],
    }
    if lazy:
        store = p.modes[0].store
        manifest["store"] = {"path": os.path.abspath(store.path),
                             "digest": store.digest}
    for d, part in enumerate(p.modes):
        # META_FIELDS are ints except block_layout (a layout-name string)
        meta = {k: (v if isinstance(v, str) else int(v))
                for k in ModePartition.META_FIELDS
                for v in (getattr(part, k),)}
        meta["rows_owned"] = [int(x) for x in part.rows_owned]
        manifest["modes"].append(meta)
        if not lazy:
            for k in ModePartition.ARRAY_FIELDS:
                arrays[f"mode{d}_{k}"] = getattr(part, k)
        arrays[f"g2p_{d}"] = np.asarray(p.global_to_padded[d])
        arrays[f"p2g_{d}"] = np.asarray(p.padded_to_global[d])
    tmp = os.path.join(path, "arrays.npz.tmp")
    with open(tmp, "wb") as f:
        np.savez(f, **arrays)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, os.path.join(path, "arrays.npz"))
    with open(os.path.join(path, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return path


def load_plan(path: str, *, expect_signature: str | None = None) -> CPPlan:
    """Load a plan saved by :func:`save_plan`. If ``expect_signature`` is
    given and the stored manifest's signature differs (different tensor,
    strategy, device count, ...), raise :class:`PlanSignatureError` rather
    than silently handing back a plan for another problem."""
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    if manifest.get("format_version") != PLAN_FORMAT_VERSION:
        raise PlanSignatureError(
            f"plan at {path!r} has format {manifest.get('format_version')}, "
            f"expected {PLAN_FORMAT_VERSION}")
    if expect_signature is not None and \
            manifest.get("signature") != expect_signature:
        raise PlanSignatureError(
            f"plan at {path!r} was built for a different problem "
            f"(stored signature {str(manifest.get('signature'))[:16]}…, "
            f"expected {expect_signature[:16]}…)")
    with np.load(os.path.join(path, "arrays.npz")) as npz:
        modes, g2ps, p2gs = [], [], []
        for d, meta in enumerate(manifest["modes"]):
            if not manifest.get("lazy"):
                # META_FIELDS are ints except block_layout
                fields = {k: (meta[k] if k == "block_layout"
                              else int(meta[k]))
                          for k in ModePartition.META_FIELDS}
                fields.update({k: npz[f"mode{d}_{k}"]
                               for k in ModePartition.ARRAY_FIELDS})
                modes.append(ModePartition(**fields))
            g2ps.append(npz[f"g2p_{d}"])
            p2gs.append(npz[f"p2g_{d}"])
    if manifest.get("lazy"):
        modes = _rebind_lazy_modes(path, manifest, g2ps, p2gs)
    return CPPlan(
        shape=tuple(manifest["shape"]),
        num_devices=int(manifest["num_devices"]),
        modes=tuple(modes),
        global_to_padded=tuple(g2ps),
        padded_to_global=tuple(p2gs),
        norm=float(manifest["norm"]),
        rebalance_epoch=int(manifest.get("rebalance_epoch", 0)),
    )


def _rebind_lazy_modes(path: str, manifest: dict, g2ps, p2gs):
    """Reattach a persisted lazy plan to its tensor store: reopen the store
    named in the manifest, verify its digest is still the one the plan was
    built from, and rebuild the lazy partitions from the saved layouts
    (owner groups are recoverable from the packed rows and the groups'
    offsets, :func:`~repro.core.partition.owner_from_packed`; everything
    else re-derives from the store's histogram sidecars)."""
    ref = manifest.get("store") or {}
    try:
        store = TensorStore(ref.get("path", ""))
    except (OSError, ValueError) as e:
        raise PlanSignatureError(
            f"lazy plan at {path!r} references tensor store "
            f"{ref.get('path')!r}, which no longer opens: {e}") from e
    if store.digest != ref.get("digest"):
        raise PlanSignatureError(
            f"lazy plan at {path!r} was built from store digest "
            f"{str(ref.get('digest'))[:16]}…, but {store.path!r} now has "
            f"{store.digest[:16]}… (store rewritten since planning)")
    layouts = []
    for d, meta in enumerate(manifest["modes"]):
        g2p = np.asarray(g2ps[d], np.int64)
        rows_owned = np.asarray(meta["rows_owned"], np.int64)
        layouts.append(ModeLayout(
            mode=int(meta["mode"]), num_devices=int(meta["num_devices"]),
            r=int(meta["r"]), n_groups=int(meta["n_groups"]),
            rows_max=int(meta["rows_max"]), tile=int(meta["tile"]),
            block_p=int(meta["block_p"]),
            owner=partition_mod.owner_from_packed(g2p, rows_owned),
            global_to_padded=g2p,
            padded_to_global=np.asarray(p2gs[d], np.int64),
            rows_owned=rows_owned,
            block_layout=meta["block_layout"]))
    return store_plan_mod.lazy_parts_from_layouts(store, layouts)


# -- the public entry ---------------------------------------------------------

def _analyze_plan(p: CPPlan, config: DecomposeConfig, analyze: str) -> CPPlan:
    """Run the static plan rules on a built or cache-loaded plan.
    ``"strict"`` raises :class:`~repro.analysis.AnalysisError` on error
    findings; ``"warn"`` prints every finding to stderr; ``"off"`` skips
    the pass entirely (zero import cost)."""
    if analyze == "off":
        return p
    if analyze not in ("warn", "strict"):
        raise ValueError(f"analyze must be 'off', 'warn', or 'strict', "
                         f"got {analyze!r}")
    from repro.analysis import AnalysisError, check_plan, errors
    findings = check_plan(p, config)
    for f in findings:
        print(f"analysis: {f}", file=sys.stderr)
    if analyze == "strict" and errors(findings):
        raise AnalysisError(errors(findings))
    return p


def plan(tensor: SparseTensor | TensorStore, config: DecomposeConfig, *,
         cache_dir: str | None = None,
         num_devices: int | None = None,
         analyze: str = "off") -> CPPlan:
    """Preprocess ``tensor`` for ``config``: autotune the blocking geometry
    (if requested), partition every mode, and — when ``cache_dir`` is given —
    reuse an on-disk plan with a matching content signature instead of
    repartitioning. Pure host work; returns a :class:`CPPlan`.

    ``tensor`` may be an out-of-core :class:`~repro.store.TensorStore`: the
    partition is then computed from the store's manifest histograms alone —
    no chunk data is read here — and the returned plan's modes materialize
    per-device shards by streaming at compile time
    (:class:`~repro.store.StoreModePartition`).

    ``analyze`` runs the :mod:`repro.analysis` plan rules on the result
    (built OR cache-loaded — a stale cached plan fails the same checks):
    ``"strict"`` raises on any error finding before the plan escapes,
    ``"warn"`` reports findings to stderr, ``"off"`` (default) skips.
    """
    with obs_trace.span("plan"):
        nd = _resolve_num_devices(config, num_devices)
        tile, block_p = _resolve_geometry(tensor.nmodes, config)

        sig = None
        if cache_dir is not None:
            sig = plan_signature(tensor, config, num_devices=nd)
            entry = os.path.join(cache_dir, sig[:32])
            if os.path.exists(os.path.join(entry, "manifest.json")):
                try:
                    p = partition_mod.validate_plan(
                        load_plan(entry, expect_signature=sig))
                    CACHE_STATS["hits"] += 1
                    obs.get_registry().inc("plan.cache_hits")
                    return _analyze_plan(p, config, analyze)
                except (PlanSignatureError, OSError, KeyError, ValueError):
                    pass  # corrupted/stale entry: rebuild below and overwrite

        CACHE_STATS["misses"] += 1
        obs.get_registry().inc("plan.cache_misses")
        if isinstance(tensor, TensorStore):
            p = store_plan_mod.build_plan_from_store(
                tensor, nd, strategy=config.resolved_policy(),
                replication=config.partition.replication, tile=tile,
                block_p=block_p, layout=config.partition.layout)
        else:
            p = partition_mod.build_plan(
                tensor, nd, strategy=config.resolved_policy(),
                replication=config.partition.replication, tile=tile,
                block_p=block_p, layout=config.partition.layout)
        if cache_dir is not None:
            try:
                save_plan(p, os.path.join(cache_dir, sig[:32]), signature=sig)
            except OSError:
                pass  # read-only filesystems: the plan still works in-process
        return _analyze_plan(p, config, analyze)
