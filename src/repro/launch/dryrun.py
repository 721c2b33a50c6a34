import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Production-mesh dry run of the CP workload: lower + compile one
distributed MTTKRP mode step (EC + exchange) at a dataset profile's full
billion-scale shape on 256 (one pod) or 512 (two pods) placeholder devices.

MUST be run as a module (``python -m repro.launch.dryrun``) — the XLA_FLAGS
line above executes before any other import so the host platform exposes 512
placeholder devices for ``jax.make_mesh``. Nothing here allocates real
arrays: the shards and factors are ShapeDtypeStructs.

Per cell it records: compile success, ``memory_analysis()`` (fits/doesn't),
``cost_analysis()`` FLOPs/bytes, per-device collective bytes parsed from the
post-SPMD HLO, and the derived three-term roofline → JSON under
``experiments/dryrun/``::

    python -m repro.launch.dryrun --mesh pod1 --cp-profile amazon
    python -m repro.launch.dryrun --mesh pod1 --cp-exchange-ab
"""
import argparse       # noqa: E402
import dataclasses    # noqa: E402
import json           # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402

import jax            # noqa: E402
import numpy as np    # noqa: E402

from repro import compat                                # noqa: E402
from repro.comm.hlo import parse_hlo                    # noqa: E402
from repro.launch import roofline as rf                 # noqa: E402
from repro.launch.mesh import make_cp_production_mesh   # noqa: E402

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def run_cp_cell(*, multi_pod: bool, profile: str = "amazon",
                replication: int = 1, use_kernel: bool = False,
                ring: bool = True, exchange_variant: str | None = None,
                wire_dtype: str = "float32", chunk_rows: int | None = None,
                save: bool = True, config=None) -> dict:
    """Dry-run of the paper's own workload: one distributed MTTKRP mode step
    (EC + exchange) on the production chips at billion-scale shapes.

    ``config`` (a :class:`repro.api.DecomposeConfig`) supersedes the scalar
    kwargs: replication/kernel/exchange settings are read off its sections
    (``replication=None`` in the config means auto — the dry run needs a
    concrete mesh factor, so it falls back to the ``replication`` kwarg).
    ``exchange_variant``/``wire_dtype``/``chunk_rows`` pick the exchange
    schedule directly (see :mod:`repro.comm`); :func:`run_cp_exchange_ab`
    compares the blocking and overlap schedules' HLO side by side.
    """
    from types import SimpleNamespace

    from repro import comm

    if config is not None:
        if config.partition.replication is not None:
            replication = config.partition.replication
        spec = comm.resolve_exchange_spec(config.exchange)
        # Explicit CLI exchange flags beat the preset's exchange section —
        # a user asking --cp-preset paper --cp-exchange overlap gets the
        # paper config with the overlap schedule, not a silent ignore.
        if exchange_variant is not None:
            spec = dataclasses.replace(spec, variant=exchange_variant)
        if chunk_rows is not None:
            spec = dataclasses.replace(spec, chunk_rows=chunk_rows)
        if wire_dtype != "float32":
            spec = dataclasses.replace(spec, wire_dtype=wire_dtype,
                                       merge="ring_rs")
    else:
        spec = comm.ExchangeSpec(
            variant=comm.resolve_variant(exchange_variant, ring),
            merge="ring_rs" if wire_dtype != "float32" else
            comm.resolve_merge(None),
            chunk_rows=chunk_rows, wire_dtype=wire_dtype)

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import mttkrp as dm
    from repro.core.partition import packed_rows
    from repro.sparse.io import DATASET_PROFILES

    prof = DATASET_PROFILES[profile]
    total = 512 if multi_pod else 256
    r = replication
    g = total // r
    mesh = make_cp_production_mesh(multi_pod=multi_pod, replication=r)
    rank = 32
    n = len(prof.shape)
    # resolve the kernel exactly as api.compile would for this problem
    # (including the autotuned num_buffers when the config asks for it)
    kernel_kw = ({"use_kernel": use_kernel} if config is None else
                 config.kernel.mttkrp_kwargs(nmodes=n, rank=rank))
    use_kernel = kernel_kw.get("use_kernel", use_kernel)
    mode = 0
    tile, block_p = 8, 128
    # balanced-partition shapes: nnz evenly split (CDF split ⇒ ±1 index)
    nnz_dev = int(np.ceil(prof.nnz / total / block_p) * block_p)

    def balanced(size):
        """(rows_max, rows_owned) of ``size`` indices split evenly over
        the groups, and the packed factor's rows."""
        rmax = int(np.ceil(size / g / tile) * tile)
        rmax = int(np.ceil(rmax / r) * r)
        owned = np.full(g, size // g, np.int64)
        owned[:size % g] += 1
        return rmax, owned, packed_rows(owned, rmax)

    rows_max, rows_owned, _ = balanced(prof.shape[mode])
    part = SimpleNamespace(mode=mode, num_devices=total, r=r, n_groups=g,
                           rows_max=rows_max, rows_owned=rows_owned,
                           tile=tile, block_p=block_p, nnz_max=nnz_dev)
    padded = [balanced(s)[2] for s in prof.shape]

    def st(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    dev = dm.DeviceArrays(
        indices=st((g, r, nnz_dev, n), jnp.int32),
        values=st((g, r, nnz_dev), jnp.float32),
        local_rows=st((g, r, nnz_dev), jnp.int32),
        block_to_tile=st((g, r, nnz_dev // block_p), jnp.int32),
        tile_visited=st((g, r, rows_max // tile), jnp.float32),
        seg_starts=st((g, r, nnz_dev // block_p, tile + 2), jnp.int32),
        seg_rows=st((g, r, nnz_dev // block_p, tile + 1), jnp.int32),
    )
    factors = [st((padded[w], rank), jnp.float32) for w in range(n)]
    fn = dm.make_mttkrp_fn(part, mesh, exchange_spec=spec, **kernel_kw)

    sh = lambda *spec: NamedSharding(mesh, P(*spec))
    dev_in = dm.DeviceArrays(
        indices=sh("group", "sub", None, None),
        values=sh("group", "sub", None),
        local_rows=sh("group", "sub", None),
        block_to_tile=sh("group", "sub", None),
        tile_visited=sh("group", "sub", None),
        seg_starts=sh("group", "sub", None, None),
        seg_rows=sh("group", "sub", None, None),
    )
    f_in = [sh(None, None) for _ in range(n)]

    xtag = spec.variant + ("" if not spec.reduced_wire else "_bf16w")
    rec = {"arch": f"cp_{profile}", "cell": f"mttkrp_r{r}_{xtag}",
           "mesh": list(mesh.devices.shape), "multi_pod": multi_pod,
           "exchange": {"variant": spec.variant, "merge": spec.merge,
                        "chunk_rows": spec.chunk_rows,
                        "wire_dtype": spec.wire_dtype},
           "meta": {"arch": f"cp_{profile}", "cell": f"mttkrp_r{r}",
                    "nnz": prof.nnz, "rank": rank, "nnz_per_dev": nnz_dev,
                    "rows_max": rows_max}}
    t0 = time.time()
    try:
        with mesh:
            jitted = jax.jit(fn, in_shardings=(dev_in, f_in),
                             out_shardings=NamedSharding(mesh, P(None, None)))
            lowered = jitted.lower(dev, factors)
            compiled = lowered.compile()
        cost = compat.cost_analysis(compiled)
        hlo = compiled.as_text()
        parsed = parse_hlo(hlo)
        coll = parsed["collectives"]
        terms = rf.roofline_terms(cost or {}, coll,
                                  dot_flops=parsed["dot_flops"] or None)
        rec.update(ok=True, t_total_s=round(time.time() - t0, 2),
                   memory_analysis=_mem_dict(compiled.memory_analysis()),
                   cost={k: cost.get(k) for k in ("flops", "bytes accessed")
                         if cost and k in cost},
                   collectives=coll, roofline=terms)
    except Exception as e:  # noqa: BLE001
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        tag = "pod2" if multi_pod else "pod1"
        kern = "_kern" if use_kernel else ""
        path = os.path.join(
            OUT_DIR, f"cp_{profile}__r{r}{kern}_{xtag}__{tag}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def run_cp_exchange_ab(*, multi_pod: bool, profile: str = "amazon",
                       replication: int = 1, use_kernel: bool = False,
                       wire_dtype: str = "float32",
                       save: bool = True) -> dict:
    """HLO comparison of the exchange schedules: compile the same MTTKRP
    mode step under the blocking ring and the chunked ``overlap`` schedule
    (same wire dtype) and put their per-device collective bytes, collective
    op mix and roofline exchange terms side by side — the machine-readable
    answer to "what did chunking do to the lowered schedule"."""
    cells = {}
    for variant in ("ring", "overlap"):
        cells[variant] = run_cp_cell(
            multi_pod=multi_pod, profile=profile, replication=replication,
            use_kernel=use_kernel, exchange_variant=variant,
            wire_dtype=wire_dtype, save=False)
    rec = {"arch": f"cp_{profile}", "cell": "exchange_ab",
           "multi_pod": multi_pod, "wire_dtype": wire_dtype,
           "variants": cells}
    ok = all(c.get("ok") for c in cells.values())
    rec["ok"] = ok
    if ok:
        rec["collective_bytes"] = {
            v: sum(c["collectives"].values()) for v, c in cells.items()}
        rec["t_collective"] = {
            v: c["roofline"]["t_collective"] for v, c in cells.items()}
        # chunking must not change how many bytes ride the wire — only when
        # they move relative to compute
        a, b = (rec["collective_bytes"][v] for v in ("ring", "overlap"))
        rec["same_volume"] = bool(a > 0 and abs(a - b) <= 0.05 * a)
    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        tag = "pod2" if multi_pod else "pod1"
        wtag = "_bf16w" if wire_dtype != "float32" else ""
        path = os.path.join(
            OUT_DIR, f"cp_{profile}__exchange_ab{wtag}__{tag}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser(
        description="Compile one CP mode step on the production mesh "
                    "(256 or 512 placeholder devices) and record its "
                    "memory, collectives and roofline.")
    ap.add_argument("--mesh", default="both", choices=["pod1", "pod2", "both"])
    ap.add_argument("--cp-profile", default="amazon")
    ap.add_argument("--cp-replication", type=int, default=1)
    ap.add_argument("--cp-kernel", action="store_true")
    ap.add_argument("--cp-preset", default=None,
                    help="repro.api preset (paper|optimized|fused) driving "
                         "the CP cell's kernel/exchange/replication settings")
    ap.add_argument("--cp-exchange", default=None,
                    choices=["allgather", "ring", "overlap"],
                    help="exchange gather variant for the CP cell")
    ap.add_argument("--cp-wire", default="float32",
                    choices=["float32", "bfloat16"],
                    help="exchange wire dtype for the CP cell")
    ap.add_argument("--cp-exchange-ab", action="store_true",
                    help="compile the CP cell under both the blocking ring "
                         "and the overlap schedule; record the HLO "
                         "comparison (collective bytes/mix per variant)")
    args = ap.parse_args()

    meshes = {"pod1": [False], "pod2": [True], "both": [False, True]}[args.mesh]
    cfg = None
    if args.cp_preset:
        from repro.api import preset
        cfg = preset(args.cp_preset)
    for mp in meshes:
        if args.cp_exchange_ab:
            rec = run_cp_exchange_ab(
                multi_pod=mp, profile=args.cp_profile,
                replication=args.cp_replication,
                use_kernel=args.cp_kernel, wire_dtype=args.cp_wire)
            _report_ab(rec)
            continue
        rec = run_cp_cell(multi_pod=mp, profile=args.cp_profile,
                          replication=args.cp_replication,
                          use_kernel=args.cp_kernel,
                          exchange_variant=args.cp_exchange,
                          wire_dtype=args.cp_wire, config=cfg)
        _report(rec)


def _report_ab(rec: dict):
    if not rec["ok"]:
        bad = {v: c.get("error") for v, c in rec["variants"].items()
               if not c.get("ok")}
        print(f"FAIL {rec['arch']:<22} exchange_ab    {bad}", flush=True)
        return
    cb, tc = rec["collective_bytes"], rec["t_collective"]
    print(f"OK   {rec['arch']:<22} exchange_ab    wire={rec['wire_dtype']:<9}"
          f"ring {cb['ring']/1e6:8.2f}MB/{tc['ring']*1e3:.2f}ms vs overlap "
          f"{cb['overlap']/1e6:8.2f}MB/{tc['overlap']*1e3:.2f}ms "
          f"same_volume={rec['same_volume']}", flush=True)


def _report(rec: dict):
    tag = "x".join(str(d) for d in rec["mesh"])
    if rec["ok"]:
        t = rec["roofline"]
        print(f"OK   {rec['arch']:<22} {rec['cell']:<14} mesh={tag:<9} "
              f"C={t['t_compute']*1e3:8.2f}ms M={t['t_memory']*1e3:8.2f}ms "
              f"X={t['t_collective']*1e3:8.2f}ms dom={t['bottleneck']}",
              flush=True)
    else:
        print(f"FAIL {rec['arch']:<22} {rec['cell']:<14} mesh={tag:<9} "
              f"{rec['error']}", flush=True)


if __name__ == "__main__":
    main()
