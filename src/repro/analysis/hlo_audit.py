"""Compiled/lowered-HLO auditor (the ``AH-*`` pass).

Lints the text the compiler actually sees — the lowered StableHLO and the
optimized compiled HLO of the jitted sweep and serving kernels — instead
of trusting that source-level intent survived lowering:

==========  ========  ==================================================
rule        severity  check
==========  ========  ==================================================
AH-H001     error     no ``gather`` in fused/sorted EC kernel lowering
                      (the paper's point: EC without pre-gather; this
                      migrates the bench's one-off ``gather_free`` grep)
AH-H002     error     no host transfers (infeed/outfeed/callbacks) in
                      the sweep-loop updates
AH-H003     error     collective-permute present when the exchange is
                      ``overlap`` on a multi-device mesh
AH-H004     error     donated factor buffers actually aliased
                      (``input_output_alias``) in the compiled HLO —
                      skipped on CPU, where donation is disabled
AH-H005     error     bf16 on the wire when ``wire_dtype=bfloat16``
                      (checked on the LOWERED text: off-TPU backends
                      upcast collectives in the compiled HLO)
AH-H006     error     serving bucket compiles within O(log max_batch)
                      (retrace counter over the engine's shape sets)
==========  ========  ==================================================

Text-matching notes that earned their scars: ``all-gather``/``all_gather``
contain the substring ``gather``, so :func:`gather_free` uses lookbehinds;
bf16 must be asserted on ``lower().as_text()`` not ``compile().as_text()``.
"""
from __future__ import annotations

import math
import re
from typing import Optional, Sequence

import numpy as np

from repro.analysis.model import Finding

__all__ = ["gather_free", "host_transfer_markers", "donation_aliased",
           "audit_ec_kernel", "audit_solver", "audit_serving_engine",
           "serving_retrace_report", "ec_lowered_text"]

# a real gather op, not the "gather" inside all-gather/all_gather collectives
_GATHER_RE = re.compile(r"(?<!all-)(?<!all_)(?<![a-z])gather")

_HOST_MARKERS = ("infeed", "outfeed", "send-start", "recv-start",
                 "host_callback", "python_callback", "xla_python",
                 "host-compute")

_PERMUTE_RE = re.compile(r"collective[-_]permute")


def gather_free(text: str) -> bool:
    """True iff ``text`` contains no gather op (collective all-gathers,
    which merely *contain* the substring, are not gathers)."""
    return _GATHER_RE.search(text) is None


def host_transfer_markers(text: str) -> list[str]:
    return [m for m in _HOST_MARKERS if m in text]


def donation_aliased(compiled_text: str) -> bool:
    """True iff the compiled HLO aliases at least one input to the output
    (what ``donate_argnums`` must produce when the backend honours it)."""
    return ("input_output_alias" in compiled_text
            or "output_to_operand_aliasing" in compiled_text)


def ec_lowered_text(variant: str, *, nmodes: int, rank: int,
                    tile: Optional[int] = None,
                    block_p: Optional[int] = None,
                    num_buffers: int = 2, nnz: int = 2048,
                    interpret: Optional[bool] = None) -> str:
    """Lower the bare EC kernel (``kernels.ops.mttkrp_local``) for a
    representative shard of this geometry and return the StableHLO text —
    the same construction the autotuner times and the bench greps."""
    import jax
    import jax.numpy as jnp
    from repro.core.partition import block_segment_descriptors
    from repro.kernels import autotune, ops

    layout = "sorted" if variant == "sorted" else "blocked"
    t, part = autotune.representative_shard(
        nmodes, nnz, tile=tile, block_p=block_p, layout=layout)
    rng = np.random.default_rng(0)
    factors = [jnp.asarray(rng.normal(size=(s, rank)).astype(np.float32))
               for s in t.shape]
    args = (jnp.asarray(part.indices[0]), jnp.asarray(part.values[0]),
            jnp.asarray(part.local_rows[0]),
            jnp.asarray(part.block_to_tile[0]))
    mask = jnp.asarray(part.tile_visited[0])
    seg_kw = {}
    if variant == "sorted":
        ss, sr = block_segment_descriptors(part.local_rows[0],
                                           tile=part.tile,
                                           block_p=part.block_p)
        seg_kw = dict(seg_starts=jnp.asarray(ss), seg_rows=jnp.asarray(sr))

    def run(indices, values, local_rows, block_to_tile, facs):
        return ops.mttkrp_local(
            indices, values, local_rows, block_to_tile, facs,
            mode=0, num_rows=part.rows_max, tile=part.tile,
            block_p=part.block_p, use_kernel=variant != "ref",
            variant=variant, num_buffers=num_buffers, interpret=interpret,
            tile_mask=mask, **seg_kw)

    return jax.jit(run).lower(*args, factors).as_text()


def audit_ec_kernel(variant: str, *, nmodes: int, rank: int,
                    tile: Optional[int] = None,
                    block_p: Optional[int] = None,
                    num_buffers: int = 2, nnz: int = 2048,
                    lowered_text: Optional[str] = None) -> list[Finding]:
    """AH-H001 on one EC kernel variant (pass ``lowered_text`` to audit a
    caller-provided lowering instead of a representative one)."""
    findings: list[Finding] = []
    if variant not in ("fused", "sorted"):
        return findings  # ref/blocked are allowed to gather
    if lowered_text is None:
        lowered_text = ec_lowered_text(
            variant, nmodes=nmodes, rank=rank, tile=tile, block_p=block_p,
            num_buffers=num_buffers, nnz=nnz)
    if not gather_free(lowered_text):
        findings.append(Finding(
            "AH-H001", "error",
            f"'{variant}' EC kernel lowering contains a gather op; the "
            f"fused/sorted paths must stream factor rows via the kernel, "
            f"not a pre-gather", f"kernel variant={variant}"))
    return findings


def audit_update_text(lowered_text: str, compiled_text: str, *, mode: int,
                      exchange_spec, backend: str,
                      multi_device: bool) -> list[Finding]:
    """AH-H002/H003/H004/H005 over one jitted mode update's text pair."""
    findings: list[Finding] = []
    loc = f"mode={mode} update"
    hits = host_transfer_markers(lowered_text) \
        or host_transfer_markers(compiled_text)
    if hits:
        findings.append(Finding(
            "AH-H002", "error",
            f"sweep update contains host-transfer ops {hits}; the sweep "
            f"loop must stay on device", loc))
    markers = exchange_spec.expected_hlo_markers(multi_device=multi_device)
    if markers["collective_permute"] and not (
            _PERMUTE_RE.search(lowered_text)
            or _PERMUTE_RE.search(compiled_text)):
        findings.append(Finding(
            "AH-H003", "error",
            f"exchange variant '{exchange_spec.variant}' promises a "
            f"chunked permute ring but no collective-permute lowered", loc))
    if backend != "cpu" and not donation_aliased(compiled_text):
        findings.append(Finding(
            "AH-H004", "error",
            "donated factor buffer is not aliased in the compiled HLO "
            "(donation silently dropped: peak HBM doubles)", loc))
    if markers["wire_bf16"] and "bf16" not in lowered_text:
        findings.append(Finding(
            "AH-H005", "error",
            "exchange.wire_dtype=bfloat16 but no bf16 values in the "
            "lowered update; the wire would carry f32 at 2x the volume",
            loc))
    return findings


def audit_solver(solver, *, modes: Optional[Sequence[int]] = None
                 ) -> list[Finding]:
    """Audit a live :class:`~repro.api.solver.CPSolver`'s jitted updates
    plus its EC kernel variant. Streaming solvers skip the per-update
    lowering (their updates are per-super-shard; the kernel-level and
    serving checks still apply)."""
    import jax

    findings: list[Finding] = []
    plan, config = solver.plan, solver.config
    kw = config.kernel.mttkrp_kwargs(nmodes=plan.nmodes, rank=config.rank)
    from repro.kernels.ops import resolve_variant
    variant = resolve_variant(kw.get("variant"),
                              kw.get("use_kernel", True))
    part0 = plan.modes[0]
    findings.extend(audit_ec_kernel(
        variant, nmodes=plan.nmodes, rank=config.rank, tile=part0.tile,
        block_p=part0.block_p,
        num_buffers=kw.get("num_buffers") or 2))

    if solver.streaming:
        return findings
    backend = jax.default_backend()
    multi = plan.num_devices > 1
    s = solver.state
    for d in (modes if modes is not None else range(plan.nmodes)):
        others = [s.factors[w] for w in range(plan.nmodes) if w != d]
        lowered = solver.updates[d].lower(
            s.factors[d], solver.streamer.get(d), others, s.grams)
        findings.extend(audit_update_text(
            lowered.as_text(), lowered.compile().as_text(), mode=d,
            exchange_spec=solver.exchange_spec, backend=backend,
            multi_device=multi))
    return findings


# -- serving retrace counter (AH-H006) ------------------------------------

def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def serving_retrace_report(engine) -> dict:
    """Bucket-compile accounting for a :class:`ServingEngine`: the distinct
    jitted shapes so far vs the O(log max_batch) bound the bucketing
    guarantees."""
    bound = (int(math.log2(engine.max_batch))
             - int(math.log2(max(engine.min_bucket, 1))) + 1)
    return {
        "reconstruct_shapes": sorted(engine._reconstruct_shapes),
        "topk_shapes": sorted(engine._topk_shapes),
        "reconstruct_compiles": len(engine._reconstruct_shapes),
        "topk_compiles": len(engine._topk_shapes),
        "bucket_bound": bound,
    }


def audit_serving_engine(engine) -> list[Finding]:
    findings: list[Finding] = []
    rep = serving_retrace_report(engine)
    bound = rep["bucket_bound"]
    sizes = {f.shape[0] for f in engine.snapshot.factors}
    for b in rep["reconstruct_shapes"]:
        if not _is_pow2(b) or b > engine.max_batch:
            findings.append(Finding(
                "AH-H006", "error",
                f"reconstruct compiled at non-bucket batch {b}; every "
                f"distinct shape is a fresh XLA compile", "serving"))
    if rep["reconstruct_compiles"] > bound:
        findings.append(Finding(
            "AH-H006", "error",
            f"{rep['reconstruct_compiles']} reconstruct bucket compiles "
            f"exceed the O(log max_batch) bound {bound}", "serving"))
    nmodes = len(engine.snapshot.factors)
    # per (mode, k-bucket) at most `bound` batch buckets; k itself is
    # bucketed to powers of two (or clamped to the mode's row count)
    for b, _mode, kb in rep["topk_shapes"]:
        if not _is_pow2(b) or (not _is_pow2(kb) and kb not in sizes):
            findings.append(Finding(
                "AH-H006", "error",
                f"topk compiled at non-bucket shape (batch={b}, k={kb})",
                "serving"))
    kbuckets = {kb for _, _, kb in rep["topk_shapes"]}
    topk_bound = bound * nmodes * max(len(kbuckets), 1)
    if rep["topk_compiles"] > topk_bound:
        findings.append(Finding(
            "AH-H006", "error",
            f"{rep['topk_compiles']} topk bucket compiles exceed the "
            f"bucketed bound {topk_bound}", "serving"))
    return findings
