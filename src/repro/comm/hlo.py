"""Loop-weighted collective bytes and dot FLOPs from compiled HLO text.

A ``while`` op's body appears once in the text but runs
``known_trip_count`` times, so a plain sum over the text (and
``cost_analysis()``) undercounts what runs inside loops, such as the EC's
per-chunk loop or a scan. :func:`parse_hlo` builds the computation call
graph (fusion ``calls=``, ``to_apply=``, while ``body=``/``condition=``
with the ``backend_config`` trip count, conditional branches) and
multiplies through nested loops.

* **collective bytes** — each collective's result bytes times its ring
  traffic factor (all-reduce 2×, the others 1×), per device;
* **dot FLOPs** — ``2 · result_elems · K_contracted`` per ``dot``
  (``convolution`` counts as a lower bound of ``2 · result_elems``).

:mod:`repro.comm.volume` reads the exchange's measured bytes from it, and
the production dry run (``launch/dryrun.py``) its roofline terms.
"""
from __future__ import annotations

import re

__all__ = ["parse_hlo", "collective_bytes"]


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "ragged-all-to-all", "collective-permute")

_TRAFFIC_FACTOR = {
    "all-gather": 1.0, "all-reduce": 2.0, "reduce-scatter": 1.0,
    "all-to-all": 1.0, "ragged-all-to-all": 1.0, "collective-permute": 1.0,
}


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        nb = _DTYPE_BYTES.get(dt)
        if nb is None:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * nb
    return total


def _shape_elems(shape_str: str) -> tuple[int, list[int]]:
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return 0, []
    dims = [int(d) for d in m.group(2).split(",") if d]
    n = 1
    for d in dims:
        n *= d
    return n, dims


_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+) = (\S+(?:\([^)]*\))?) "
                    r"([\w\-]+)\(")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\((.*)\)\s*->")
_CALLS_RE = re.compile(r"(?:calls|to_apply)=%?([\w\.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w\.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w\.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count[":{]+n[":]+(\d+)')
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_PARAM_RE = re.compile(r"%?([\w\.\-]+):\s*([^,)]+)")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def parse_hlo(hlo_text: str) -> dict:
    """``{"collectives": {kind: bytes, ..., "total": bytes},
    "dot_flops": flops}``, loop-weighted (see the module docstring)."""
    # --- split into computations ------------------------------------------
    comps: dict[str, list[str]] = {}
    entry = None
    cur = None
    for line in hlo_text.splitlines():
        hdr = _COMP_HDR.match(line)
        if hdr and line.rstrip().endswith("{"):
            cur = hdr.group(1)
            comps[cur] = [line]
            if line.startswith("ENTRY"):
                entry = cur
            continue
        if cur is not None:
            comps[cur].append(line)
            if line.strip() == "}":
                cur = None
    if not comps:
        comps = {"main": hlo_text.splitlines()}
        comps["main"].insert(0, "")  # no header line
        entry = "main"
    if entry is None and comps:
        entry = next(iter(comps))

    # --- per computation: direct costs + call edges ------------------------
    info: dict[str, dict] = {}
    for name, lines in comps.items():
        shapes: dict[str, str] = {}
        hdr = _COMP_HDR.match(lines[0]) if lines else None
        if hdr:
            for pname, ptype in _PARAM_RE.findall(hdr.group(2)):
                shapes[pname] = ptype
        coll: dict[str, float] = {}
        flops = 0.0
        edges: list[tuple[str, float]] = []
        for line in lines[1:]:
            m = _OP_RE.match(line)
            if m:
                op_name, result_shape, op = m.groups()
                shapes[op_name] = result_shape
                if op in _COLL_KINDS and "-done" not in line:
                    b = _shape_bytes(result_shape) * _TRAFFIC_FACTOR[op]
                    coll[op] = coll.get(op, 0.0) + b
                elif op == "dot":
                    flops += _dot_flops(line, result_shape, shapes)
                elif op == "convolution":
                    n, _ = _shape_elems(result_shape)
                    flops += 2.0 * n  # lower bound; convs are stubs here
            body = _BODY_RE.search(line)
            if "while(" in line and body:
                trip = 1
                tm = _TRIP_RE.search(line)
                if tm:
                    trip = int(tm.group(1))
                edges.append((body.group(1), float(trip)))
                cm = _COND_RE.search(line)
                if cm:
                    edges.append((cm.group(1), float(trip)))
            else:
                for callee in _CALLS_RE.findall(line):
                    edges.append((callee, 1.0))
                br = _BRANCH_RE.search(line)
                if br:
                    for c in br.group(1).split(","):
                        c = c.strip().lstrip("%")
                        if c:
                            edges.append((c, 1.0))
        info[name] = {"coll": coll, "flops": flops, "edges": edges}

    # --- weighted transitive totals ----------------------------------------
    memo: dict[str, tuple[dict, float]] = {}

    def total(name: str, stack=()) -> tuple[dict, float]:
        if name in memo:
            return memo[name]
        if name not in info or name in stack:
            return {}, 0.0
        node = info[name]
        coll = dict(node["coll"])
        flops = node["flops"]
        for callee, mult in node["edges"]:
            c_coll, c_flops = total(callee, stack + (name,))
            for k, v in c_coll.items():
                coll[k] = coll.get(k, 0.0) + v * mult
            flops += c_flops * mult
        memo[name] = (coll, flops)
        return memo[name]

    coll, flops = total(entry) if entry else ({}, 0.0)
    coll["total"] = sum(coll.values())
    return {"collectives": coll, "dot_flops": flops}


def _dot_flops(line: str, result_shape: str, shapes: dict[str, str]) -> float:
    n, _ = _shape_elems(result_shape)
    k = 1
    ops = _OPERAND_RE.findall(line.split("dot(", 1)[1])
    cd = _CDIMS_RE.search(line)
    if ops and cd and ops[0] in shapes:
        _, dims = _shape_elems(shapes[ops[0]])
        for di in cd.group(1).split(","):
            if di and int(di) < len(dims):
                k *= dims[int(di)]
    return 2.0 * n * k


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Loop-weighted per-device collective bytes by kind, plus ``total``."""
    return parse_hlo(hlo_text)["collectives"]
