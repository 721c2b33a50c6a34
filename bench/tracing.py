"""From a ``jax.profiler`` trace to the numbers the per-layer metrics read.

A device operation is an event on a TPU plane's ``XLA Ops`` line (named by
its HLO instruction, in the module the ``XLA Modules`` line places it), or (on
the CPU, where XLA runs on host threads) an event that carries an
``hlo_op`` stat. Each is attributed to one of the program's device scopes
(``ec_local``, ``merge``, ``factor_exchange``; ``jax.named_scope`` names
that reach the op's metadata) or to ``other``. The scope is looked up in
the event's string stats, then in an optional ``{(module, op): op_name}``
map parsed from the compiled programs' HLO text (``op_scopes_from_hlo``):
neither the TPU's nor the CPU's op events carry the op's metadata. An op
whose key two programs give different scopes counts as ``unattributed``,
never as ``other``; where such ops hold more than ``MAX_UNATTRIBUTED`` of
the device time the reduction fails, naming them.

The window is the benchmark's own ``window`` annotation on a host thread;
every device interval is clipped to it. ``busy_s`` is the union of the
device-op intervals, averaged over the devices; idle gaps are the holes in
that union, each named by the benchmark's innermost host annotation
(``sweep``, ``fit_read``, ``window_wait``) at the gap's midpoint.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re

__all__ = ["SCOPES", "HOST_NAMES", "Summary", "capture", "reduce",
           "reduce_dir", "op_scopes_from_hlo"]

SCOPES = ("ec_local", "merge", "factor_exchange")
UNATTRIBUTED = "unattributed"
CLASH = "\x00clash"              # op_scopes_from_hlo's mark for such a key
MAX_UNATTRIBUTED = 0.01          # share of the device time
HOST_NAMES = ("sweep", "fit_read", "window_wait")
WINDOW = "window"
_SCOPE_RE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?:/|$)")
TOP = 10


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace device activity and host annotations into ``log_dir``, with
    the Python function tracer off."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(log_dir, profiler_options=opts):
        yield


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # mean over devices
    devices: int
    scope_s: dict                 # scope (or "other") -> device s, mean
    op_s: dict                    # op display name -> device s, mean
    gaps: list                    # [(host annotation, seconds)], longest first

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:TOP]]}


def _scope_of(stats: dict, module: str, op: tuple, op_scopes) -> str:
    for v in stats.values():
        if isinstance(v, str):
            m = _SCOPE_RE.search(v)
            if m:
                return m.group(1)
    names = op_scopes or {}
    name = names.get((module, op[0], op[1]))
    if name is None:
        name = names.get((module, op[0]), "")
    return UNATTRIBUTED if name == CLASH else _scope(name)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _device_ops(pd):
    """{device: [(start_ns, end_ns, module, op, stats)]}."""
    ops: dict = {}
    for plane in pd.planes:
        tpu = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if tpu and line.name not in ("XLA Ops",):
                continue
            for ev in line.events:
                stats = dict(ev.stats)
                if tpu:
                    key = plane.name
                elif "hlo_op" in stats:
                    key = f"device:{stats.get('device_ordinal', 0)}"
                else:
                    continue
                module = str(stats.get("hlo_module", ""))
                op, shape = _op_name(ev.name)
                op = str(stats.get("hlo_op", "")) or op
                ops.setdefault(key, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns, module,
                     (op, shape if tpu else None), stats))
    return ops


_OP_RE = re.compile(r"^%?([\w.\-]+)\s*=\s*(\S+)")
_MODULE_ID_RE = re.compile(r"\(\d+\)$")


def _op_name(event_name: str) -> tuple[str, str | None]:
    """(``fusion.2``, ``f32[9648,32]{1,0:T(8,128)}``) from a TPU op event
    named by its HLO instruction (``%fusion.2 = f32[...] fusion(...)``):
    the result type tells apart the same instruction of programs of one
    name (each mode's ``jit_update``)."""
    m = _OP_RE.match(event_name)
    return (m.group(1), m.group(2)) if m else (event_name, None)


def _tpu_modules(pd):
    """{plane: [(start, end, module name)]} from the ``XLA Modules`` lines
    (``jit_update(<program id>)`` becomes ``jit_update``), for op events
    that do not name their module."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                out[plane.name] = sorted(
                    (e.start_ns, e.start_ns + e.duration_ns,
                     _MODULE_ID_RE.sub("", e.name))
                    for e in line.events)
    return out


def _host_annotations(pd):
    """(window (start, end), [(start, end, name)] of HOST_NAMES)."""
    window = None
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW and window is None:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name in HOST_NAMES:
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return window, spans


def _module_at(modules, t):
    for s, e, name in modules:
        if s <= t < e:
            return name
    return ""


def reduce(pd, op_scopes: dict | None = None) -> Summary:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Summary`."""
    window, spans = _host_annotations(pd)
    if window is None:
        raise ValueError(f"the trace has no {WINDOW!r} annotation")
    w0, w1 = window
    ops = _device_ops(pd)
    if not ops:
        raise ValueError("the trace has no device operations")
    modules = _tpu_modules(pd)
    ndev = len(ops)
    busy = 0.0
    scope_s: dict = {}
    op_s: dict = {}
    gaps = []
    for key, evs in sorted(ops.items()):
        clipped = []
        for s, e, module, op, stats in evs:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            if not module and key in modules:
                module = _module_at(modules[key], s)
            scope = _scope_of(stats, module, op, op_scopes)
            dur = (e - s) * 1e-9
            scope_s[scope] = scope_s.get(scope, 0.0) + dur / ndev
            name = f"{module}/{op[0]} [{scope}]" if module else \
                f"{op[0]} [{scope}]"
            op_s[name] = op_s.get(name, 0.0) + dur / ndev
            clipped.append((s, e))
        merged = _union(clipped)
        busy += sum(e - s for s, e in merged) * 1e-9 / ndev
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_host_name(spans, (a + b) / 2), (b - a) * 1e-9))
    for scope in SCOPES + ("other", UNATTRIBUTED):
        scope_s.setdefault(scope, 0.0)
    total = sum(scope_s.values())
    if scope_s[UNATTRIBUTED] > MAX_UNATTRIBUTED * total:
        lost = {k: v for k, v in op_s.items()
                if k.endswith(f"[{UNATTRIBUTED}]")}
        raise ValueError(
            f"{scope_s[UNATTRIBUTED]:.6f} of {total:.6f} device seconds are "
            f"in ops that two programs give different scopes: {lost}")
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(w1 - w0) * 1e-9, busy_s=busy, devices=ndev,
                   scope_s=scope_s, op_s=op_s, gaps=gaps)


def _host_name(spans, t) -> str:
    """The innermost (shortest) benchmark annotation that holds ``t``."""
    best = None
    for s, e, name in spans:
        if s <= t < e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "host"


def reduce_dir(log_dir: str, op_scopes: dict | None = None) -> Summary:
    """Reduce the newest ``.xplane.pb`` under ``log_dir``."""
    import jax
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise ValueError(f"no profiler trace under {log_dir}")
    return reduce(jax.profiler.ProfileData.from_file(files[-1]), op_scopes)


_MODULE_RE = re.compile(r"^HloModule\s+([\w.\-]+)")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\S+).*?"
    r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")


def op_scopes_from_hlo(texts) -> dict:
    """``{(module, instruction, result type): op_name}``, and the same
    under ``(module, instruction)``, from compiled HLO module texts.
    Programs of one name (each mode's ``jit_update``) share instruction
    names; where two give one key different scopes, the key maps to
    ``CLASH``, and the op counts as ``unattributed``."""
    out = {}
    clash = set()
    for text in texts:
        module = ""
        for line in text.splitlines():
            m = _MODULE_RE.match(line)
            if m:
                module = m.group(1)
                continue
            m = _INSTR_RE.match(line)
            if not m:
                continue
            op, shape, name = m.groups()
            for key in ((module, op, shape), (module, op)):
                if key in out and _scope(out[key]) != _scope(name):
                    clash.add(key)
                out[key] = name
    for key in clash:
        out[key] = CLASH
    return out


def _scope(op_name: str) -> str:
    m = _SCOPE_RE.search(op_name)
    return m.group(1) if m else "other"
