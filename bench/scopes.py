"""Device time by the window program's named scopes, and host time by the
program's spans, from a profiler trace (``.xplane.pb``).

The window program wraps its work in four ``jax.named_scope`` names
(``repro.core.schedule.SCOPES``); an op belongs to the innermost of them in
its ``op_name``. The trace names an op by its HLO instruction (the TPU's
events carry its text, shape and opcode, but no ``op_name``); its
``op_name`` comes from ``metadata={op_name=...}`` in the window
executable's compiled HLO text. Ops of other executables, and ops with no
scope, go to ``other``.

Time is *self* time: an op event's duration less the time covered by the
events fully nested in it on the same line, so a loop op does not count
its body twice. Two forms are read: device planes (``/device:TPU:n``,
ops on the ``XLA Ops`` line, executables on ``XLA Modules``), and the
CPU's, where ops are host-thread events that carry ``hlo_op`` and
``hlo_module`` stats.
"""

from __future__ import annotations

import gzip
import re
from collections import defaultdict

SCOPES = ("neuron_update", "intra_deliver", "inter_exchange", "ring")
OTHER = "other"

_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
_OP_LINES = ("XLA Ops", "Ops")
_MODULE_LINE = "XLA Modules"
# ``jit(f)/vmap(intra_deliver)/...``: a transform wraps a path component.
_WRAPPED = re.compile(r"^(?:[\w-]+\()*([^()]*)\)*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.-]+)")
_OPERAND = re.compile(r"(?<![\w=])%([\w.-]+)")
_CALLEE = re.compile(r"(?:body|condition|calls|to_apply)=%?([\w.-]+)")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.-]+)\s.*\{\s*$")
_MODULE = re.compile(r"^HloModule\s+([\w.-]+)")
_SIGNATURE = re.compile(r"=\s*(.+?) ([\w-]+)\(")


def load(path: str) -> dict:
    """Op events, executable events and host spans of a trace (a path to
    an ``.xplane.pb``, or to one gzipped, ``.xplane.pb.gz``).

    ``ops``: ``(line, name, start_ns, dur_ns, stats)``; ``modules``:
    ``(line, name, start_ns, dur_ns)``; ``spans``: ``(name, start_ns,
    dur_ns)``. ``line`` keys the line an event lies on (a device plane, or
    a host thread).
    """
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            prof = ProfileData.from_serialized_xspace(f.read())
    else:
        prof = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    for plane in prof.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in _OP_LINES:
                    ops.extend((plane.name, ev.name, float(ev.start_ns),
                                float(ev.duration_ns), _stats(ev))
                               for ev in line.events)
                elif line.name == _MODULE_LINE:
                    modules.extend((plane.name, ev.name, float(ev.start_ns),
                                    float(ev.duration_ns))
                                   for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                key = f"{plane.name}/{line.name}"
                for ev in line.events:
                    stats = _stats(ev)
                    event = (ev.name, float(ev.start_ns),
                             float(ev.duration_ns))
                    if "hlo_op" in stats:
                        ops.append((key,) + event + (stats,))
                    else:
                        spans.append(event)
    return {"ops": ops, "modules": modules, "spans": spans}


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def hlo_op_names(hlo_text: str):
    """``(module name, {instruction: op_name}, {instruction: signature})``
    of a compiled HLO text; a signature is the result shape and opcode.

    An instruction without metadata (one XLA made) takes the ``op_name`` of
    the computation it calls (its root's, else the first in it), else that
    of its first operand whose ``op_name`` has a scope (else of its first
    operand that has one), else that of the instruction that calls its
    computation (a loop XLA made runs its body so), until none changes.
    """
    module = None
    names: dict[str, str] = {}
    sigs: dict[str, tuple[str, str]] = {}
    pending: list[tuple[str, str | None, list[str], str | None]] = []
    comp_names: dict[str, list[str]] = defaultdict(list)
    comp_root: dict[str, str] = {}
    caller: dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _MODULE.match(line)
        if m:
            module = m.group(1)
            continue
        m = _COMP.match(line)
        if m and "=" not in line.split("{")[0].split("(")[0]:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        instr = m.group(1)
        sig = _signature(line)
        if sig:
            sigs[instr] = sig
        for callee in _CALLEE.findall(line):
            caller.setdefault(callee, instr)
        op = _OP_NAME.search(line)
        if op and "/" in op.group(1):  # a path, not an argument's name
            names[instr] = op.group(1)
            if comp is not None:
                comp_names[comp].append(op.group(1))
                if line.lstrip().startswith("ROOT"):
                    comp_root[comp] = op.group(1)
        else:
            callee = _CALLS.search(line)
            operands = _OPERAND.findall(line.split("=", 1)[1])
            pending.append((instr, callee and callee.group(1), operands,
                            comp))
    changed = True
    while changed:
        changed = False
        for instr, callee, operands, comp in pending:
            if instr in names:
                continue
            found = callee and (comp_root.get(callee)
                                or next(iter(comp_names[callee]), None))
            if not found:
                known = [names[o] for o in operands if o in names]
                found = next((n for n in known if scope_of(n) != OTHER),
                             next(iter(known), None))
            if not found:
                found = names.get(caller.get(comp))
            if found:
                names[instr] = found
                changed = True
    return module, names, sigs


def _signature(text: str) -> tuple[str, str] | None:
    """``(result shape, opcode)`` of an HLO instruction's text."""
    m = _SIGNATURE.search(text)
    return (m.group(1), m.group(2)) if m else None


def scope_of(op_name: str | None) -> str:
    """The innermost of ``SCOPES`` in an ``op_name`` path, else ``other``."""
    for part in reversed((op_name or "").split("/")):
        m = _WRAPPED.match(part)
        if m and m.group(1) in SCOPES:
            return m.group(1)
    return OTHER


def _instr_name(event_name: str) -> str:
    """``%fusion.79 = f32[...] ...`` or ``fusion.79`` -> ``fusion.79``."""
    return event_name.lstrip("%").split(" ", 1)[0].split("=", 1)[0]


def _base_module(name: str) -> str:
    """``jit_window(12)`` -> ``jit_window``."""
    return name.split("(", 1)[0].strip()


def self_times(events, window=None) -> list[float]:
    """Self time (ns) of each ``(line, name, start, dur, ...)`` event.

    Events are clipped to ``window`` (``(start_ns, end_ns)``); an event is
    nested in another on the same line when it lies wholly inside it. The
    self time is the duration less the union of the event's direct
    children, which hold their own children.
    """
    w0, w1 = window if window is not None else (-float("inf"), float("inf"))
    out = [0.0] * len(events)
    by_line = defaultdict(list)
    for i, ev in enumerate(events):
        a, b = max(ev[2], w0), min(ev[2] + ev[3], w1)
        if b > a:
            by_line[ev[0]].append((a, b, i))
    for items in by_line.values():
        items.sort(key=lambda x: (x[0], -x[1]))
        children = defaultdict(list)
        stack: list[tuple[float, float, int]] = []
        for a, b, i in items:
            while stack and not (stack[-1][0] <= a and b <= stack[-1][1]):
                stack.pop()
            if stack:
                children[stack[-1][2]].append((a, b))
            stack.append((a, b, i))
        for a, b, i in items:
            out[i] = (b - a) - _covered(children[i])
    return out


def _covered(intervals) -> float:
    total, end = 0.0, -float("inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def scope_seconds(events: dict, hlo_text: str | None = None,
                  window=None) -> dict:
    """Self seconds per scope and ``other``, over the op events in
    ``window``, averaged over the devices that ran them.

    ``hlo_text`` is the window executable's compiled HLO: an op's
    ``op_name`` is that of its instruction there. Also ``module_s``, the
    self seconds of the ops that ran in that executable; ``unjoined_s``,
    those of its ops whose event names an instruction the HLO does not
    have (a sign that the HLO is another executable's); and ``busy_s``,
    the union of the op intervals on each line (a device's ops; a CPU's
    threads, which run ops side by side, each count).
    """
    module, names, sigs = (hlo_op_names(hlo_text) if hlo_text
                           else (None, {}, {}))
    ops = events["ops"]
    selfs = self_times(ops, window)
    owner = _module_of(ops, events.get("modules", []))
    totals = {s: 0.0 for s in SCOPES + (OTHER, "module_s", "unjoined_s")}
    for ev, own, dt in zip(ops, owner, selfs):
        name = ev[1]
        ours = module is not None and own == module
        op_name = None
        if ours or own is None:
            instr = _instr_name(name)
            op_name = names.get(instr)
            if " = " in name and _signature(name) != sigs.get(instr):
                # Not the instruction of this HLO: another executable's.
                op_name = None
                totals["unjoined_s"] += dt if ours else 0.0
        totals[scope_of(op_name)] += dt
        if ours:
            totals["module_s"] += dt
    n = max(len({_device(ev[0]) for ev in ops}), 1)
    out = {k: v * 1e-9 / n for k, v in totals.items()}
    out["busy_s"] = _busy(ops, window) * 1e-9 / n
    out["n_devices"] = n if ops else 0
    return out


def _device(line: str) -> str:
    """Host threads of the CPU form are one device."""
    return line if line.startswith("/device:") else "/host"


def _module_of(ops, modules) -> list[str | None]:
    """The executable each op ran in: its ``hlo_module`` stat, else the
    ``XLA Modules`` event on its line that holds it."""
    by_line = defaultdict(list)
    for line, name, s, d in modules:
        by_line[line].append((s, s + d, _base_module(name)))
    out = []
    for line, _, s, d, stats in ops:
        if "hlo_module" in stats:
            out.append(_base_module(str(stats["hlo_module"])))
            continue
        out.append(next((m for a, b, m in by_line.get(line, ())
                         if a <= s and s + d <= b), None))
    return out


def _busy(ops, window) -> float:
    """The union of op intervals on each line, summed over the lines."""
    w0, w1 = window if window is not None else (-float("inf"), float("inf"))
    by_line = defaultdict(list)
    for line, _, s, d, _ in ops:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            by_line[line].append((a, b))
    return sum(_covered(v) for v in by_line.values())


def span_seconds(events: dict, name: str) -> float | None:
    """Total seconds of the host spans called ``name``, or ``None``."""
    durs = [d for n, _, d in events["spans"] if n == name]
    return sum(durs) * 1e-9 if durs else None
