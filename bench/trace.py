"""Reduce a profiler trace (``.xplane.pb``) to device busy time, the top
device operations and the longest idle gaps.

Busy time is the union of the intervals in which an operation ran on a
device, averaged over the devices. An idle gap is a stretch of the traced
window in which no device operation ran; each is named by the innermost
host span (``jax.profiler.TraceAnnotation``) that was open over most of it,
or ``host`` when none was.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
# Lines of a device plane that hold one event per executed operation.
_OP_LINES = ("XLA Ops", "Ops")
TOP = 10


def find_xplane(directory: str) -> str | None:
    """The newest ``.xplane.pb`` under ``directory``."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> dict:
    """Device op events and host spans as plain tuples (ns)."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    devices, spans, planes = {}, [], []
    for plane in prof.planes:
        planes.append((plane.name, [line.name for line in plane.lines]))
        if _DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            name = next((n for n in _OP_LINES if n in lines), None)
            if name is None:
                continue
            devices[plane.name] = [
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in lines[name].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    spans.append((ev.name, float(ev.start_ns),
                                  float(ev.duration_ns)))
    return {"devices": devices, "spans": spans, "planes": planes}


def _union(intervals):
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def reduce(events: dict, window: tuple[float, float] | None = None,
           span_prefix: str = "bench.") -> dict:
    """Busy seconds (mean over devices), the window's length, the top
    device ops by total time, and the longest idle gaps by host span.

    ``window`` is ``(start_ns, end_ns)``; by default the span of all device
    events. Events are clipped to it.
    """
    devices = events["devices"]
    if not devices:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                "idle_gaps": [], "n_devices": 0}
    if window is None:
        starts = [s for evs in devices.values() for _, s, _ in evs]
        ends = [s + d for evs in devices.values() for _, s, d in evs]
        window = (min(starts), max(ends))
    w0, w1 = window
    op_time = defaultdict(float)
    busy, gaps = [], []
    spans = [(n, s, s + d) for n, s, d in events["spans"]
             if n.startswith(span_prefix)]
    for evs in devices.values():
        clipped = []
        for name, s, d in evs:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((a, b))
                op_time[name] += (b - a) * 1e-9
        merged = _union(clipped)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_span_over(spans, a, b), (b - a) * 1e-9))
    n = len(devices)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps, key=lambda g: -g[1])[:TOP]
    return {
        "busy_s": sum(busy) / n,
        "window_s": (w1 - w0) * 1e-9,
        "device_ops": [[k, v / n] for k, v in top_ops],
        "idle_gaps": [[k, v] for k, v in top_gaps],
        "n_devices": n,
    }


def _span_over(spans, a: float, b: float) -> str:
    """The innermost host span that covers most of ``[a, b)``."""
    best, best_key = "host", None
    for name, s, e in spans:
        cover = min(e, b) - max(s, a)
        if cover >= 0.5 * (b - a):
            key = e - s  # innermost: the shortest covering span
            if best_key is None or key < best_key:
                best, best_key = name, key
    return best


def span_window(events: dict, name: str) -> tuple[float, float] | None:
    """``(start_ns, end_ns)`` of the first host span called ``name``."""
    for n, s, d in events["spans"]:
        if n == name:
            return s, s + d
    return None
