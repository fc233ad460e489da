"""From a profiler trace (``.xplane.pb``) to device busy time, the operations
that took most of it, and the idle gaps labelled by what the host was doing.

Read with ``jax.profiler.ProfileData`` alone.  Device planes are those named
``/device:TPU:<n>``; an operation ran on the device while an event of the
plane's ``XLA Ops`` line is open (``XLA Modules`` where a trace has no ops
line).  Busy time is the union of those intervals, clipped to the traced
slice and averaged over the device planes.

The slice and the host's spans are on ``time.perf_counter()``; the trace has a
clock of its own.  ``run.py`` writes a ``jax.profiler.TraceAnnotation`` named
``MARK`` and notes ``perf_counter()`` beside it: the event's start in the
trace against that reading is the offset between the clocks.
"""
from __future__ import annotations

import re
from typing import Iterable, List, Sequence, Tuple

MARK = "perfbench_mark"
DEVICE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops", "XLA Modules")
#: the host's spans that may cover an idle gap, most telling first
LABEL_ORDER = ("execute", "d2h", "plan", "queue_wait", "serialize", "unspanned",
               "wire")

Interval = Tuple[float, float]


_HLO = re.compile(r"^(%[\w.\-]+) = \(?(\w+\[[\d,]*\])?.*?[})] (\w[\w\-]*)\(")


def short_name(name: str) -> str:
    """An HLO instruction's text cut to ``%name shape opcode``; the profile
    names a device operation by its whole instruction."""
    m = _HLO.match(name)
    if m is None:
        return name[:80]
    return " ".join(part for part in m.groups() if part)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals: Iterable[Interval], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def label_gap(gap: Interval, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The host span open at the gap's middle; ``client`` where none is."""
    mid = (gap[0] + gap[1]) / 2.0
    open_now = {name for name, t0, t1 in spans if t0 <= mid <= t1}
    for name in LABEL_ORDER:
        if name in open_now:
            return name
    return "client"


def read(path: str) -> dict:
    """{"mark_ns": start of the MARK event, "devices": {plane: [(name,
    start_ns, end_ns)]}, "lines": {plane: {line: count}}}."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    mark_ns, devices, lines = None, {}, {}
    for plane in data.planes:
        by_line = {}
        for line in plane.lines:
            events = [(e.name, float(e.start_ns),
                       float(e.start_ns) + float(e.duration_ns))
                      for e in line.events]
            by_line[line.name] = events
            if mark_ns is None and not plane.name.startswith(DEVICE_PREFIX):
                marks = [s for n, s, _ in events if n == MARK]
                if marks:
                    mark_ns = min(marks)
        lines[plane.name] = {name: len(ev) for name, ev in by_line.items()}
        if plane.name.startswith(DEVICE_PREFIX):
            for name in OP_LINES:
                if by_line.get(name):
                    devices[plane.name] = by_line[name]
                    break
    return {"mark_ns": mark_ns, "devices": devices, "lines": lines}


def reduce(trace: dict, mark_perf_s: float, lo_s: float, hi_s: float,
           spans: Sequence[Tuple[str, float, float]], top: int = 10) -> dict:
    """Busy seconds, the slice's length, the ``top`` operations by time and
    the idle time by host label, for the slice [lo_s, hi_s] of the host's
    ``perf_counter`` clock."""
    if trace["mark_ns"] is None:
        raise ValueError(f"no {MARK} event in the trace: {trace['lines']}")
    if not trace["devices"]:
        raise ValueError(f"no device plane with operations in the trace: "
                         f"{trace['lines']}")
    shift = mark_perf_s - trace["mark_ns"] / 1e9  # trace seconds -> perf
    busy_s, ops, idle = 0.0, {}, {}
    for events in trace["devices"].values():
        timed = [(n, s / 1e9 + shift, e / 1e9 + shift) for n, s, e in events]
        for n, a, b in timed:
            for ca, cb in clip([(a, b)], lo_s, hi_s):
                n = short_name(n)
                ops[n] = ops.get(n, 0.0) + (cb - ca)
        busy = union(clip([(a, b) for _, a, b in timed], lo_s, hi_s))
        busy_s += sum(b - a for a, b in busy)
        for gap in gaps(busy, lo_s, hi_s):
            name = label_gap(gap, spans)
            idle[name] = idle.get(name, 0.0) + (gap[1] - gap[0])
    n = len(trace["devices"])
    rank = lambda d: [[k, v / n] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": busy_s / n, "window_s": hi_s - lo_s,
            "device_ops": rank(ops), "idle_gaps": rank(idle)}
