"""Reduce a profiler trace (``.xplane.pb``) of one measured window.

Reads, with nothing but ``jax.profiler.ProfileData``:

* the window: the host annotation named :data:`WINDOW` that the harness
  opens around the measured window;
* per chip, the device's busy time in the window: the union of the
  intervals of the events on the chip's ``XLA Ops`` line;
* the time of every device operation, by its HLO instruction's name,
  summed over the chips, and the ten that took most;
* the device's idle gaps in the window, each named by the innermost host
  span it falls in (the benchmark's tracer mirrors every program span as
  an annotation ``@<category>:<name>``, see ``spans.py``), summed over
  the chips.
"""
from __future__ import annotations

import heapq
import re
from collections import defaultdict
from typing import Dict, List, Tuple

#: Host annotation around the measured window.
WINDOW = "@bench:window"
#: Prefix of the benchmark's mirrored host spans.
SPAN_PREFIX = "@"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
TOP = 10


class TraceError(RuntimeError):
    """The trace lacks what the reduction needs."""


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def op_name(name: str) -> str:
    """``%copy.1 = f32[...] copy(...)`` -> ``%copy.1``: the HLO
    instruction's name without its text."""
    return name.split(" = ", 1)[0]


def _label(name: str) -> str:
    """``@dispatch:s3`` -> ``dispatch``: the span's category."""
    return name[len(SPAN_PREFIX):].split(":", 1)[0]


def _innermost(spans: List[Tuple[float, float, str]],
               points: List[float]) -> List[str]:
    """For each point (ascending), the label of the latest-starting span
    that contains it, or ``"(no span)"``."""
    spans = sorted(spans)
    heap: List[Tuple[float, float, str]] = []
    out, i = [], 0
    for m in points:
        while i < len(spans) and spans[i][0] <= m:
            a, b, name = spans[i]
            heapq.heappush(heap, (-a, b, name))
            i += 1
        while heap and heap[0][1] < m:
            heapq.heappop(heap)
        out.append(_label(heap[0][2]) if heap else "(no span)")
    return out


def reduce_trace(data, chips: int) -> Dict:
    """``data`` is a ``jax.profiler.ProfileData`` of a run on ``chips``
    chips (TPU 0 .. chips-1).  Returns ``window_s``, ``busy_s`` (mean
    over those chips), ``busy_s_by_chip``, ``op_seconds`` (every
    operation's name to its seconds), ``device_ops`` and ``idle_gaps``
    (each at most :data:`TOP` ``[name, seconds]`` pairs, longest
    first)."""
    window = None
    spans: List[Tuple[float, float, str]] = []
    ops_of: Dict[int, List[Tuple[float, float, str]]] = {
        c: [] for c in range(chips)}
    lines_seen = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            lines_seen.append(f"{plane.name}/{line.name}")
            if m and line.name == _OPS_LINE and int(m.group(1)) < chips:
                ops_of[int(m.group(1))] = [
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events
                ]
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if not e.name.startswith(SPAN_PREFIX):
                        continue
                    iv = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if e.name == WINDOW:
                        window = iv[:2]
                    else:
                        spans.append(iv)
    if window is None:
        raise TraceError(f"no {WINDOW!r} annotation in the trace")
    w0, w1 = window
    busy: Dict[int, float] = {}
    ops: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    for chip, events in sorted(ops_of.items()):
        clipped = [(max(a, w0), min(b, w1), n) for a, b, n in events
                   if b > w0 and a < w1]
        for a, b, n in clipped:
            ops[op_name(n)] += (b - a) * 1e-9
        merged = _union([(a, b) for a, b, _ in clipped])
        busy[chip] = sum(b - a for a, b in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        holes = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                 if edges[k + 1] > edges[k]]
        labels = _innermost(spans, [(a + b) / 2 for a, b in holes])
        for (a, b), lab in zip(holes, labels):
            gaps[lab] += (b - a) * 1e-9
    if not any(busy.values()):
        raise TraceError(
            "no device operation in the window; lines seen: "
            + ", ".join(lines_seen[:40]))

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:TOP]

    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy.values()) / len(busy),
        "busy_s_by_chip": busy,
        "op_seconds": dict(ops),
        "device_ops": top(ops),
        "idle_gaps": top(gaps),
    }


def reduce_file(path: str, chips: int) -> Dict:
    from jax.profiler import ProfileData

    return reduce_trace(ProfileData.from_file(path), chips)
