"""From a profiler trace (``.xplane.pb``) to device busy time, idle share,
the top device operations, the idle gaps named by program stage, and
device seconds by operation and by XLA program.

The traced sub-window is the span from the start of the first to the end
of the last host step annotation (``jax.profiler.StepTraceAnnotation``,
one per round). Busy time is the union of the intervals in which an
operation runs on a device, clipped to that sub-window, averaged over
the devices. Each idle gap is attributed to what the host was doing: the
program stage whose span covers it, ``round_self`` inside a round but
outside its stages, or ``between_rounds``. Stage spans come from the
program's telemetry clock; the offset to the profiler's clock is taken
per round from the step annotation that wraps it. A device's ``XLA
Modules`` line holds one event per program execution, named
``jit_<function>(<fingerprint>)``; a program's seconds are summed over
its fingerprints under ``jit_<function>``.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OP_LINES = ("XLA Ops", "XLA Modules")
_MODULE_LINE = "XLA Modules"


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def _events(line, sep: str) -> List[Tuple[int, int, str]]:
    return [] if line is None else [
        (int(e.start_ns), int(e.end_ns), e.name.split(sep)[0])
        for e in line.events]


def read_xplane(path: str, step_name: str):
    """``(device_ops, device_programs, steps)`` of a trace: per device
    plane the ``(start_ns, end_ns, op name)`` of its operations and the
    ``(start_ns, end_ns, program name)`` of its program executions, and
    the host's ``(start_ns, end_ns, step_num)`` step annotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[int, int, str]]] = {}
    programs: Dict[str, List[Tuple[int, int, str]]] = {}
    steps: List[Tuple[int, int, int]] = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            line = next((lines[n] for n in _OP_LINES if n in lines), None)
            # "%fusion.6 = f32[...] fusion(...)": keep the op's own name
            devices[plane.name] = _events(line, " = ")
            # "jit_local_grads(1234...)": keep the function's name
            programs[plane.name] = _events(lines.get(_MODULE_LINE), "(")
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == step_name:
                        stats = dict(e.stats)
                        steps.append((int(e.start_ns), int(e.end_ns),
                                      int(stats.get("step_num", -1))))
    steps.sort()
    return devices, programs, steps


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _labels(steps, stage_spans, lo, hi):
    """Sorted, disjoint ``(start_ns, end_ns, label)`` covering [lo, hi]."""
    segs = []
    for s, e, step in steps:
        segs.append((s, e, "round_self"))
        spans = stage_spans.get(step) if stage_spans else None
        if spans:
            round_t0, stages = spans
            off = s - int(round_t0 * 1e9)
            for name, t0, dur in stages:
                a = int(t0 * 1e9) + off
                segs.append((max(a, s), min(a + int(dur * 1e9), e), name))
    # paint: later (inner) segments override the round's own label
    points = sorted({lo, hi} | {p for a, b, _ in segs for p in (a, b)
                                if lo <= p <= hi})
    out = []
    for a, b in zip(points, points[1:]):
        mid = (a + b) // 2
        label = "between_rounds"
        for s, e, name in segs:
            if s <= mid < e:
                label = name
        out.append((a, b, label))
    return out


def reduce(devices: Dict[str, List[Tuple[int, int, str]]],
           steps: List[Tuple[int, int, int]],
           stage_spans: Optional[Dict[int, tuple]] = None,
           top: int = 10,
           programs: Optional[Dict[str, List[Tuple[int, int, str]]]] = None
           ) -> dict:
    """Busy/idle of the stepped sub-window, top ops, idle by stage, and
    the seconds of every op (``ops_s``) and of every program
    (``programs_s``, from ``programs``) in it, averaged over devices.

    ``stage_spans`` maps a step number to ``(round_t0_s, [(stage,
    t0_s, dur_s), ...])`` on the telemetry clock.
    """
    if not steps:
        raise ValueError("trace has no step annotations")
    if not devices:
        raise ValueError("trace has no TPU device plane")
    lo = min(s for s, _, _ in steps)
    hi = max(e for _, e, _ in steps)
    window_ns = hi - lo
    labels = _labels(steps, stage_spans, lo, hi)
    starts = [a for a, _, _ in labels]
    busy_ns = 0
    ops: Dict[str, float] = collections.defaultdict(float)
    idle: Dict[str, float] = collections.defaultdict(float)
    progs: Dict[str, float] = collections.defaultdict(float)
    for events in (programs or {}).values():
        for s, e, name in events:
            if e > lo and s < hi:
                progs[name] += (min(e, hi) - max(s, lo)) / 1e9
    for events in devices.values():
        inside = [(max(s, lo), min(e, hi), name) for s, e, name in events
                  if e > lo and s < hi]
        busy = union((s, e) for s, e, _ in inside)
        busy_ns += sum(e - s for s, e in busy)
        for s, e, name in inside:
            ops[name] += (e - s) / 1e9
        for g0, g1 in _gaps(busy, lo, hi):
            i = max(bisect.bisect_right(starts, g0) - 1, 0)
            while i < len(labels) and labels[i][0] < g1:
                a, b, name = labels[i]
                overlap = min(b, g1) - max(a, g0)
                if overlap > 0:
                    idle[name] += overlap / 1e9
                i += 1
    n = len(devices)
    busy_s = busy_ns / n / 1e9
    window_s = window_ns / 1e9
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "steps": len(steps),
        "device_ops": [[k, v / n] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "ops_s": {k: v / n for k, v in ops.items()},
        "programs_s": {k: v / n for k, v in progs.items()},
        "idle_gaps": [[k, v / n] for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])[:top]],
    }
