"""From a profiler trace (``.xplane.pb``) to launches, transfers to the
host and idle device time per program span, on the profiler's clock.

A recording ``repro.obs.Telemetry`` opens a ``jax.profiler.TraceAnnotation``
under each span's name, so the trace holds the program's span tree as host
events around the runtime's own. Inside each profiled ``round`` every
launch, transfer and stretch of idle device time is given to the innermost
annotation named in ``rows`` that covers it, or to the round itself. A span
left out of ``rows`` counts toward the row around it. Anything inside a
``telemetry`` span (work done only because a sink or registry is on) is
given to that row whatever lies within it, and is left out of the totals,
which then describe the round as it runs untraced. A stage's ``block`` is
a wait, not a launch or a transfer, and counts as neither.

On a TPU one launch is one ``PJRT_LoadedExecutable_Execute`` host event,
and one transfer to the host one ``CommonPjRtBuffer::ToLiteral`` (beneath
it one ``D2H Dispatch`` and one ``tpu::System::TransferFromDevice``).
"""
from __future__ import annotations

import bisect
import collections
from typing import Dict, Iterable, List, Sequence, Tuple

import trace_reduce

LAUNCH = "PJRT_LoadedExecutable_Execute"
SYNC = "CommonPjRtBuffer::ToLiteral"
ROOT = "round"
SKIP = "telemetry"

HostEvent = Tuple[int, int, str]


def read_trace(path: str):
    """``(devices, host)``: per device plane the ``(start_ns, end_ns)``
    of its operations, and every host event as ``(start_ns, end_ns,
    name)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[int, int]]] = {}
    host: List[HostEvent] = []
    for plane in data.planes:
        if trace_reduce._DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            line = next((lines[n] for n in trace_reduce._OP_LINES
                         if n in lines), None)
            devices[plane.name] = [] if line is None else [
                (int(e.start_ns), int(e.end_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((int(e.start_ns), int(e.end_ns), e.name)
                            for e in line.events)
    return devices, host


def _labels(host: Sequence[HostEvent], rows: Iterable[str]):
    """The rounds' ``(start_ns, end_ns)``, and sorted, disjoint
    ``(start_ns, end_ns, label)`` over them: each piece carries the
    innermost row annotation covering it (``SKIP`` wherever one covers
    it), else ``ROOT``."""
    names = set(rows) | {SKIP}
    rounds = sorted((s, e) for s, e, n in host if n == ROOT)
    anns = [(s, e, n) for s, e, n in host if n in names
            and any(a <= s and e <= b for a, b in rounds)]
    points = sorted({p for s, e in rounds for p in (s, e)}
                    | {p for s, e, _ in anns for p in (s, e)})
    out = []
    for a, b in zip(points, points[1:]):
        if not any(s <= a and b <= e for s, e in rounds):
            continue
        cover = [(s, -e, n) for s, e, n in anns if s <= a and b <= e]
        if any(n == SKIP for _, _, n in cover):
            label = SKIP
        else:
            label = max(cover)[2] if cover else ROOT
        out.append((a, b, label))
    return rounds, out


def attribute(devices: Dict[str, List[Tuple[int, int]]],
              host: Sequence[HostEvent], rows: Iterable[str]) -> dict:
    """Launches, syncs, idle and self seconds per row of the profiled
    rounds; ``totals`` leaves out the ``SKIP`` row."""
    rounds, labels = _labels(host, rows)
    if not rounds:
        raise ValueError(f"trace has no {ROOT!r} annotations")
    starts = [a for a, _, _ in labels]
    table: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: {"launches": 0, "syncs": 0, "idle_s": 0.0, "self_s": 0.0})

    def label_at(t: int):
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and labels[i][0] <= t < labels[i][1]:
            return labels[i][2]
        return None

    for s, _, name in host:
        kind = ("launches" if name == LAUNCH
                else "syncs" if name == SYNC else None)
        label = label_at(s) if kind else None
        if label is not None:
            table[label][kind] += 1
    for a, b, label in labels:
        table[label]["self_s"] += (b - a) / 1e9
    lo, hi = rounds[0][0], max(e for _, e in rounds)
    for events in devices.values():
        busy = trace_reduce.union((max(s, lo), min(e, hi))
                                  for s, e in events if e > lo and s < hi)
        for g0, g1 in trace_reduce._gaps(busy, lo, hi):
            i = max(bisect.bisect_right(starts, g0) - 1, 0)
            while i < len(labels) and labels[i][0] < g1:
                a, b, label = labels[i]
                overlap = min(b, g1) - max(a, g0)
                if overlap > 0:
                    table[label]["idle_s"] += overlap / 1e9 / len(devices)
                i += 1
    totals = {k: sum(v[k] for n, v in table.items() if n != SKIP)
              for k in ("launches", "syncs")}
    return {"rounds": len(rounds), "rows": dict(table), "totals": totals}


def span_table(path: str, rows: Iterable[str]) -> dict:
    devices, host = read_trace(path)
    return attribute(devices, host, rows)


def format_table(out: dict) -> List[str]:
    """One line per row, per profiled round, largest self time first."""
    n = out["rounds"]
    lines = [f"spans over {n} profiled rounds, per round: launches syncs "
             f"idle_ms self_ms"]
    for name, v in sorted(out["rows"].items(),
                          key=lambda kv: -kv[1]["self_s"]):
        lines.append(f"span {name}: {v['launches'] / n:.2f} "
                     f"{v['syncs'] / n:.2f} {1e3 * v['idle_s'] / n:.3f} "
                     f"{1e3 * v['self_s'] / n:.3f}")
    t = out["totals"]
    lines.append(f"spans total (outside {SKIP}): "
                 f"{t['launches'] / n:.2f} launches {t['syncs'] / n:.2f} "
                 f"syncs per round")
    return lines
