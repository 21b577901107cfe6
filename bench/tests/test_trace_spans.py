import os

import pytest

import record_spans
import trace_spans as ts

TRACE = os.path.join(os.path.dirname(__file__), "data", "spans.xplane.pb")
LAUNCH, SYNC = ts.LAUNCH, ts.SYNC


def test_attribute_innermost_row_and_skip():
    host = [
        (0, 100, "round"),
        (10, 40, "a"),
        (20, 30, "a.inner"),          # not a row: counts toward a
        (32, 38, "a2"),               # a row inside a row
        (50, 70, "telemetry"),
        (55, 60, "b"),                # a row inside telemetry: skipped
        (12, 13, LAUNCH), (21, 22, LAUNCH), (22, 23, SYNC),
        (33, 34, LAUNCH),
        (51, 52, LAUNCH), (56, 57, SYNC),
        (80, 81, LAUNCH),             # the round's own
        (120, 121, LAUNCH),           # outside every round
    ]
    devices = {"/device:TPU:0": [(0, 10), (40, 50), (70, 100)]}
    out = ts.attribute(devices, host, ["a", "a2", "b", "telemetry"])
    rows = out["rows"]
    assert out["rounds"] == 1
    assert (rows["a"]["launches"], rows["a"]["syncs"]) == (2, 1)
    assert (rows["a2"]["launches"], rows["a2"]["syncs"]) == (1, 0)
    assert (rows["telemetry"]["launches"], rows["telemetry"]["syncs"]) \
        == (1, 1)
    assert (rows["round"]["launches"], rows["round"]["syncs"]) == (1, 0)
    assert "b" not in rows
    assert out["totals"] == {"launches": 4, "syncs": 1}
    # device idle [10, 40) in a and a2, [50, 70) in telemetry
    assert rows["a"]["idle_s"] == pytest.approx(24e-9)
    assert rows["a2"]["idle_s"] == pytest.approx(6e-9)
    assert rows["telemetry"]["idle_s"] == pytest.approx(20e-9)
    assert rows["round"]["idle_s"] == pytest.approx(0.0)
    assert rows["a"]["self_s"] == pytest.approx(24e-9)
    assert rows["round"]["self_s"] == pytest.approx(50e-9)
    assert sum(v["self_s"] for v in rows.values()) == pytest.approx(100e-9)


def test_recorded_trace_counts_known_launches_and_syncs():
    """A trace recorded on a TPU v5e by record_spans.py: each round's
    spans make known numbers of launches and transfers to the host."""
    out = ts.span_table(TRACE, ["a", "b", "c", "telemetry"])
    n = record_spans.ROUNDS
    assert out["rounds"] == n
    got = {k: (v["launches"], v["syncs"]) for k, v in out["rows"].items()}
    assert got == {k: (n * a, n * s)
                   for k, (a, s) in record_spans.KNOWN.items()}
    outside = [v for k, v in record_spans.KNOWN.items() if k != "telemetry"]
    assert out["totals"] == {"launches": n * sum(a for a, _ in outside),
                             "syncs": n * sum(s for _, s in outside)}
    # stage a waits 2 ms on the host after its block: idle device time
    assert out["rows"]["a"]["idle_s"] > n * 0.002
    lines = ts.format_table(out)
    assert len(lines) == 2 + len(record_spans.KNOWN)
