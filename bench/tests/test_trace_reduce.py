import os

import pytest

import record_spans
import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")
SPANS = os.path.join(os.path.dirname(__file__), "data", "spans.xplane.pb")


def test_union_and_gaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr._gaps([(2, 3), (5, 8)], 0, 10) == [(0, 2), (3, 5), (8, 10)]


def test_reduce_attributes_idle_time_to_stages():
    devices = {"/device:TPU:0": [(10, 20, "a"), (15, 30, "b"), (60, 70, "a")]}
    steps = [(0, 50, 0), (50, 100, 1)]
    # round 0 starts at telemetry time 1.0 s; its "sigma" stage covers
    # telemetry 1.0 s + [30, 40) ns -> profiler [30, 40)
    spans = {0: (1.0, [("sigma", 1.0 + 30e-9, 10e-9)])}
    out = tr.reduce(devices, steps, spans)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx(30e-9)
    assert out["idle_pct"] == pytest.approx(70.0)
    assert dict(out["device_ops"]) == pytest.approx({"a": 20e-9,
                                                     "b": 15e-9})
    idle = dict(out["idle_gaps"])
    assert idle["sigma"] == pytest.approx(10e-9)
    assert idle["round_self"] == pytest.approx(60e-9)
    assert sum(idle.values()) == pytest.approx(70e-9)


def test_recorded_trace():
    """A trace recorded on a TPU v5e by record_trace.py: three steps of
    two small programs with host pauses between them."""
    devices, _, steps = tr.read_xplane(TRACE, "feel_round")
    assert [s[2] for s in steps] == [0, 1, 2]
    assert devices and all(devices.values())
    out = tr.reduce(devices, steps)
    assert 0 < out["busy_s"] < out["window_s"]
    assert 0 < out["idle_pct"] < 100
    # the 2 ms and 1 ms host pauses are idle device time
    assert out["window_s"] * (1 - out["idle_pct"] / 100) == pytest.approx(
        out["busy_s"])
    assert out["window_s"] - out["busy_s"] > 3 * 0.002
    assert sum(v for _, v in out["device_ops"]) >= out["busy_s"] * 0.99
    assert dict(out["idle_gaps"]).keys() <= {"round_self", "between_rounds"}


def test_recorded_trace_device_seconds_by_op_and_program():
    """``record_spans.py``'s trace: its ``round`` annotations as the
    steps; the programs are the two jitted lambdas and the eager sum and
    add."""
    devices, programs, steps = tr.read_xplane(SPANS, "round")
    assert len(steps) == record_spans.ROUNDS
    out = tr.reduce(devices, steps, programs=programs, top=3)
    assert set(out["programs_s"]) == {"jit__lambda", "jit__reduce_sum",
                                      "jit_add"}
    assert all(v > 0 for v in out["programs_s"].values())
    # every op runs inside a program
    assert sum(out["programs_s"].values()) >= out["busy_s"]
    # device_ops is the head of the full per-op table
    assert len(out["ops_s"]) > len(out["device_ops"]) == 3
    assert dict(out["device_ops"]) == {k: out["ops_s"][k]
                                       for k, _ in out["device_ops"]}
    assert min(v for _, v in out["device_ops"]) >= max(
        v for k, v in out["ops_s"].items()
        if k not in dict(out["device_ops"]))
    assert sum(out["ops_s"].values()) >= out["busy_s"] * 0.99
