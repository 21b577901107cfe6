"""What a metric's reader can reach through ``run.Context``: spans at any
depth with their attributes, and device time by program and by op."""
import pytest
from repro.obs.spans import SpanNode

import run as bench_run


def _node(name, dur, *children, **attrs):
    return SpanNode(name=name, t0_s=0.0, dur_s=dur, attrs=attrs,
                    children=list(children))


def _ctx(**kw):
    return bench_run.Context(setup_s=1.0, walls=[0.1], window_s=0.1, **kw)


def test_span_ms_sums_within_a_round_at_any_depth():
    trees = [
        _node("round", 1.0, _node("round.draws", 0.1),
              _node("selection", 0.3, _node("selection.gp", 0.25, steps=20)),
              _node("round.draws", 0.05)),
        _node("round", 1.0, _node("round.draws", 0.2),
              _node("a", 0.4, _node("a", 0.3))),
    ]
    ctx = _ctx(trees=trees)
    assert ctx.span_ms("round.draws") == pytest.approx(175.0)
    assert ctx.span_ms("selection.gp") == pytest.approx(125.0)
    assert ctx.span_ms("a") == pytest.approx(200.0)   # the inner one once
    assert ctx.span_ms("round") == pytest.approx(1000.0)
    assert ctx.span_ms("no.such.span") is None
    assert [n.attrs["steps"] for n in ctx.span_nodes("selection.gp")] == [20]
    assert _ctx().span_ms("round") is None


def test_device_ms_per_profiled_round_by_program_and_op():
    profile = {"steps": 4, "programs_s": {"jit_local_grads": 0.2},
               "ops_s": {"%fusion.6": 0.02}}
    ctx = _ctx(profile=profile)
    assert ctx.program_ms("jit_local_grads") == pytest.approx(50.0)
    assert ctx.op_ms("%fusion.6") == pytest.approx(5.0)
    assert ctx.program_ms("jit_other") is None
    assert _ctx().program_ms("jit_local_grads") is None
    assert _ctx().op_ms("%fusion.6") is None
