"""Tests for the repro.obs telemetry subsystem: JSONL round-trip,
no-op default sink, and the instrumented FEELTrainer round."""
import collections
import glob
import json
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import default_system, selection
from repro.data import SyntheticImages, non_iid_split
from repro.fed import FEELConfig, FEELTrainer
from repro.models import cnn


# ------------------------------------------------------------------ trace

def test_trace_roundtrip_jsonl(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    with obs.Telemetry(path=path, meta={"who": "test"}) as tele:
        tele.begin_round(0)
        with tele.stage("matching"):
            time.sleep(0.01)
        tele.solver("matching", swaps=3, sweeps=2, feasible=True)
        tele.devices(energy_cmp_j=[1.0, 2.0], energy_com_j=[0.5, 0.5],
                     cost=[7.5, 12.5], reward=[0.1, 0.2],
                     selected=[4, 5], uploaded=[1, 0],
                     mislabel_frac=[0.25, 0.0])
        tele.round_end(wall_s=0.02, net_cost=-1.5, delta_obj=3.0,
                       n_selected=9, n_uploaded=1, feasible=True)

    records = obs.load_trace(path)
    assert records[0]["ev"] == "header"
    assert records[0]["v"] == obs.SCHEMA_VERSION
    assert records[0]["meta"] == {"who": "test"}
    kinds = [r["ev"] for r in records[1:]]
    assert kinds == ["stage", "solver", "devices", "round"]

    # every line is plain JSON; parse_record gives typed events back
    parsed = [obs.parse_record(r) for r in records]
    assert parsed[0] is None  # header has no event class
    st, so, dv, ro = parsed[1:]
    assert isinstance(st, obs.StageEvent) and st.stage == "matching"
    assert st.round == 0 and st.dur_s >= 0.01
    assert isinstance(so, obs.SolverEvent)
    assert so.counters["swaps"] == 3
    assert isinstance(dv, obs.DeviceEvent) and dv.selected == [4, 5]
    assert isinstance(ro, obs.RoundEvent) and ro.net_cost == -1.5

    # in-memory events and the file carry identical records
    assert [e.to_record() for e in tele.events] == records[1:]


def test_summary_aggregates_and_csv_rows(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with obs.Telemetry(path=path) as tele:
        for i in range(3):
            tele.begin_round(i)
            with tele.stage("sigma"):
                pass
            tele.solver("power", method="ccp", iterations=4,
                        feasible=(i != 1))
            tele.round_end(wall_s=0.5, net_cost=0.0, delta_obj=0.0,
                           n_selected=1, n_uploaded=1, feasible=(i != 1))

    s = obs.summarize(obs.load_trace(path))
    assert s.n_rounds == 3
    assert s.infeasible_rounds == 1
    assert s.stages["sigma"].calls == 3
    assert s.solvers["power"]["calls"] == 3
    assert s.solvers["power"]["iterations"] == 12
    assert s.solvers["power"]["infeasible"] == 1
    assert s.total_wall_s == pytest.approx(1.5)

    rows = obs.rows(s)
    names = [r[0] for r in rows]
    assert "telemetry.stage.sigma" in names
    assert "telemetry.solver.power" in names
    assert "telemetry.round" in names
    for name, us, derived in rows:
        assert isinstance(us, float) and "," not in derived  # CSV-safe

    # summarize accepts live event objects and raw dicts identically
    s2 = obs.summarize(tele.events)
    assert obs.rows(s2) == rows


def test_schema_version_mismatch_raises():
    with pytest.raises(ValueError):
        obs.parse_record({"ev": "stage", "v": obs.SCHEMA_VERSION + 1,
                          "stage": "x", "t0_s": 0.0, "dur_s": 0.0})


def test_null_sink_records_nothing(tmp_path):
    null = obs.NullTelemetry()
    with null.stage("matching"):
        pass
    null.solver("power", iterations=3)
    null.round_end(wall_s=0.0, net_cost=0.0, delta_obj=0.0, n_selected=0,
                   n_uploaded=0, feasible=True)
    assert not hasattr(null, "events")
    assert null.enabled is False
    # block is the identity when disabled (no device sync forced)
    x = object()
    assert null.block(x) is x
    # the process default is a no-op unless explicitly installed
    assert obs.get_default().enabled is False
    assert obs.resolve(None) is obs.get_default()
    tele = obs.Telemetry()
    assert obs.resolve(tele) is tele


def test_set_default_install_and_reset():
    tele = obs.Telemetry()
    obs.set_default(tele)
    try:
        assert obs.resolve(None) is tele
    finally:
        obs.set_default(None)
    assert obs.get_default() is obs.NULL


def test_load_trace_tolerates_truncated_final_line(tmp_path):
    path = str(tmp_path / "crash.jsonl")
    with obs.Telemetry(path=path) as tele:
        tele.begin_round(0)
        tele.solver("power", method="closed_form", feasible=True)
    # simulate a process dying mid-write
    with open(path, "a") as f:
        f.write('{"ev": "round", "v": 2, "wall_s": 0.')

    with pytest.warns(UserWarning, match="truncated final trace line"):
        records = obs.load_trace(path)
    assert [r["ev"] for r in records] == ["header", "solver"]

    # strict mode restores the raise
    with pytest.raises(json.JSONDecodeError):
        obs.load_trace(path, strict=True)

    # corruption anywhere else still raises in default mode
    bad = str(tmp_path / "corrupt.jsonl")
    with open(path) as f:
        lines = f.readlines()
    with open(bad, "w") as f:
        f.write(lines[0])
        f.write('{"ev": "solv\n')  # malformed *interior* line
        f.write(lines[1])
    with pytest.raises(json.JSONDecodeError):
        obs.load_trace(bad)


def test_telemetry_close_is_idempotent(tmp_path):
    path = str(tmp_path / "t.jsonl")
    tele = obs.Telemetry(path=path)
    tele.begin_round(0)
    tele.solver("power", feasible=True)
    tele.close()
    tele.close()  # double close: no error, no re-registration issues
    assert obs.load_trace(path)[-1]["ev"] == "solver"
    # events stay readable in memory after close; file writes stop
    tele.solver("power", feasible=True)
    assert len(tele.events) == 2
    assert len(obs.load_trace(path)) == 2  # header + first solver only

    # context-manager exit and explicit close compose
    with obs.Telemetry(path=str(tmp_path / "u.jsonl")) as t2:
        t2.close()


# ------------------------------------------------------- trainer round

def _tiny_trainer(telemetry=None, scheme="proposed", optimizer="adam"):
    train = SyntheticImages.make(200, side=8, seed=0)
    test = SyntheticImages.make(50, side=8, seed=1)
    data = non_iid_split(train, test, K=4, per_device=20,
                         mislabel_prop=0.2, seed=0)
    sys_ = default_system(K=4, N=3, Q=2, D_hat=8)
    cfg = FEELConfig(scheme=scheme, d_hat=8, gp_steps=20, eval_every=1,
                     optimizer=optimizer)
    cc = cnn.CNNConfig(side=8)
    params = cnn.init(jax.random.PRNGKey(0), cc)
    model = types.SimpleNamespace(features=cnn.features, apply=cnn.apply,
                                  loss_fn=cnn.loss_fn,
                                  accuracy=cnn.accuracy)
    return FEELTrainer(sys_, data, model, params, cfg, telemetry=telemetry)


def test_run_round_emits_six_stages_with_consistent_timings(tmp_path):
    path = str(tmp_path / "round.jsonl")
    tele = obs.Telemetry(path=path)
    trainer = _tiny_trainer(telemetry=tele)
    m = trainer.run_round(0, eval_now=False)
    tele.close()

    stage_evs = [e for e in tele.events if isinstance(e, obs.StageEvent)]
    round_evs = [e for e in tele.events if isinstance(e, obs.RoundEvent)]
    assert len(round_evs) == 1
    wall = round_evs[0].wall_s

    names = [e.stage for e in stage_evs]
    for required in obs.REQUIRED_STAGES:
        assert required in names, f"missing stage {required}"

    # timings are monotonically consistent: stages are emitted in
    # increasing start order, each has non-negative duration, no stage
    # overruns the round, and together they account for the round wall
    starts = [e.t0_s for e in stage_evs]
    assert starts == sorted(starts)
    assert all(e.dur_s >= 0.0 for e in stage_evs)
    assert all(e.round == 0 for e in stage_evs)
    total = sum(e.dur_s for e in stage_evs)
    assert total <= wall * 1.01 + 1e-6
    assert total >= 0.5 * wall  # stages explain the bulk of the round

    # the trace on disk round-trips to the same picture
    s = obs.summarize(obs.load_trace(path))
    assert s.n_rounds == 1
    assert set(obs.REQUIRED_STAGES) <= set(s.stages)

    # device event matches the round metrics
    dev = [e for e in tele.events if isinstance(e, obs.DeviceEvent)][0]
    assert sum(dev.selected) == m.n_selected
    assert sum(dev.uploaded) == m.n_uploaded
    assert len(dev.energy_cmp_j) == 4
    assert all(v >= 0 for v in dev.energy_com_j)
    # net cost (eq. 18) == sum_k cost_k - sum_k reward_k
    assert (sum(dev.cost) - sum(dev.reward)
            == pytest.approx(m.net_cost, rel=1e-4, abs=1e-7))


def test_trainer_disabled_by_default_and_unchanged():
    trainer = _tiny_trainer()
    assert trainer.obs.enabled is False
    m = trainer.run_round(0)
    assert np.isfinite(m.net_cost)

    # telemetry does not perturb training numerics
    t2 = _tiny_trainer(telemetry=obs.Telemetry())
    m2 = t2.run_round(0)
    assert m2.net_cost == pytest.approx(m.net_cost)
    assert m2.n_selected == m.n_selected
    assert m2.n_uploaded == m.n_uploaded


def test_full_observability_is_bit_for_bit_identical(tmp_path):
    """The whole observability stack — trace + profiling + metrics +
    monitor — must not change a single bit of the training state."""
    plain = _tiny_trainer()
    ms_plain = plain.run(2)

    reg = obs.Registry()
    obs.metrics.set_default(reg)
    tele = obs.Telemetry(path=str(tmp_path / "t.jsonl"), profile=True)
    inst = _tiny_trainer(telemetry=tele)
    inst.monitor = obs.ConvergenceMonitor(inst.sys, telemetry=tele,
                                          registry=reg)
    ms_inst = inst.run(2)
    obs.metrics.set_default(None)
    tele.close()

    leaves_a = jax.tree.leaves(plain.params)
    leaves_b = jax.tree.leaves(inst.params)
    assert len(leaves_a) == len(leaves_b)
    for a, b in zip(leaves_a, leaves_b):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for ma, mb in zip(ms_plain, ms_inst):
        assert ma.net_cost == mb.net_cost  # exact, not approx
        assert ma.n_selected == mb.n_selected
        assert ma.n_uploaded == mb.n_uploaded

    # and the instrumented run actually recorded everything — including
    # the v4 span instrumentation (nested solver spans + round roots)
    kinds = {type(e).__name__ for e in tele.events}
    assert {"StageEvent", "SolverEvent", "RoundEvent",
            "ProfileEvent", "SpanEvent"} <= kinds
    span_names = {e.name for e in tele.events
                  if isinstance(e, obs.SpanEvent)}
    assert "round" in span_names
    assert {"selection.gp", "selection.recover"} <= span_names
    assert reg.counter("feel_rounds_total").value() == 2.0
    assert inst.monitor.summary()["rounds"] == 2


def _assert_decisions_equal(a, b):
    for f in ("rho", "p", "delta", "unmatched"):
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    for f in ("net_cost", "delta_obj", "objective", "feasible", "swaps",
              "fallbacks"):
        assert getattr(a, f) == getattr(b, f), f


def test_null_sink_builds_no_annotation_and_is_bit_identical(monkeypatch):
    """A round on the NULL sink opens no profiler annotation, and it
    computes exactly what a recording round computes."""
    rec = _tiny_trainer(telemetry=obs.Telemetry())
    ms_rec = rec.run(2)

    class Refused:
        def __init__(self, *args, **kw):
            raise AssertionError("the NULL sink built a TraceAnnotation")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refused)
    with pytest.raises(AssertionError):
        obs.Telemetry().span("x").__enter__()
    null = _tiny_trainer(telemetry=obs.NULL)
    assert null.obs is obs.NULL
    ms_null = null.run(2)

    for a, b in zip(jax.tree.leaves(rec.params),
                    jax.tree.leaves(null.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for ma, mb in zip(ms_rec, ms_null):
        assert ma.net_cost == mb.net_cost
        assert ma.n_selected == mb.n_selected
        assert ma.n_uploaded == mb.n_uploaded
        assert ma.test_acc == mb.test_acc
        _assert_decisions_equal(ma.decision, mb.decision)


# ------------------------------------------- spans on the profiler clock

def _profiled(fn, log_dir):
    """Run ``fn`` under the profiler; return the host events of the
    trace as ``name -> [(start_ns, end_ns), ...]``."""
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=options):
        fn()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    events = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events[e.name].append((int(e.start_ns), int(e.end_ns)))
    return events


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_round_spans_are_profiler_annotations(tmp_path):
    tele = obs.Telemetry()
    trainer = _tiny_trainer(telemetry=tele)
    trainer.run_round(0)  # compiles outside the profile
    events = _profiled(lambda: trainer.run_round(1), tmp_path)

    (rnd,) = events["round"]
    for name in ("round.draws", "data", "sigma", "matching.prep",
                 "matching", "power", "selection", "objective",
                 "joint.finish", "round.uploads", "local_grads",
                 "aggregate", "round.record", "telemetry"):
        assert events[name], f"no {name!r} annotation in the trace"
        assert all(_inside(e, rnd) for e in events[name]), name
    # two openings each of round.draws and round.uploads; the telemetry
    # of the round and of the selection solve
    assert len(events["round.draws"]) == 2
    assert len(events["round.uploads"]) == 2
    assert len(events["telemetry"]) == 2
    # the annotations carry the recorded spans' own nesting
    (sel,) = events["selection"]
    assert any(_inside(e, sel) for e in events["telemetry"])
    assert all(_inside(e, sel) for e in events["selection.gp"])


def test_selection_count_sync_sits_in_telemetry_span(tmp_path):
    sys_ = default_system(K=4, N=3, Q=2, D_hat=8)
    sigma = jax.random.uniform(jax.random.PRNGKey(0), (4, 8))
    mask = jnp.ones_like(sigma)
    tele = obs.Telemetry()

    def solve():
        with tele.stage("selection"):
            tele.block(selection.solve_selection(sys_, sigma, mask, steps=20,
                                                 telemetry=tele))

    solve()  # compiles outside the profile
    events = _profiled(solve, tmp_path)

    (sel,) = events["selection"]
    (tel,) = events["telemetry"]
    assert _inside(tel, sel)
    # every transfer to the host in the solve is the count's, and it
    # falls inside the telemetry span
    syncs = [e for e in events["np.asarray(jax.Array)"] if _inside(e, sel)]
    assert len(syncs) == 1 and _inside(syncs[0], tel)
    # the recorded tree has the same shape
    spans = {e.name: e for e in tele.events if isinstance(e, obs.SpanEvent)}
    stages = [e for e in tele.events if isinstance(e, obs.StageEvent)]
    assert spans["telemetry"].parent_id == stages[-1].span_id
