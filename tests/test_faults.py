"""Fault-injection + resilience layer (docs/robustness.md).

Covers: FaultPlan determinism/replay, the bit-identity invariant with
faults disabled, chaos-run determinism, the eps_k == 0 and all-dropped
aggregation guards, NaN quarantine, partial matching, the solver
fallback chain, and checkpoint/resume bit-identity.
"""
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import default_system, matching
from repro.core import joint as joint_mod
from repro.core import sample_round
from repro.data import SyntheticImages, non_iid_split
from repro.fed import (CHAOS_SPEC, FEELConfig, FEELTrainer, FaultPlan,
                       FaultSpec, ResilienceConfig, server)
from repro.models import cnn


# ----------------------------------------------------------------------
# FaultPlan: determinism, replay, spec round-trip
# ----------------------------------------------------------------------

def test_plan_same_spec_same_faults():
    a = FaultPlan(CHAOS_SPEC)
    b = FaultPlan(FaultSpec.from_dict(CHAOS_SPEC.to_dict()))
    for i in (0, 3, 17):
        ra, rb = a.for_round(i, 8), b.for_round(i, 8)
        assert np.array_equal(ra.dropout, rb.dropout)
        assert np.array_equal(ra.straggler, rb.straggler)
        assert np.array_equal(ra.delay_s, rb.delay_s)
        assert np.array_equal(ra.nan_upload, rb.nan_upload)
        assert ra.fail_matching == rb.fail_matching
        assert ra.fail_power == rb.fail_power


def test_plan_call_order_free():
    """Faults for round i must not depend on which rounds were queried
    before — this is what makes resume() replay exact faults."""
    a, b = FaultPlan(CHAOS_SPEC), FaultPlan(CHAOS_SPEC)
    ra = a.for_round(5, 6)           # fresh plan, round 5 first
    for i in range(5):
        b.for_round(i, 6)            # other plan walks 0..4 first
    rb = b.for_round(5, 6)
    assert np.array_equal(ra.dropout, rb.dropout)
    assert np.array_equal(ra.delay_s, rb.delay_s)
    assert a.retry_delay_s(5, 2, 1) == b.retry_delay_s(5, 2, 1)


def test_plan_window_and_zero_rate():
    spec = FaultSpec(seed=1, dropout_prob=1.0, start_round=2,
                     stop_round=4)
    plan = FaultPlan(spec)
    assert not plan.for_round(1, 4).any()
    assert plan.for_round(2, 4).dropout.all()
    assert not plan.for_round(4, 4).any()
    assert not FaultPlan(FaultSpec(seed=0)).for_round(0, 4).any()


def test_spec_rejects_unknown_fields():
    with pytest.raises(ValueError, match="unknown FaultSpec"):
        FaultSpec.from_dict({"seed": 0, "nope": 1})


def test_disjoint_fault_classes():
    plan = FaultPlan(FaultSpec(seed=3, dropout_prob=0.5,
                               straggler_prob=0.9, nan_prob=0.9))
    for i in range(10):
        rf = plan.for_round(i, 16)
        assert not (rf.dropout & rf.straggler).any()
        assert not (rf.dropout & rf.nan_upload).any()
        assert np.all(rf.delay_s[~rf.straggler] == 0.0)


# ----------------------------------------------------------------------
# aggregation guards (server.py)
# ----------------------------------------------------------------------

def _sys_with_eps(eps):
    sys_ = default_system(K=len(eps), N=3, Q=2, D_hat=4)
    import dataclasses
    return dataclasses.replace(sys_, eps=jnp.asarray(eps, jnp.float32))


def test_eps_zero_guard_no_nan():
    sys_ = _sys_with_eps([0.0, 0.5, 0.9])
    alpha = jnp.asarray([1.0, 1.0, 0.0])
    w = server.ipw_weights(sys_, alpha)
    assert bool(jnp.all(jnp.isfinite(w)))
    assert float(w[0]) == 0.0       # eps=0 device contributes nothing
    grads = {"w": jnp.ones((3, 2))}
    g = server.aggregate_gradients(sys_, grads, alpha)
    assert bool(jnp.all(jnp.isfinite(g["w"])))


def test_renormalized_aggregation():
    sys_ = _sys_with_eps([0.5, 0.5, 0.5])
    grads = {"w": jnp.asarray([[2.0], [4.0], [8.0]])}
    alpha = jnp.asarray([1.0, 1.0, 0.0])
    g = server.aggregate_gradients(sys_, grads, alpha, renormalize=True)
    # equal weights on the two survivors -> plain mean of their grads
    np.testing.assert_allclose(np.asarray(g["w"]), [3.0], rtol=1e-6)
    zero = server.aggregate_gradients(sys_, grads, jnp.zeros(3),
                                      renormalize=True)
    assert float(jnp.abs(zero["w"]).sum()) == 0.0
    assert server.ipw_mass(sys_, jnp.zeros(3)) == 0.0


# ----------------------------------------------------------------------
# partial matching (core/matching.py) + fallback chain (core/joint.py)
# ----------------------------------------------------------------------

def test_partial_matching_reports_unmatched():
    """K > N*Q: capacity can't seat everyone; the leftovers must be an
    explicit outcome, not a silent break."""
    sys_ = default_system(K=7, N=2, Q=2, D_hat=4)   # capacity 4 < 7
    st = sample_round(jax.random.PRNGKey(0), sys_)
    alpha = jnp.ones((7,), jnp.float32)
    reg = obs.Registry()
    obs.metrics.set_default(reg)
    res = matching.swap_matching(sys_, st.h, alpha)
    assert res.unmatched.size == 7 - 4
    assert not res.feasible
    seated = np.flatnonzero(res.rho.sum(axis=1) > 0)
    assert np.intersect1d(seated, res.unmatched).size == 0
    rendered = reg.render()
    assert "feel_solver_infeasible_total" in rendered


def test_forced_solver_failures_fall_back():
    sys_ = default_system(K=6, N=3, Q=2, D_hat=4)
    st = sample_round(jax.random.PRNGKey(1), sys_)
    tele = obs.Telemetry()
    reg = obs.Registry()
    obs.metrics.set_default(reg)
    rf = types.SimpleNamespace(fail_matching=True, fail_power=True,
                               dropout=np.zeros(6, bool))
    dec = joint_mod.proposed_scheme(sys_, st, gp_steps=30, faults=rf,
                                    power_evaluator="ccp", telemetry=tele)
    assert dec.feasible                       # greedy fallback succeeded
    assert "matching->greedy" in dec.fallbacks
    assert "ccp->closed_form" in dec.fallbacks
    kinds = [e.kind for e in tele.events if isinstance(e, obs.FaultEvent)]
    assert "solver_fail" in kinds and "fallback" in kinds
    rendered = reg.render()
    assert 'feel_fallbacks_total{solver="matching",to="greedy"}' in rendered
    assert 'feel_faults_injected_total{kind="solver_fail"}' in rendered


def test_no_faults_no_fallbacks():
    sys_ = default_system(K=6, N=3, Q=2, D_hat=4)
    st = sample_round(jax.random.PRNGKey(1), sys_)
    dec = joint_mod.proposed_scheme(sys_, st, gp_steps=30)
    assert dec.fallbacks == ()
    assert dec.unmatched.size == 0


@pytest.mark.parametrize("resilient", [False, True])
def test_natural_matching_error_needs_resilience(monkeypatch, resilient):
    """A matching exception that no fault plan injected propagates
    unless the resilience layer is on; only then does greedy take over."""
    sys_ = default_system(K=6, N=3, Q=2, D_hat=4)
    st = sample_round(jax.random.PRNGKey(1), sys_)

    def broken(*args, **kwargs):
        raise FloatingPointError("solver blew up")

    monkeypatch.setattr(joint_mod.matching_mod, "swap_matching", broken)
    if not resilient:
        with pytest.raises(FloatingPointError):
            joint_mod.proposed_scheme(sys_, st, gp_steps=30)
        return
    dec = joint_mod.proposed_scheme(sys_, st, gp_steps=30,
                                    repair_infeasible=True)
    assert dec.fallbacks == ("matching->greedy",)


# ----------------------------------------------------------------------
# trainer-level: bit identity, chaos determinism, quarantine, resume
# ----------------------------------------------------------------------

def _build_trainer(faults=None, res=None, telemetry=None, K=4):
    train = SyntheticImages.make(240, side=10, seed=0)
    test = SyntheticImages.make(80, side=10, seed=1)
    fd = non_iid_split(train, test, K=K, per_device=40,
                       mislabel_prop=0.1, seed=0)
    sys_ = default_system(K=K, N=2, Q=2, D_hat=8)
    cfg = FEELConfig(d_hat=8, gp_steps=30, eval_every=100)
    cc = cnn.CNNConfig(side=10)
    params = cnn.init(jax.random.PRNGKey(0), cc)
    model = types.SimpleNamespace(features=cnn.features, apply=cnn.apply,
                                  loss_fn=cnn.loss_fn,
                                  accuracy=cnn.accuracy)
    return FEELTrainer(sys_, fd, model, params, cfg, telemetry=telemetry,
                       faults=faults, resilience=res)


def _params_equal(a, b):
    return all(np.array_equal(x, y)
               for x, y in zip(jax.tree.leaves(a.params),
                               jax.tree.leaves(b.params)))


@pytest.mark.slow
def test_disabled_faults_bit_identical():
    """faults with all-zero rates + resilience on must not perturb the
    trajectory by a single bit (the PR's acceptance invariant)."""
    plain = _build_trainer()
    plain.run(3)
    guarded = _build_trainer(faults=FaultSpec(seed=0),
                             res=ResilienceConfig())
    guarded.run(3)
    assert _params_equal(plain, guarded)


@pytest.mark.slow
def test_chaos_deterministic_and_finite():
    spec = FaultSpec(seed=2, dropout_prob=0.4, straggler_prob=0.4,
                     straggler_delay_s=0.5, nan_prob=0.3,
                     matching_fail_prob=0.3, power_fail_prob=0.3)
    a = _build_trainer(faults=spec, res=ResilienceConfig())
    ms = a.run(4)
    for leaf in jax.tree.leaves(a.params):
        assert bool(np.isfinite(np.asarray(leaf)).all())
    assert sum(m.n_dropped for m in ms) > 0
    b = _build_trainer(faults=spec, res=ResilienceConfig())
    b.run(4)
    assert _params_equal(a, b)


@pytest.mark.slow
def test_total_dropout_skips_updates():
    spec = FaultSpec(seed=0, dropout_prob=1.0)
    tr = _build_trainer(faults=spec, res=ResilienceConfig())
    init = [np.asarray(x).copy() for x in jax.tree.leaves(tr.params)]
    ms = tr.run(2)
    assert all(m.skipped_update for m in ms)
    assert all(m.n_uploaded == 0 for m in ms)
    final = jax.tree.leaves(tr.params)
    assert all(np.array_equal(a, b) for a, b in zip(init, final))


@pytest.mark.slow
def test_nan_uploads_trigger_quarantine():
    spec = FaultSpec(seed=0, nan_prob=1.0)
    tele = obs.Telemetry()
    tr = _build_trainer(faults=spec,
                        res=ResilienceConfig(quarantine_threshold=1,
                                             quarantine_rounds=2),
                        telemetry=tele)
    ms = tr.run(3)
    for leaf in jax.tree.leaves(tr.params):
        assert bool(np.isfinite(np.asarray(leaf)).all())
    kinds = [e.kind for e in tele.events if isinstance(e, obs.FaultEvent)]
    assert "nan_upload" in kinds
    assert "quarantine" in kinds
    assert any(m.n_quarantined > 0 for m in ms[1:])


@pytest.mark.slow
def test_checkpoint_resume_bit_identical(tmp_path):
    spec = FaultSpec(seed=5, dropout_prob=0.3, nan_prob=0.2)
    res = ResilienceConfig(checkpoint_every=2,
                           checkpoint_dir=str(tmp_path))
    full = _build_trainer(faults=spec, res=res)
    full.run(4)
    half = _build_trainer(faults=spec, res=res)
    half.run(2)                      # checkpoint written at round 2
    assert os.path.exists(os.path.join(str(tmp_path),
                                       "feel_ckpt.npz"))
    resumed = _build_trainer(faults=spec, res=res)
    assert resumed.resume() == 2
    resumed.run(4)
    assert _params_equal(full, resumed)


@pytest.mark.slow
def test_resolve_policy_runs():
    spec = FaultSpec(seed=1, dropout_prob=0.5)
    tr = _build_trainer(faults=spec,
                        res=ResilienceConfig(dropout_policy="resolve"))
    ms = tr.run(3)
    assert any("resolve_survivors" in m.fallbacks for m in ms)
    for leaf in jax.tree.leaves(tr.params):
        assert bool(np.isfinite(np.asarray(leaf)).all())


def test_resolved_decision_is_the_one_reported():
    """After a survivor re-solve, ``RoundMetrics.decision`` is the
    re-solved decision: RBs only for the devices that uploaded."""
    spec = FaultSpec(seed=1, dropout_prob=0.5)
    tr = _build_trainer(faults=spec,
                        res=ResilienceConfig(dropout_policy="resolve"))
    ms = tr.run(3)
    resolved = [m for m in ms if "resolve_survivors" in m.fallbacks]
    assert resolved
    for m in ms:
        assert m.decision.fallbacks == m.fallbacks
        assert m.decision.net_cost == m.net_cost
    for m in resolved:
        assert int(m.decision.rho.sum()) == m.n_uploaded


def _server_step_traces(reg):
    return reg.counter("feel_server_step_traces_total").value()


def test_zero_ipw_mass_skips_update_without_the_server_step():
    """Survivors with zero IPW mass: the update is skipped on the host,
    parameters and optimizer state (Adam's count too) stay as they were,
    and the fused server step is never traced."""
    reg = obs.Registry()
    obs.metrics.set_default(reg)
    tr = _build_trainer(faults=FaultSpec(seed=0, dropout_prob=1.0),
                        res=ResilienceConfig())
    before = [np.asarray(x).copy()
              for x in jax.tree.leaves((tr.params, tr.opt_state))]
    m = tr.run_round(0)
    assert m.skipped_update and m.n_uploaded == 0
    after = jax.tree.leaves((tr.params, tr.opt_state))
    assert all(np.array_equal(a, b) for a, b in zip(before, after))
    assert int(tr.opt_state.count) == 0
    assert _server_step_traces(reg) == 0
    assert reg.counter("feel_rounds_skipped_total").value() == 1


def test_server_step_traces_once_in_clean_rounds():
    """Five clean rounds trace the fused server step once: a higher
    count would be a retrace, so a compile, every round."""
    reg = obs.Registry()
    obs.metrics.set_default(reg)
    tr = _build_trainer()
    ms = tr.run(5)
    assert not all(m.skipped_update for m in ms)
    assert _server_step_traces(reg) == 1
