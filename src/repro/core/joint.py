"""Algorithm 1: joint resource allocation + data selection, and the four
baseline schemes of paper §VI-A.

The server-side round decision is:
  1. solve Problem 3 (RB assignment + power) via Algorithm 2/3,
  2. solve Problem 4 (data selection) via Algorithms 4/5,
and ship (delta*, rho*, p*) back to the devices.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..obs import metrics as metrics_mod
from . import cost as cost_mod
from . import delta as delta_mod
from . import matching as matching_mod
from . import power as power_mod
from . import selection as selection_mod
from .types import RoundState, SystemParams

Array = jax.Array


@dataclasses.dataclass
class RoundDecision:
    """Server decision for one communication round."""

    rho: np.ndarray      # (K, N) RB assignment
    p: np.ndarray        # (K, N) powers
    delta: np.ndarray    # (K, J) binary data selection
    net_cost: float      # eq. (18)
    delta_obj: float     # Delta_hat(delta), eq. (26)
    objective: float     # Problem-2 objective
    feasible: bool
    swaps: int = 0
    #: available devices the matching could not give an RB (partial
    #: matching outcome, see core/matching.py) — they cannot upload.
    unmatched: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int64))
    #: solver degradations taken while producing this decision, e.g.
    #: ["matching->greedy", "ccp->closed_form"]; empty = clean solve.
    fallbacks: tuple = ()


def _finish(sys: SystemParams, rho, p, delta, state: RoundState,
            feasible: bool, swaps: int = 0, unmatched=None,
            fallbacks: tuple = (), telemetry=None) -> RoundDecision:
    tele = obs.resolve(telemetry)
    with tele.stage("objective"):
        rho_j = jnp.asarray(rho, jnp.float32)
        p_j = jnp.asarray(p, jnp.float32)
        delta_j = jnp.asarray(delta, jnp.float32)
        n_sel = jnp.sum(delta_j, axis=1)
        nc = float(cost_mod.net_cost(sys, rho_j, p_j, n_sel))
        dv = float(delta_mod.delta(sys, delta_j, state.sigma))
        obj = float(sys.lam) * dv + (1.0 - float(sys.lam)) * nc
    with tele.span("joint.finish"):
        reg = metrics_mod.get_default()
        if reg.enabled:
            reg.counter("feel_decisions_total",
                        "round decisions evaluated (eq. 18 + eq. 26)").inc()
            reg.gauge("feel_decision_net_cost",
                      "net cost (eq. 18) of the last round decision").set(nc)
            reg.gauge("feel_decision_delta_obj",
                      "Delta_hat (eq. 26) of the last round decision").set(dv)
        if unmatched is None:
            unmatched = np.zeros(0, np.int64)
        return RoundDecision(rho=np.asarray(rho), p=np.asarray(p),
                             delta=np.asarray(delta), net_cost=nc,
                             delta_obj=dv, objective=obj, feasible=feasible,
                             swaps=swaps,
                             unmatched=np.asarray(unmatched, np.int64),
                             fallbacks=tuple(fallbacks))


def _count_injected(kind: str) -> None:
    reg = metrics_mod.get_default()
    if reg.enabled:
        reg.counter("feel_faults_injected_total",
                    "faults injected by the FaultPlan, by kind").inc(
                        1, kind=kind)


def _count_fallback(solver: str, to: str) -> None:
    reg = metrics_mod.get_default()
    if reg.enabled:
        reg.counter("feel_fallbacks_total",
                    "solver degradations by solver and target").inc(
                        1, solver=solver, to=to)


def _greedy_fallback(sys: SystemParams, state: RoundState, tele,
                     injected: bool, reason: str):
    """Terminal link of the matching chain: greedy max-gain RB
    assignment (the baseline-3/4 construction) + exact closed-form
    powers.  Pure numpy + one closed-form solve — cannot raise."""
    h = np.asarray(state.h)
    alpha = np.asarray(state.alpha)
    with tele.span("joint.greedy_fallback", reason=reason):
        rho = _greedy_rb(sys, h, alpha, prefer_max=True)
        with tele.stage("power"):
            p, cost, ok = power_mod.allocate_power(
                sys, jnp.asarray(rho), state.h, state.alpha,
                method="closed_form", telemetry=tele)
            p = tele.block(p)
    tele.fault("fallback", injected=injected, solver="matching",
               to="greedy", reason=reason)
    _count_fallback("matching", "greedy")
    avail = np.flatnonzero(alpha > 0)
    unmatched = avail[rho[avail].sum(axis=1) <= 0]
    return rho, np.asarray(p), ok and unmatched.size == 0, unmatched


def proposed_scheme(sys: SystemParams, state: RoundState,
                    selection_method: str = "faithful",
                    power_evaluator: str = "closed_form",
                    gp_steps: int = 400,
                    gp_step0: float = 0.3,
                    matching_mode: str = "auto",
                    selection_chunk: int = 0,
                    faults=None,
                    repair_infeasible: bool = False,
                    telemetry=None) -> RoundDecision:
    """Algorithm 1 (the paper's proposed scheme).

    ``matching_mode``/``selection_chunk`` select the batched solver
    variants (core/matching.py, core/selection.py — see
    docs/solvers.md); the defaults keep small rounds on the historical
    scalar/full-matrix paths.

    ``faults``: an optional ``repro.fed.faults.RoundFaults`` whose
    ``fail_power``/``fail_matching`` flags force the corresponding
    solve to fail so the fallback chain runs (chaos testing).  The
    chain — CCP power failure -> closed-form evaluator, failed/
    infeasible matching -> greedy feasible baseline — also catches
    *natural* failures when the resilience layer is on (``faults``
    given or ``repair_infeasible`` set): a solver exception then
    degrades instead of propagating, and every degradation is recorded
    as a ``fault`` trace event plus ``feel_fallbacks_total``.  With the
    layer off, a natural solver exception propagates.

    ``repair_infeasible``: additionally route *naturally infeasible*
    (but non-crashing) matchings through the greedy fallback when that
    repairs feasibility.  Off by default so a plain run stays
    bit-for-bit the pre-fallback behavior; ``FEELTrainer`` turns it on
    whenever its resilience layer is active.
    """
    tele = obs.resolve(telemetry)
    fallbacks = []
    evaluator = power_evaluator

    # -- forced power failure: downgrade the evaluator up front --------
    if faults is not None and faults.fail_power:
        tele.fault("solver_fail", injected=True, solver="power",
                   method=evaluator)
        _count_injected("solver_fail")
        if evaluator != "closed_form":
            tele.fault("fallback", injected=True, solver="power",
                       to="closed_form", reason="injected")
            _count_fallback("power", "closed_form")
            fallbacks.append(f"{evaluator}->closed_form")
            evaluator = "closed_form"
        # closed form is the chain's terminal link: nothing to degrade
        # to — the injected failure is recorded and the solve proceeds.

    # -- matching with the greedy terminal fallback --------------------
    match = None
    if faults is not None and faults.fail_matching:
        tele.fault("solver_fail", injected=True, solver="matching")
        _count_injected("solver_fail")
        matching_reason = "injected"
    else:
        matching_reason = None
        try:
            match = matching_mod.swap_matching(
                sys, state.h, state.alpha, evaluator=evaluator,
                mode=(matching_mode if evaluator == "closed_form"
                      else "auto"),
                telemetry=tele)
        except Exception as e:
            if faults is None and not repair_infeasible:
                raise  # resilience layer off: a solver error is fatal
            matching_reason = type(e).__name__
            tele.fault("solver_fail", injected=False, solver="matching",
                       reason=matching_reason)
            if evaluator != "closed_form":
                # the CCP scorer may be the culprit: retry the matching
                # with the exact closed-form evaluator first
                tele.fault("fallback", injected=False, solver="power",
                           to="closed_form", reason=matching_reason)
                _count_fallback("power", "closed_form")
                fallbacks.append(f"{evaluator}->closed_form")
                evaluator = "closed_form"
                try:
                    match = matching_mod.swap_matching(
                        sys, state.h, state.alpha, evaluator=evaluator,
                        mode=matching_mode, telemetry=tele)
                except Exception as e2:  # pragma: no cover - double fail
                    matching_reason = type(e2).__name__

    if match is not None and match.feasible:
        rho, p = match.rho, match.p
        feasible, swaps, unmatched = True, match.swaps, match.unmatched
    elif match is not None:
        # naturally infeasible (but non-crashing) matching: with the
        # resilience layer active, try the greedy terminal fallback —
        # it often repairs feasibility (max-gain assignments need less
        # power).  Otherwise keep the infeasible decision so a plain
        # run stays bit-identical to the pre-fallback behavior.
        repaired = False
        if repair_infeasible:
            rho_g, p_g, ok_g, un_g = _greedy_fallback(
                sys, state, tele, injected=False, reason="infeasible")
            if ok_g:
                rho, p, feasible, swaps = rho_g, p_g, True, 0
                unmatched = un_g
                fallbacks.append("matching->greedy")
                repaired = True
        if not repaired:
            rho, p = match.rho, match.p
            feasible, swaps = False, match.swaps
            unmatched = match.unmatched
    else:
        rho, p, feasible, unmatched = _greedy_fallback(
            sys, state, tele,
            injected=bool(faults is not None and faults.fail_matching),
            reason=matching_reason or "unknown")
        swaps = 0
        fallbacks.append("matching->greedy")

    with tele.stage("selection"):
        delta = tele.block(selection_mod.solve_selection(
            sys, state.sigma, state.sigma_mask, method=selection_method,
            steps=gp_steps, step0=gp_step0,
            device_chunk=selection_chunk, telemetry=tele))
    return _finish(sys, rho, p, delta, state, feasible=feasible,
                   swaps=swaps, unmatched=unmatched,
                   fallbacks=tuple(fallbacks), telemetry=tele)


# --------------------------------------------------------------------------
# Baselines 1-4 (paper §VI-A).  Data: random half / all samples.
# RB: each device prefers its min- / max-gain RB (greedy, capacity Q).
# Power for all baselines comes from Algorithm 3's problem — we use the
# exact closed form (identical optimum).
# --------------------------------------------------------------------------

def _greedy_rb(sys: SystemParams, h: np.ndarray, alpha: np.ndarray,
               prefer_max: bool) -> np.ndarray:
    K, N, Q = sys.K, sys.N, sys.Q
    assign = np.full(K, -1, np.int64)
    slots = np.full(N, Q, np.int64)
    for k in np.flatnonzero(alpha > 0):
        prefs = np.argsort(-h[k] if prefer_max else h[k], kind="stable")
        for n in prefs:
            if slots[n] > 0:
                assign[k] = n
                slots[n] -= 1
                break
    rho = np.zeros((K, N), np.float32)
    m = assign >= 0
    rho[np.flatnonzero(m), assign[m]] = 1.0
    return rho


def _random_half(key: jax.Array, mask: Array) -> Array:
    """Random half of each device's samples (at least one)."""
    scores = jax.random.uniform(key, mask.shape) * mask
    n_valid = jnp.sum(mask, axis=1)
    want = jnp.maximum(jnp.floor(n_valid / 2.0), 1.0)
    ranks = jnp.argsort(jnp.argsort(-scores, axis=1), axis=1)
    return (ranks < want[:, None]).astype(jnp.float32) * mask


def baseline_scheme(sys: SystemParams, state: RoundState, index: int,
                    key: Optional[jax.Array] = None,
                    telemetry=None) -> RoundDecision:
    """Baselines 1-4: (half|all data) x (min|max gain RB)."""
    if index not in (1, 2, 3, 4):
        raise ValueError("baseline index must be 1..4")
    tele = obs.resolve(telemetry)
    half = index in (1, 2)
    prefer_max = index in (2, 4)
    with tele.stage("selection"):
        if half:
            assert key is not None, "baselines 1/2 need a PRNG key"
            delta = tele.block(_random_half(key, state.sigma_mask))
        else:
            delta = state.sigma_mask
    h = np.asarray(state.h)
    alpha = np.asarray(state.alpha)
    with tele.stage("matching"):
        rho = _greedy_rb(sys, h, alpha, prefer_max)
    with tele.stage("power"):
        p, _, ok = power_mod.allocate_power(
            sys, jnp.asarray(rho), state.h, state.alpha,
            method="closed_form", telemetry=tele)
        p = tele.block(p)
    return _finish(sys, rho, p, delta, state, feasible=ok, telemetry=tele)
