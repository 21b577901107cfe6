"""Swap-matching RB assignment (paper §IV-A, Algorithm 2).

Devices and RBs form a bipartite matching (Definition 1): each
*available* device gets exactly one RB, each RB carries at most Q
devices.  Starting from an initial matching, pairs of devices exchange
RBs whenever the exchange strictly lowers the net cost (evaluated with
the power allocator of §IV-B under the candidate assignment); the loop
terminates because the cost is bounded below and strictly decreases.

Implementation notes
--------------------
* The cost of a matching is separable per RB (each device occupies one
  RB), so a swap between RBs n1, n2 only requires re-solving those two
  RBs — this is what makes the O(U^2) swap sweep cheap.
* ``evaluator="closed_form"`` (default) scores candidate assignments
  with the exact per-RB solution; ``evaluator="ccp"`` uses the
  paper-faithful Algorithm 3 (identical decisions up to solver
  tolerance — the closed form *is* the optimum of (28); verified in
  tests/test_power.py).
* In addition to pairwise swaps we allow moves into *open slots*
  (a swap with a virtual empty device), mirroring the open-house swaps
  of the housing-assignment model [37] the paper builds on.  Disable
  with ``allow_moves=False`` for the strictest reading of Alg. 2.
* Two sweep implementations share the same accept-improvement
  semantics (see docs/solvers.md): the historical ``scalar`` loop
  scores one candidate move per Python call, while ``batched`` scores
  *every* remaining candidate move of a device in one vectorized
  closed-form evaluation (``_BatchScorer``) and applies the first
  improving one in the same enumeration order — the decisions match
  the scalar path move for move, but a K=256 round runs ~K fewer
  Python-level cost evaluations per sweep.  ``mode="auto"`` (default)
  switches to the batched sweep at ``AUTO_BATCH_MIN`` available
  devices; the equivalence is enforced by
  tests/test_solver_equivalence.py.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from .. import obs
from ..obs import metrics as metrics_mod
from . import power as power_mod
from .types import SystemParams

_INF = float("inf")

#: ``mode="auto"`` picks the batched sweep at/above this many available
#: devices; below it the scalar sweep has comparable latency and stays
#: the byte-for-byte historical path.
AUTO_BATCH_MIN = 32


@dataclasses.dataclass
class MatchingResult:
    assign: np.ndarray    # (K,) RB index per device, -1 = unmatched
    rho: np.ndarray       # (K, N) dense assignment
    p: np.ndarray         # (K, N) powers
    cost: float           # C^com (upload cost); add C^cmp for Problem-3 obj
    swaps: int
    sweeps: int
    feasible: bool
    #: available devices left without an RB (partial matching: more
    #: available devices than N*Q slots).  Empty when every available
    #: device was matched; the round can still proceed — unmatched
    #: devices simply cannot upload (their alpha-weighted IPW term is
    #: handled by the resilience layer in ``repro.fed.rounds``).
    unmatched: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int64))
    #: sweep implementation that produced this result ("scalar" or
    #: "batched"); decisions are mode-independent, the field exists so
    #: benchmarks and tests can confirm which path ran.
    mode: str = "scalar"


def _rb_cost(sys: SystemParams, members: np.ndarray, h: np.ndarray,
             c: np.ndarray, p_max: np.ndarray, gamma: float,
             N0: float, T: float) -> tuple[float, np.ndarray]:
    """Exact min upload cost of one RB given its member devices.

    ``members`` are device ids; ``h`` their gains on this RB.  Returns
    (cost, powers) with cost=inf when any power exceeds its p_max.
    """
    if members.size == 0:
        return 0.0, np.zeros((0,))
    order = np.argsort(h, kind="stable")  # ascending: weakest first
    p = np.zeros(members.size)
    cum_i = N0
    for r, idx in enumerate(order):
        p[idx] = gamma * cum_i / max(h[idx], 1e-30)
        cum_i += p[idx] * h[idx]
        if p[idx] > p_max[idx] * (1 + 1e-9):
            return _INF, p
    return float(np.sum(c * p) * T), p


class _Scorer:
    """Caches per-RB costs for the current assignment."""

    def __init__(self, sys: SystemParams, h: np.ndarray, alpha: np.ndarray,
                 evaluator: str):
        self.sys = sys
        self.h = h
        self.alpha = alpha
        self.evaluator = evaluator
        self.gamma = float(power_mod.snr_target(sys))
        self.c = np.asarray(sys.c)
        self.p_max = np.asarray(sys.p_max)
        self.N0 = float(sys.N0)
        self.T = float(sys.T)
        self.evals = 0  # candidate per-RB power solves (telemetry)

    def rb_cost(self, n: int, members: np.ndarray) -> float:
        self.evals += 1
        if self.evaluator == "closed_form":
            cost, _ = _rb_cost(self.sys, members, self.h[members, n],
                               self.c[members], self.p_max[members],
                               self.gamma, self.N0, self.T)
            return cost
        # paper-faithful: per-RB CCP (Algorithm 3) on a masked assignment
        import jax.numpy as jnp
        K, N = self.h.shape
        rho = np.zeros((K, N), np.float32)
        rho[members, n] = 1.0
        _, cost, ok = power_mod.allocate_power(
            self.sys, jnp.asarray(rho), jnp.asarray(self.h),
            jnp.asarray(self.alpha), method="ccp", telemetry=obs.NULL)
        return cost if ok else _INF


class _BatchScorer:
    """Vectorized counterpart of ``_Scorer``.

    Scores a *batch* of candidate RB member sets in one numpy
    evaluation of the exact closed-form per-RB power solution — the
    same arithmetic as ``_rb_cost`` applied row-wise in the same op
    order (so each row reproduces the scalar cost bit-for-bit for
    member counts below numpy's pairwise-sum blocking) — instead of
    one Python call per candidate.
    """

    def __init__(self, sys: SystemParams, h: np.ndarray):
        self.gamma = float(power_mod.snr_target(sys))
        self.h = h
        self.c = np.asarray(sys.c, np.float64)
        self.p_max = np.asarray(sys.p_max, np.float64)
        self.N0 = float(sys.N0)
        self.T = float(sys.T)
        self.evals = 0  # candidate per-RB power solves (telemetry)

    def rb_costs(self, ids: np.ndarray, rbs: np.ndarray) -> np.ndarray:
        """Exact min upload cost of each candidate member set.

        ``ids``: (C, Qp) member device ids with -1 padding *after* the
        real members (the scalar member-array order, so stable-sort
        tie-breaking matches ``_rb_cost``); ``rbs``: (C,) the RB each
        row is priced on.  Returns (C,) float64 costs, inf where any
        member power exceeds its p_max (same tolerance as the scalar).
        """
        C, Qp = ids.shape
        self.evals += C
        act = ids >= 0
        safe = np.where(act, ids, 0)
        h = np.where(act, self.h[safe, rbs[:, None]], _INF)
        pmax = np.where(act, self.p_max[safe], _INF)
        order = np.argsort(h, axis=1, kind="stable")  # weakest first
        h_s = np.take_along_axis(h, order, axis=1)
        act_s = np.take_along_axis(act, order, axis=1)
        pmax_s = np.take_along_axis(pmax, order, axis=1)
        p_s = np.zeros((C, Qp))
        cum = np.full(C, self.N0)
        feas = np.ones(C, bool)
        for r in range(Qp):  # SIC accumulation over <= Q rank levels
            a = act_s[:, r]
            hr = np.where(a, h_s[:, r], 0.0)  # pads carry h=inf (sort key)
            pr = np.where(a, self.gamma * cum / np.maximum(hr, 1e-30), 0.0)
            p_s[:, r] = pr
            cum = cum + np.where(a, pr * hr, 0.0)
            feas &= ~(a & (pr > pmax_s[:, r] * (1 + 1e-9)))
        p = np.zeros_like(p_s)
        np.put_along_axis(p, order, p_s, axis=1)  # back to member order
        cost = np.sum(np.where(act, self.c[safe], 0.0) * p, axis=1) * self.T
        return np.where(feas, cost, _INF)


def _batched_sweeps(sys: SystemParams, scorer: _BatchScorer,
                    avail: np.ndarray, assign: np.ndarray,
                    M: np.ndarray, counts: np.ndarray,
                    rb_costs: np.ndarray, allow_moves: bool,
                    max_sweeps: int, tele) -> tuple[int, int]:
    """The batched sweep loop; mutates ``assign``/``M``/``counts``/
    ``rb_costs`` in place and returns (swaps, sweeps).

    Replays the scalar acceptance order exactly: for each available
    device u (same order) every remaining candidate move — pairwise
    swap partners in ``avail`` order, then open-slot moves by RB index
    — is scored in ONE vectorized closed-form evaluation, and the
    first improving candidate in that enumeration order is applied;
    the remaining suffix is then re-scored under the updated
    assignment.  Decisions therefore match the scalar sweep move for
    move; only the Python-level evaluation count changes.
    """
    N, Q = sys.N, sys.Q
    Qp = M.shape[1]
    P = avail.size
    pos_sw = np.arange(P)
    pos_mv = P + np.arange(N)

    swaps = 0
    sweeps = 0
    improved = True
    while improved and sweeps < max_sweeps:
        improved = False
        sweeps += 1
        sweep_span = tele.span("matching.sweep", sweep=sweeps)
        sweep_span.__enter__()
        for u in avail:
            if assign[u] < 0:
                continue
            cursor = 0
            while True:
                n_u = assign[u]
                # -- remaining candidates, vectorized filters ----------
                swap_ok = ((avail > u) & (assign[avail] >= 0)
                           & (assign[avail] != n_u) & (pos_sw >= cursor))
                sw_pos = np.flatnonzero(swap_ok)
                partners = avail[sw_pos]
                if allow_moves:
                    mv_ok = ((np.arange(N) != n_u) & (counts < Q)
                             & (pos_mv >= cursor))
                    mv_ns = np.flatnonzero(mv_ok)
                else:
                    mv_ns = np.zeros(0, np.int64)
                C1, C2 = partners.size, mv_ns.size
                C = C1 + C2
                if C == 0:
                    break
                # -- candidate member sets (scalar member-array order) -
                base = M[n_u]
                base = base[(base != u) & (base >= 0)]  # minus the mover
                s0 = base.size
                rows_from = np.full((C, Qp), -1, np.int64)
                rows_from[:, :s0] = base
                rows_to = np.full((C, Qp), -1, np.int64)
                to_rbs = np.empty(C, np.int64)
                if C1:
                    rows_from[:C1, s0] = partners        # j joins n_u
                    n_js = assign[partners]
                    to_rbs[:C1] = n_js
                    ids0 = M[n_js]                       # (C1, Qp)
                    keep0 = (ids0 >= 0) & (ids0 != partners[:, None])
                    ordr = np.argsort(~keep0, axis=1, kind="stable")
                    comp = np.take_along_axis(
                        np.where(keep0, ids0, -1), ordr, axis=1)
                    comp[np.arange(C1), keep0.sum(axis=1)] = u  # u joins
                    rows_to[:C1] = comp
                if C2:
                    to_rbs[C1:] = mv_ns
                    rows_to[C1:] = M[mv_ns]
                    rows_to[C1 + np.arange(C2), counts[mv_ns]] = u
                # -- one vectorized closed-form evaluation -------------
                costs = scorer.rb_costs(
                    np.concatenate([rows_from, rows_to]),
                    np.concatenate([np.full(C, n_u, np.int64), to_rbs]))
                c_from, c_to = costs[:C], costs[C:]
                d = (c_from + c_to) - (rb_costs[n_u] + rb_costs[to_rbs])
                hits = np.flatnonzero(d < -1e-12)
                if hits.size == 0:
                    break
                i = int(hits[0])
                n_to = int(to_rbs[i])
                # -- apply it (the winning rows are already built) -----
                M[n_u] = rows_from[i]
                M[n_to] = rows_to[i]
                rb_costs[n_u] = c_from[i]
                rb_costs[n_to] = c_to[i]
                if i < C1:              # pairwise swap with partner j
                    j = int(partners[i])
                    assign[u], assign[j] = n_to, n_u
                    cursor = int(sw_pos[i]) + 1
                else:                   # open-slot move
                    counts[n_u] -= 1
                    counts[n_to] += 1
                    assign[u] = n_to
                    cursor = P + n_to + 1
                swaps += 1
                improved = True
        sweep_span.__exit__(None, None, None)
    return swaps, sweeps


def swap_matching(sys: SystemParams, h, alpha, evaluator: str = "closed_form",
                  allow_moves: bool = True, max_sweeps: int = 50,
                  rng: Optional[np.random.Generator] = None,
                  telemetry: Optional[obs.NullTelemetry] = None,
                  mode: str = "auto") -> MatchingResult:
    """Algorithm 2. ``h``: (K,N) gains; ``alpha``: (K,) availability.

    ``mode``: ``"scalar"`` is the historical per-candidate Python
    loop; ``"batched"`` scores all remaining candidate moves of a
    device in one vectorized closed-form evaluation (same decisions,
    see ``_batched_sweeps``); ``"auto"`` (default) picks batched for
    the closed_form evaluator with at least ``AUTO_BATCH_MIN``
    available devices, scalar otherwise.  The CCP evaluator cannot be
    vectorized per candidate and always runs scalar.
    """
    tele = obs.resolve(telemetry)
    if mode not in ("auto", "scalar", "batched"):
        raise ValueError(f"unknown matching mode: {mode!r}")
    if mode == "batched" and evaluator != "closed_form":
        raise ValueError("mode='batched' requires evaluator='closed_form' "
                         "(per-candidate CCP solves cannot be vectorized); "
                         "use mode='scalar' or mode='auto'")
    # the inputs and the system's constants come to the host here
    with tele.span("matching.prep"):
        h = np.asarray(h, np.float64)
        alpha = np.asarray(alpha, np.float64)
        K, N, Q = sys.K, sys.N, sys.Q
        avail = np.flatnonzero(alpha > 0)
        use_batched = (mode == "batched"
                       or (mode == "auto" and evaluator == "closed_form"
                           and avail.size >= AUTO_BATCH_MIN))
        mode_used = "batched" if use_batched else "scalar"
        scorer = (_BatchScorer(sys, h) if use_batched
                  else _Scorer(sys, h, alpha, evaluator))

    stage = tele.stage("matching")
    stage.__enter__()
    # ---- initial matching Psi_0: greedy best-gain with capacity ----
    with tele.span("matching.init"):
        assign = np.full(K, -1, np.int64)
        slots = np.full(N, Q, np.int64)
        order = avail[np.argsort(-h[avail].max(axis=1), kind="stable")]
        for k in order:
            open_rbs = np.flatnonzero(slots > 0)
            if open_rbs.size == 0:
                # More available devices than N*Q slots: Definition 1
                # cannot be satisfied, so the matching is *partial* — the
                # remaining devices stay at assign == -1 and are reported
                # in ``MatchingResult.unmatched`` (and counted in the
                # ``feel_matching_unmatched_total`` /
                # ``feel_solver_infeasible_total`` metrics below) instead
                # of being silently skipped.  The round still proceeds
                # with the devices that did get an RB.
                break
            n = open_rbs[np.argmax(h[k, open_rbs])]
            assign[k] = n
            slots[n] -= 1

        if use_batched:
            Qp = max(Q, 1)
            M = np.full((N, Qp), -1, np.int64)
            counts = np.zeros(N, np.int64)
            for n in range(N):
                ids = np.flatnonzero(assign == n)
                M[n, :ids.size] = ids
                counts[n] = ids.size
            rb_costs = scorer.rb_costs(M, np.arange(N))
        else:
            members = [np.flatnonzero(assign == n) for n in range(N)]
            rb_costs = np.array([scorer.rb_cost(n, members[n])
                                 for n in range(N)])

    if use_batched:
        swaps, sweeps = _batched_sweeps(sys, scorer, avail, assign, M,
                                        counts, rb_costs, allow_moves,
                                        max_sweeps, tele)
    else:
        def try_reassign(k: int, n_from: int, n_to: int, j: Optional[int]):
            """Cost delta of moving k from n_from to n_to (swapping with j)."""
            m_from = members[n_from][members[n_from] != k]
            m_to = members[n_to]
            if j is not None:
                m_to = m_to[m_to != j]
                m_from = np.append(m_from, j)
            m_to = np.append(m_to, k)
            c_from = scorer.rb_cost(n_from, m_from)
            c_to = scorer.rb_cost(n_to, m_to)
            new = c_from + c_to
            old = rb_costs[n_from] + rb_costs[n_to]
            return new - old, (m_from, m_to, c_from, c_to)

        swaps = 0
        sweeps = 0
        improved = True
        while improved and sweeps < max_sweeps:
            improved = False
            sweeps += 1
            # one child span per sweep: a regression in sweep count (or one
            # pathologically slow sweep) is attributable from the trace
            sweep_span = tele.span("matching.sweep", sweep=sweeps)
            sweep_span.__enter__()
            for u in avail:
                if assign[u] < 0:
                    continue
                # pairwise swaps (the paper's swap operation)
                for k in avail:
                    if k <= u or assign[k] < 0 or assign[k] == assign[u]:
                        continue
                    d, upd = try_reassign(u, assign[u], assign[k], k)
                    if d < -1e-12:
                        n_u, n_k = assign[u], assign[k]
                        members[n_u], members[n_k] = upd[0], upd[1]
                        rb_costs[n_u], rb_costs[n_k] = upd[2], upd[3]
                        assign[u], assign[k] = n_k, n_u
                        swaps += 1
                        improved = True
                # open-slot moves (housing-model open houses)
                if allow_moves:
                    for n in range(N):
                        if n == assign[u] or members[n].size >= Q:
                            continue
                        d, upd = try_reassign(u, assign[u], n, None)
                        if d < -1e-12:
                            n_u = assign[u]
                            members[n_u], members[n] = upd[0], upd[1]
                            rb_costs[n_u], rb_costs[n] = upd[2], upd[3]
                            assign[u] = n
                            swaps += 1
                            improved = True
            sweep_span.__exit__(None, None, None)

    rho = np.zeros((K, N), np.float32)
    matched = assign >= 0
    rho[np.flatnonzero(matched), assign[matched]] = 1.0
    stage.__exit__(None, None, None)

    # final powers under the chosen evaluator's assignment
    import jax.numpy as jnp
    with tele.stage("power"):
        p, cost, ok = power_mod.allocate_power(
            sys, jnp.asarray(rho), jnp.asarray(h, np.float32),
            jnp.asarray(alpha, np.float32), method="closed_form",
            telemetry=tele)
        p = tele.block(p)
    all_matched = bool(np.all(assign[avail] >= 0)) if avail.size else True
    feasible = ok and all_matched and np.isfinite(cost)
    unmatched_ids = (avail[assign[avail] < 0] if avail.size
                     else np.zeros(0, np.int64))
    unmatched = int(unmatched_ids.size)
    tele.solver("matching", swaps=swaps, sweeps=sweeps,
                rb_evals=scorer.evals, unmatched=unmatched,
                feasible=bool(feasible), mode=mode_used)
    if unmatched:
        tele.fault("partial_matching", injected=False,
                   unmatched=[int(k) for k in unmatched_ids])
    reg = metrics_mod.get_default()
    if reg.enabled:
        reg.counter("feel_matching_calls_total",
                    "swap-matching (Alg. 2) invocations").inc()
        reg.counter("feel_matching_swaps_total",
                    "accepted swap/move operations").inc(swaps)
        reg.counter("feel_matching_sweeps_total",
                    "swap sweeps over available devices").inc(sweeps)
        reg.counter("feel_matching_rb_evals_total",
                    "candidate per-RB power evaluations").inc(scorer.evals)
        reg.counter("feel_matching_unmatched_total",
                    "available devices left without an RB").inc(unmatched)
        if not feasible:
            reg.counter("feel_solver_infeasible_total",
                        "infeasible solver outcomes by solver").inc(
                            1, solver="matching")
    return MatchingResult(assign=assign, rho=rho, p=np.asarray(p),
                          cost=cost, swaps=swaps, sweeps=sweeps,
                          feasible=feasible, unmatched=unmatched_ids,
                          mode=mode_used)
