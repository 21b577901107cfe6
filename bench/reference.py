"""Plain reference of the FEEL round, written from the paper.

It imports nothing of the program. Given a configuration, a traffic
mix, the run's sub-seeds and the model module's part of the reference
(``bench/models/<model>.py``: forward pass, sigma, weighted loss), it
follows the first rounds of training in straightforward ``jax.numpy``
and NumPy:

1. each device draws |D̂_k| of its samples (the trainer's NumPy stream,
   seeded by the rounds sub-seed) and scores them with the model's
   sigma;
2. channel gains h ~ Exp * MEAN_GAIN and availability alpha ~ Bern(eps)
   come from the trainer's JAX key stream;
3. the decision: Alg. 2 swap matching (greedy best-gain start, pairwise
   swaps then moves into open slots, a move taken when it lowers the
   upload cost by more than 1e-12) scored with the exact per-RB SIC
   powers, then those powers; Alg. 4 gradient projection and Alg. 5
   thresholding for the selection;
4. eq. (4) local gradients and the eq. (19) inverse-propensity sum, as
   one gradient of the model's weighted loss (eq. 19 is linear in the
   uploads), over the selection that ``selections`` names where it is
   given (the program's, as a served model's reference is fed the
   served tokens), else over its own;
5. an Adam step (skipped when no upload survived).

``dtype``/``precision`` choose the arithmetic: float32 at ``HIGHEST``
is the reference; bfloat16 at default precision is the control.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: mean of the exponential channel gain, the program's fixed channel model
MEAN_GAIN = 1e-5
#: Adam's published defaults (Kingma & Ba), which the program's Adam uses
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
#: devices per block of the forward and backward passes, so that the
#: reference fits beside what the run has left on the device
BLOCK = 32


@dataclasses.dataclass
class RoundOut:
    sigma: np.ndarray      # (K, J) float64
    rho: np.ndarray        # (K, N) 0/1
    p: np.ndarray          # (K, N) float64
    delta: np.ndarray      # (K, J) 0/1, its own selection
    n_uploaded: int
    skipped: bool
    g_hat: Optional[dict]  # the aggregated gradient of an applied step


@dataclasses.dataclass
class Trajectory:
    rounds: List[RoundOut]
    params0: dict
    params: dict           # after the last round


# -------------------------------------------------------------- decision

def _sic_powers(h_rb, c, p_max, gamma, N0):
    """Exact powers of one RB's members, weakest decoded last: each
    meets gamma against noise plus every weaker member's signal."""
    order = np.argsort(h_rb, kind="stable")
    p = np.zeros(h_rb.size)
    interference = N0
    ok = True
    for i in order:
        p[i] = gamma * interference / max(h_rb[i], 1e-30)
        interference += p[i] * h_rb[i]
        ok &= p[i] <= p_max[i] * (1 + 1e-9)
    return p, ok


class _Costs:
    def __init__(self, h, c, p_max, gamma, N0, T):
        self.h, self.c, self.p_max = h, c, p_max
        self.gamma, self.N0, self.T = gamma, N0, T

    def rb(self, n, members):
        if members.size == 0:
            return 0.0
        p, ok = _sic_powers(self.h[members, n], self.c[members],
                            self.p_max[members], self.gamma, self.N0)
        return float(np.sum(self.c[members] * p) * self.T) if ok \
            else float("inf")


def swap_matching(h, alpha, Q, costs: _Costs, max_sweeps=50):
    """(K,) RB of each device, -1 where none (Alg. 2)."""
    K, N = h.shape
    avail = np.flatnonzero(alpha > 0)
    assign = np.full(K, -1, np.int64)
    slots = np.full(N, Q, np.int64)
    for k in avail[np.argsort(-h[avail].max(axis=1), kind="stable")]:
        open_rbs = np.flatnonzero(slots > 0)
        if open_rbs.size == 0:
            break
        n = open_rbs[np.argmax(h[k, open_rbs])]
        assign[k] = n
        slots[n] -= 1
    members = [np.flatnonzero(assign == n) for n in range(N)]
    cost = np.array([costs.rb(n, members[n]) for n in range(N)])

    def attempt(u, n_to, partner):
        n_from = assign[u]
        m_from = members[n_from][members[n_from] != u]
        m_to = members[n_to]
        if partner is not None:
            m_to = m_to[m_to != partner]
            m_from = np.append(m_from, partner)
        m_to = np.append(m_to, u)
        c_from, c_to = costs.rb(n_from, m_from), costs.rb(n_to, m_to)
        if (c_from + c_to) - (cost[n_from] + cost[n_to]) < -1e-12:
            members[n_from], members[n_to] = m_from, m_to
            cost[n_from], cost[n_to] = c_from, c_to
            if partner is not None:
                assign[partner] = n_from
            assign[u] = n_to
            return True
        return False

    for _ in range(max_sweeps):
        improved = False
        for u in avail:
            if assign[u] < 0:
                continue
            for k in avail:
                if k > u and assign[k] >= 0 and assign[k] != assign[u]:
                    improved |= attempt(u, assign[k], k)
            for n in range(N):
                if n != assign[u] and members[n].size < Q:
                    improved |= attempt(u, n, None)
        if not improved:
            break
    return assign


def powers(assign, h, alpha, costs: _Costs):
    """(K, N) SIC powers of the assignment, and whether every available
    device is matched within its p_max."""
    K, N = h.shape
    p = np.zeros((K, N))
    ok = True
    for n in range(N):
        m = np.flatnonzero((assign == n) & (alpha > 0))
        if m.size:
            pm, ok_n = _sic_powers(h[m, n], costs.c[m], costs.p_max[m],
                                   costs.gamma, costs.N0)
            p[m, n] = pm
            ok &= ok_n
    avail = alpha > 0
    return p, ok and bool(np.all(assign[avail] >= 0))


def _project_row(z, iters=60):
    """Euclidean projection onto {0 <= d <= 1, sum d >= 1}."""
    clipped = jnp.clip(z, 0, 1)

    def simplex(z):
        def body(_, lohi):
            lo, hi = lohi
            mid = (lo + hi) / 2
            low = jnp.sum(jnp.clip(z + mid, 0, 1)) < 1
            return jnp.where(low, mid, lo), jnp.where(low, hi, mid)

        lo, hi = jax.lax.fori_loop(0, iters, body,
                                   (-jnp.max(z), 1 - jnp.min(z)))
        return jnp.clip(z + (lo + hi) / 2, 0, 1)

    return jnp.where(jnp.sum(clipped) < 1, simplex(z), clipped)


def select(sigma, A, q, lam, steps, step0):
    """Alg. 4 (diminishing-step gradient projection on the relaxation,
    gradients normalized per device) and Alg. 5 (threshold at 1/2, at
    least one sample per device)."""
    dt = sigma.dtype
    lam = jnp.asarray(lam, dt)

    def body(v, d):
        n = jnp.sum(d, axis=1, keepdims=True)
        mean = jnp.sum(d * sigma, axis=1, keepdims=True) / n
        g = lam * A[:, None] * (sigma - mean) / n - (1 - lam) * q[:, None]
        g = g / jnp.maximum(jnp.max(jnp.abs(g), axis=1, keepdims=True),
                            jnp.asarray(1e-12, dt))
        step = jnp.asarray(step0, dt) / (1 + v.astype(dt)) ** 0.6
        return jax.vmap(_project_row)(d - step * g)

    d = jax.lax.fori_loop(0, steps, body, jnp.full(sigma.shape, 0.5, dt))
    sel = d > 0.5
    best = jax.nn.one_hot(jnp.argmax(d, axis=1), d.shape[1], dtype=bool)
    return jnp.where(jnp.any(sel, axis=1, keepdims=True), sel, best)


# ------------------------------------------------------------------ round

class Reference:
    """The reference (or, in bfloat16, the control) for one cell;
    ``model`` is the cell's model module."""

    def __init__(self, cfg: dict, traffic: dict, sub: dict, model,
                 dtype=jnp.float32, precision=HIGHEST):
        if cfg["optimizer"] != "adam" or traffic["scheme"] != "proposed":
            raise ValueError("the reference follows Adam under the "
                             "proposed scheme only")
        self.cfg, self.sub = cfg, sub
        self.dtype, self.precision = dtype, precision
        K = cfg["K"]
        k1 = np.arange(1, K + 1)
        odd = k1 % 2 == 1
        self.c = np.where(odd, cfg["c_odd"], cfg["c_even"])
        self.q = np.where(odd, cfg["q_odd"], cfg["q_even"])
        self.eps = np.where(odd, traffic["eps_odd"], traffic["eps_even"])
        self.p_max = np.full(K, cfg["p_max_w"])
        d = float(cfg["d_hat"])
        self.d = np.full(K, d)
        self.A = d * d / self.eps + d * (d * K - d)
        self.gamma = 2.0 ** (cfg["L_bits"] / (cfg["B_hz"] * cfg["T_s"])) - 1
        self._sigma = jax.jit(
            lambda p, x, y: model.sigma(p, x, y, precision))
        # x: (k, J, ...) and y, w: (k, J), flattened to one batch
        self._grad = jax.jit(jax.grad(
            lambda p, x, y, w: model.weighted_loss(
                p, x.reshape((-1,) + x.shape[2:]), y.reshape(-1),
                w.reshape(-1), precision)))
        self._select = jax.jit(select, static_argnames=("steps", "step0"))

    def _cast(self, tree):
        return jax.tree.map(lambda a: jnp.asarray(a, self.dtype), tree)

    def _inputs(self, x):
        """Floating inputs in the reference's dtype; ids as they are."""
        if np.issubdtype(x.dtype, np.floating):
            return jnp.asarray(x, self.dtype)
        return jnp.asarray(x)

    def _blocks(self, K):
        return [(a, min(a + BLOCK, K)) for a in range(0, K, BLOCK)]

    def _draw_inputs(self, key):
        """The round's (h, alpha) from the trainer's key stream."""
        cfg = self.cfg
        key, kh, ka, _ = jax.random.split(key, 4)
        h = jax.random.exponential(kh, (cfg["K"], cfg["N"])) * MEAN_GAIN
        u = jax.random.uniform(ka, (cfg["K"],))
        h = np.asarray(jnp.asarray(h, self.dtype), np.float64)
        alpha = np.asarray(u < jnp.asarray(self.eps, jnp.float32),
                           np.float64)
        return key, h, alpha

    def run(self, data, params0, rounds: int = 3,
            selections=None) -> Trajectory:
        """``data``: an ``inputs.Samples``; ``selections``: per round, the
        (K, J) selection whose samples the gradient is taken over;
        ``None`` takes the reference's own."""
        cfg = self.cfg
        K, J = cfg["K"], cfg["d_hat"]
        rng = np.random.default_rng(self.sub["rounds"])
        key = jax.random.PRNGKey(self.sub["rounds"])
        params = self._cast(params0)
        p0 = params
        adam_m = jax.tree.map(jnp.zeros_like, params)
        adam_v = jax.tree.map(jnp.zeros_like, params)
        count = 0
        costs = _Costs(None, self.c, self.p_max, self.gamma, cfg["N0_w"],
                       cfg["T_s"])
        out = []
        for i in range(rounds):
            idx = [rng.choice(len(x), size=min(J, len(x)), replace=False)
                   for x in data.x]
            x = np.stack([data.x[k][idx[k]] for k in range(K)])
            y = np.stack([data.labels[k][idx[k]] for k in range(K)])
            x_d = self._inputs(x)
            y_d = jnp.asarray(y)
            sigma = jnp.concatenate([
                self._sigma(params, x_d[a:b].reshape((-1,) + x.shape[2:]),
                            y_d[a:b].reshape(-1)).reshape(b - a, J)
                for a, b in self._blocks(K)])
            key, h, alpha = self._draw_inputs(key)
            costs.h = h
            assign = swap_matching(h, alpha, cfg["Q"], costs)
            delta = np.asarray(self._select(
                sigma, jnp.asarray(self.A, self.dtype),
                jnp.asarray(self.q, self.dtype), cfg["lam"],
                steps=cfg["gp_steps"], step0=cfg["gp_step0"]))
            p, _ = powers(assign, h, alpha, costs)
            rho = np.zeros((K, cfg["N"]))
            rho[np.flatnonzero(assign >= 0), assign[assign >= 0]] = 1
            uploads = (alpha > 0) & (assign >= 0)
            used = delta if selections is None else (
                np.asarray(selections[i]) > 0.5)
            skipped = not np.any(uploads)
            g_hat = None
            if not skipped:
                w_dev = self.d / self.eps * uploads / self.d.sum()
                w = w_dev[:, None] * used / np.maximum(
                    used.sum(axis=1, keepdims=True), 1e-9)
                w_d = jnp.asarray(w, self.dtype)
                for a, b in self._blocks(K):
                    if not np.any(w[a:b]):
                        continue
                    g = self._grad(params, x_d[a:b], y_d[a:b], w_d[a:b])
                    g_hat = g if g_hat is None else jax.tree.map(
                        jnp.add, g_hat, g)
                count += 1
                params, adam_m, adam_v = self._adam(params, g_hat, adam_m,
                                                    adam_v, count)
            out.append(RoundOut(
                sigma=np.asarray(sigma, np.float64), rho=rho, p=p,
                delta=np.asarray(delta, bool),
                n_uploaded=int(uploads.sum()), skipped=skipped,
                g_hat=None if g_hat is None else jax.tree.map(
                    lambda a: np.asarray(a, np.float64), g_hat)))
        return Trajectory(rounds=out, params0=p0, params=params)

    def _adam(self, params, g, m, v, t):
        dt, b1, b2 = self.dtype, ADAM_B1, ADAM_B2
        m = jax.tree.map(lambda m, g: (b1 * m + (1 - b1) * g).astype(dt),
                         m, g)
        v = jax.tree.map(lambda v, g: (b2 * v + (1 - b2) * g * g).astype(dt),
                         v, g)

        def step(p, m, v):
            mh = m / (1 - b1 ** t)
            vh = v / (1 - b2 ** t)
            return (p - self.cfg["lr"] * mh / (jnp.sqrt(vh) + ADAM_EPS)
                    ).astype(dt)

        return jax.tree.map(step, params, m, v), m, v
