"""Data selection (paper §V, Problem 4, Algorithms 4-5) + exact oracle.

Faithful pipeline
-----------------
1. *Continuous relaxation* (Alg. 4): gradient projection on (36) with a
   diminishing stepsize; the projection (37) onto
   {0 <= delta <= 1, sum_j delta_kj >= 1} decouples per device and is
   computed exactly (box clip, then capped-simplex projection via
   bisection when the clipped sum falls below 1).
2. *Binary recovery* (Alg. 5): the lambda-representation LP (39).
   Substituting b = delta, a = 1 - delta the LP objective becomes
       sum_kj [(1-delta†)^2 - (delta†)^2] delta_kj + const
     = sum_kj (1 - 2 delta†_kj) delta_kj + const,
   linear in delta over a box with the >=1-per-device constraint (a
   totally-unimodular system, as the paper's Lemma 4 argues), so the
   optimum is delta = 1[delta† > 1/2], repaired per device by selecting
   argmax_j delta†_kj when the threshold selects nothing.  This *is*
   the exact solution of (39) — no LP solver needed.

Exact oracle (beyond paper, DESIGN.md §4)
-----------------------------------------
The Problem-4 objective decouples per device into
    lambda * A_k * mean(sigma over selected) - (1-lambda) * q_k * m_k,
and for a fixed selection size m the optimum takes the m smallest
sigmas, so scanning prefix means of the sorted sigmas yields the global
optimum in O(J log J).  ``exact_selection`` is jit-able and is what the
large-model training path uses inside the jitted step.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from .. import obs
from ..obs import metrics as metrics_mod
from . import delta as delta_mod
from .types import SystemParams

Array = jax.Array
_BIG = 1e30


# --------------------------------------------------------------------------
# Projection (37): per-device {0<=d<=1, sum d >= 1} Euclidean projection.
# --------------------------------------------------------------------------

def _project_one(z: Array, mask: Array) -> Array:
    """Project a single device's vector; masked entries pinned to 0."""
    clipped = jnp.clip(z, 0.0, 1.0) * mask
    need_simplex = jnp.sum(clipped) < 1.0

    def capped_simplex(z):
        # find tau with sum(clip(z + tau, 0, 1) * mask) == 1 by bisection
        lo = 1.0 / jnp.maximum(jnp.sum(mask), 1.0) - jnp.max(
            jnp.where(mask > 0, z, -_BIG))
        lo = jnp.minimum(lo, 0.0) - 1.0
        hi = 1.0 - jnp.min(jnp.where(mask > 0, z, _BIG))
        hi = jnp.maximum(hi, 0.0) + 1.0

        def body(_, lohi):
            lo, hi = lohi
            mid = 0.5 * (lo + hi)
            s = jnp.sum(jnp.clip(z + mid, 0.0, 1.0) * mask)
            return jnp.where(s < 1.0, mid, lo), jnp.where(s < 1.0, hi, mid)

        lo, hi = jax.lax.fori_loop(0, 60, body, (lo, hi))
        tau = 0.5 * (lo + hi)
        return jnp.clip(z + tau, 0.0, 1.0) * mask

    return jnp.where(need_simplex, capped_simplex(z), clipped)


def project_feasible(z: Array, mask: Array) -> Array:
    """Projection (37), vmapped over devices. z, mask: (K, J)."""
    return jax.vmap(_project_one)(z, mask)


# --------------------------------------------------------------------------
# Algorithm 4: gradient projection on the continuous relaxation (36).
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("steps", "device_chunk"))
def gradient_projection(sys: SystemParams, sigma: Array, mask: Array,
                        steps: int = 400, step0: float = 0.3,
                        init: Array | None = None,
                        device_chunk: int = 0) -> Array:
    """Returns a stationary point delta† of (36) (continuous).

    step0 controls WHICH stationary point of the non-convex fractional
    objective the diminishing-step GP lands at: small step0 (~0.3)
    yields the threshold-like filter that keeps most samples and drops
    high-sigma outliers (the behaviour the paper's experiments rely
    on); large step0 (~5.0) chases the *global* optimum of Problem 4,
    which under the paper's lambda degenerates to ~1 sample/device and
    stalls training (EXPERIMENTS.md §Paper-validation).  Faithful
    either way — the paper does not specify the stepsize constant.

    ``device_chunk``: 0 (default) iterates the full (K, J) matrix in
    one fori_loop — the historical path.  A positive value runs the
    same iteration over device blocks of that size under one
    ``lax.scan`` (via ``lax.map``), bounding peak memory to
    O(device_chunk * J) at K=1000+ scale.  The objective (36) is
    separable per device (DESIGN.md §4: the A_k weights fold the only
    cross-device coupling, the |D̂| total, into per-device constants),
    so the chunked iterates equal the full-matrix ones device for
    device.
    """
    if init is None:
        init = 0.5 * mask
    if device_chunk and device_chunk < sigma.shape[0]:
        return _gp_chunked(sys, sigma, mask, steps, step0, init,
                           device_chunk)
    return _gp_rows(sigma, mask, init, sys.a_weights(), sys.q, sys.lam,
                    steps, step0)


def _gp_rows(sigma: Array, mask: Array, init: Array, A: Array, q: Array,
             lam: Array, steps: int, step0: float) -> Array:
    """Algorithm 4 on a block of devices (rows), given their A_k and q_k.

    The objective is the delta-dependent part of Problem 4
    (``delta_mod.selection_only_objective``) with the A_k weights, which
    carry the global |D̂| total, passed in: every operation is row-wise,
    so a block's iterates equal those rows of the full-matrix run.
    """

    def f(d):
        # C^com/C^cmp are constants w.r.t. delta; argmin is unchanged.
        dm = d * mask
        n_sel = jnp.sum(dm, axis=1)
        mean = (jnp.sum(dm * sigma, axis=1)
                / jnp.maximum(n_sel, delta_mod._EPSDIV))
        return lam * jnp.sum(A * mean) - (1.0 - lam) * jnp.sum(q * n_sel)

    grad_f = jax.grad(f)

    def body(v, d):
        step = step0 / (1.0 + v) ** 0.6  # sum a = inf, sum a^2 < inf
        g = grad_f(d)
        g = jnp.where(jnp.isfinite(g), g, 0.0)
        # per-device normalization: the Delta term scales like A_k/m_k,
        # which varies by orders of magnitude across devices; scale-free
        # steps keep every device's subproblem moving at the same rate.
        norm = jnp.max(jnp.abs(g), axis=1, keepdims=True)
        g = g / jnp.maximum(norm, 1e-12)
        return project_feasible(d - step * g, mask)

    return jax.lax.fori_loop(0, steps, body, init * mask)


def _gp_chunked(sys: SystemParams, sigma: Array, mask: Array, steps: int,
                step0: float, init: Array, chunk: int) -> Array:
    """Algorithm 4 over device blocks of ``_gp_rows`` under one
    ``lax.map``; the A_k weights are computed once for all devices."""
    K, J = sigma.shape
    pad = (-K) % chunk

    def padk(x):
        # padded devices have mask=0 rows: the projection pins them to 0
        # and their objective terms vanish, so they never affect the loop
        return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))

    n_blocks = (K + pad) // chunk

    def blocks(x):
        return padk(x).reshape((n_blocks, chunk) + x.shape[1:])

    def run_block(args):
        sig, msk, ini, A_b, q_b = args
        return _gp_rows(sig, msk, ini, A_b, q_b, sys.lam, steps, step0)

    out = jax.lax.map(run_block, (blocks(sigma), blocks(mask), blocks(init),
                                  blocks(sys.a_weights()), blocks(sys.q)))
    return out.reshape(n_blocks * chunk, J)[:K]


# --------------------------------------------------------------------------
# Algorithm 5: binary recovery via the lambda-representation LP (39).
# --------------------------------------------------------------------------

def binary_recovery(delta_cont: Array, mask: Array) -> Array:
    """Exact solution of LP (39): threshold at 1/2 with >=1 repair."""
    sel = (delta_cont > 0.5).astype(jnp.float32) * mask
    none = jnp.sum(sel, axis=1) < 1.0
    best = jnp.argmax(jnp.where(mask > 0, delta_cont, -_BIG), axis=1)
    repair = jax.nn.one_hot(best, delta_cont.shape[1], dtype=jnp.float32)
    return jnp.where(none[:, None], jnp.maximum(sel, repair * mask), sel)


def faithful_selection(sys: SystemParams, sigma: Array, mask: Array,
                       steps: int = 400, step0: float = 0.3,
                       device_chunk: int = 0) -> Array:
    """Algorithms 4 + 5 end to end (the paper's data-selection solver)."""
    d_cont = gradient_projection(sys, sigma, mask, steps=steps,
                                 step0=step0, device_chunk=device_chunk)
    return binary_recovery(d_cont, mask)


# --------------------------------------------------------------------------
# Exact per-device prefix-scan solver (beyond paper; also the jit-able
# selector used inside large-model train steps).
# --------------------------------------------------------------------------

@jax.jit
def exact_selection(sys: SystemParams, sigma: Array, mask: Array) -> Array:
    """Global optimum of Problem 4 in O(K J log J)."""
    A = sys.a_weights()  # (K,)
    big_sigma = jnp.where(mask > 0, sigma, _BIG)
    order = jnp.argsort(big_sigma, axis=1)
    sorted_sigma = jnp.take_along_axis(big_sigma, order, axis=1)
    m = jnp.arange(1, sigma.shape[1] + 1, dtype=jnp.float32)
    prefix_mean = jnp.cumsum(jnp.where(sorted_sigma < _BIG, sorted_sigma,
                                       0.0), axis=1) / m
    valid = m[None, :] <= jnp.sum(mask, axis=1, keepdims=True)
    obj = (sys.lam * A[:, None] * prefix_mean
           - (1.0 - sys.lam) * sys.q[:, None] * m[None, :])
    obj = jnp.where(valid, obj, _BIG)
    best_m = jnp.argmin(obj, axis=1) + 1  # (K,) optimal selection size
    ranks = jnp.argsort(order, axis=1)  # rank of each sample in sorted order
    return (ranks < best_m[:, None]).astype(jnp.float32) * mask


def solve_selection(sys: SystemParams, sigma: Array, mask: Array,
                    method: str = "faithful", steps: int = 400,
                    step0: float = 0.3, device_chunk: int = 0,
                    telemetry=None) -> Array:
    tele = obs.resolve(telemetry)
    reg = metrics_mod.get_default()
    if method == "faithful":
        # the two Alg. 4/5 phases as child spans of the selection stage;
        # same computation as faithful_selection (block is a no-op sync)
        with tele.span("selection.gp", steps=steps):
            d_cont = tele.block(gradient_projection(
                sys, sigma, mask, steps=steps, step0=step0,
                device_chunk=device_chunk))
        with tele.span("selection.recover"):
            out = tele.block(binary_recovery(d_cont, mask))
        gp_steps = steps
    elif method == "exact":
        with tele.span("selection.exact"):
            out = tele.block(exact_selection(sys, sigma, mask))
        gp_steps = 0
    else:
        raise ValueError(f"unknown selection method: {method}")
    if tele.enabled or reg.enabled:
        with tele.span("telemetry"):
            # one host sync, shared by the trace event and the metrics
            n_selected = int(jnp.sum(out))
            if tele.enabled:
                tele.solver("selection", method=method, gp_steps=gp_steps,
                            n_selected=n_selected)
            if reg.enabled:
                reg.counter("feel_selection_calls_total",
                            "data-selection solves by method").inc(
                                1, method=method)
                reg.counter("feel_selection_gp_steps_total",
                            "gradient-projection (Alg. 4) steps").inc(
                                gp_steps)
                reg.counter("feel_selection_selected_total",
                            "samples selected across rounds").inc(n_selected)
    return out
