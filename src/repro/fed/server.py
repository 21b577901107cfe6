"""Server-side aggregation (paper §II-D).

eq. (19): g_hat = (1/|D̂|) sum_k (|D̂_k|/eps_k) * alpha_k * g_k.
Lemma 1: unbiased under alpha_k ~ Bernoulli(eps_k) (tested in
tests/test_fed.py by Monte-Carlo).

Robustness extensions (docs/robustness.md):

* ``eps_k == 0`` is guarded — such a device can never be available, so
  its IPW term is defined as 0 instead of the 0/0 NaN the raw formula
  produces (which would silently poison the whole aggregate);
* ``renormalize=True`` divides by the *realized* IPW mass of the
  surviving uploads instead of the planned ``|D̂|`` total.  When a
  device drops out *after* the allocation was fixed (mid-round fault,
  straggler timeout, quarantine), plain eq. (19) under-scales the step;
  renormalizing keeps g_hat a convex combination of the surviving local
  gradients, so its direction stays consistent with the survivor set.
  With no survivors the result is an all-zeros tree — callers should
  skip the optimizer update entirely when the realized mass is zero.

``FEELTrainer`` runs eq. (19) and the optimizer step as one compiled
program (``FEELTrainer._server_step``), and makes the skip decision
before it, on the host: every weight is 0 or at least ``|D̂_k|``, so
the mass is positive exactly when an upload of a device with
``eps_k > 0`` and ``|D̂_k| > 0`` survived.  ``ipw_mass`` computes the
same answer on the device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.types import SystemParams

Array = jax.Array


def ipw_weights(sys: SystemParams, alpha: Array) -> Array:
    """Unnormalized eq.-(19) weights |D̂_k|/eps_k * alpha_k, with the
    eps_k == 0 guard (weight 0, not NaN)."""
    eps_safe = jnp.where(sys.eps > 0, sys.eps, 1.0)
    live = (sys.eps > 0).astype(alpha.dtype)
    return (sys.D_hat / eps_safe) * alpha * live


def ipw_mass(sys: SystemParams, alpha: Array) -> float:
    """Total realized IPW weight of ``alpha``; 0.0 means no usable
    upload survived and the optimizer update should be skipped."""
    return float(jnp.sum(ipw_weights(sys, alpha)))


def aggregate_gradients(sys: SystemParams, local_grads, alpha: Array,
                        renormalize: bool = False):
    """``local_grads``: pytree with a leading K axis on every leaf."""
    w = ipw_weights(sys, alpha)
    if renormalize:
        denom = jnp.sum(w)
        w = jnp.where(denom > 0, w / jnp.where(denom > 0, denom, 1.0), 0.0)
    else:
        w = w / sys.D_hat_total

    def agg(leaf):
        return jnp.tensordot(w.astype(leaf.dtype), leaf, axes=(0, 0))

    return jax.tree.map(agg, local_grads)
