"""Faults planted in the program's timed path, to show that ``correct``
then comes out false (``calibrate.py`` reads them at the cell's own size
on the chip, ``tests/test_correct.py`` at a small size on the CPU).

Each is a context manager that replaces one function of the program
while it is open:

* ``unchanged``: the optimizer step returns the parameters as they were;
* ``half_batch``: every other upload is left out of the eq. (19) sum,
  the mean taken over the rest;
* ``altered_power``: each round's largest transmit power doubled where
  the power solve produces it.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp


@contextlib.contextmanager
def _replaced(module, name: str, make):
    old = getattr(module, name)
    setattr(module, name, make(old))
    try:
        yield
    finally:
        setattr(module, name, old)


def unchanged():
    import repro.optim
    return _replaced(repro.optim, "apply_updates",
                     lambda old: lambda params, updates: params)


def half_batch():
    from repro.fed import server

    def make(agg):
        def half(sys_, grads, alpha, renormalize=False):
            keep = (jnp.cumsum(alpha > 0) % 2 == 1) & (alpha > 0)
            w = server.ipw_weights(sys_, alpha) * keep
            total = jnp.sum(server.ipw_weights(sys_, alpha))
            # the mean over the kept half, scaled as eq. 19 scales the whole
            scale = total / jnp.maximum(jnp.sum(w), 1e-30)
            return agg(sys_, grads, jnp.where(keep, alpha * scale, 0.0),
                       renormalize=renormalize)
        return half

    return _replaced(server, "aggregate_gradients", make)


def altered_power():
    from repro.core import power

    def make(closed):
        def altered(*args):
            p, feasible = closed(*args)
            flat = p.reshape(-1)
            return (flat.at[jnp.argmax(flat)].multiply(2.0).reshape(p.shape),
                    feasible)
        return altered

    return _replaced(power, "closed_form_power", make)


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "altered_power": altered_power}
