"""Pallas TPU kernels with pure-jnp references (``ref.py``).

Every kernel takes ``interpret: bool | None = None``.  ``None`` is
resolved outside the kernel's jit by ``resolve_interpret``: the kernel
compiles through Mosaic whenever the default backend is a TPU, and runs
the Pallas interpreter only on other backends (the CPU test suite).
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> interpret only where the default backend is not a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
