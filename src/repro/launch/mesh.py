"""Production meshes.

Single pod: (16, 16) = ("data", "model") — 256 TPU v5e chips.
Multi-pod: (2, 16, 16) = ("pod", "data", "model") — 512 chips.

Defined as a FUNCTION so importing this module never touches jax
device state (the dry-run launcher's ``main`` sets XLA_FLAGS before
jax initializes its backends).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return jax.make_mesh(shape, axes, devices=devices[:n])


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh for CPU integration tests (honors available devices)."""
    import numpy as np
    devs = np.asarray(jax.devices()[:data * model]).reshape(data, model)
    return jax.sharding.Mesh(devs, ("data", "model"))


def data_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """The batch-sharding axes: ("pod","data") multi-pod, else ("data",)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def data_size(mesh: jax.sharding.Mesh) -> int:
    return math.prod(mesh.shape[a] for a in data_axes(mesh))


def model_size(mesh: jax.sharding.Mesh) -> int:
    return mesh.shape.get("model", 1)
