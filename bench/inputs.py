"""What a benchmark run feeds the trainer, made from ``--seed``.

The benchmark keeps its own copy of the input recipe so that a change
to the program cannot change what it is measured on:

* seeded MNIST-shaped images: one smooth prototype per class (fixed,
  the class definition) plus a shift of up to 2 pixels and Gaussian
  pixel noise, clipped to [0, 1];
* the paper's non-IID placement: device k holds ``per_device`` images
  of class ``k % classes``, a ``mislabel_prop`` share of them with a
  wrong label drawn uniformly from the other classes;
* the CNN's weights, He-normal, made on the device in one jitted call.

At data seed 0 the images and the placement equal those of
``repro.fed.paper_setup`` (checked by hand, see PERF.md).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Placement:
    """Per-device shards as the trainer's data object needs them."""

    images: List[np.ndarray]   # K x (per_device, side, side) float32
    labels: List[np.ndarray]   # labels as seen (some corrupted), int32
    true: List[np.ndarray]     # ground-truth labels, int32


def seeds(seed: int) -> Dict[str, int]:
    """Independent 31-bit sub-seeds for data, weights and rounds."""
    words = np.random.SeedSequence(int(seed)).generate_state(3, np.uint32)
    return {name: int(w) & 0x7FFFFFFF
            for name, w in zip(("data", "weights", "rounds"),
                               words)}


def _prototypes(classes: int, side: int) -> np.ndarray:
    rng = np.random.default_rng(991_000 + side)
    yy, xx = np.mgrid[0:side, 0:side].astype(np.float64) / side
    out = []
    for _ in range(classes):
        img = np.zeros((side, side))
        for _ in range(4):
            cx, cy = rng.uniform(0.2, 0.8, 2)
            sx, sy = rng.uniform(0.08, 0.25, 2)
            amp = rng.uniform(0.5, 1.0) * rng.choice([-1.0, 1.0])
            img += amp * np.exp(-((xx - cx) ** 2 / (2 * sx ** 2)
                                  + (yy - cy) ** 2 / (2 * sy ** 2)))
        img = (img - img.min()) / max(img.max() - img.min(), 1e-9)
        out.append(img)
    return np.stack(out).astype(np.float32)


def images(n: int, side: int, classes: int, noise: float, seed: int):
    """``(images, labels)``: n seeded class-conditional images."""
    rng = np.random.default_rng(seed)
    protos = _prototypes(classes, side)
    labels = rng.integers(0, classes, n).astype(np.int32)
    base = protos[labels]
    shifts = rng.integers(-2, 3, (n, 2))
    out = np.empty_like(base)
    for i in range(n):
        out[i] = np.roll(base[i], tuple(shifts[i]), axis=(0, 1))
    out += rng.normal(0, noise, out.shape).astype(np.float32)
    return np.clip(out, 0.0, 1.0), labels


def _mislabel(labels: np.ndarray, share: float, classes: int,
              seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    n_bad = int(round(share * labels.shape[0]))
    idx = rng.choice(labels.shape[0], size=n_bad, replace=False)
    out = labels.copy()
    if n_bad:
        out[idx] = (labels[idx] + rng.integers(1, classes, n_bad)) % classes
    return out


def placement(cfg: dict, seed: int) -> Placement:
    """The configuration's train set placed one class per device."""
    classes = cfg["num_classes"]
    imgs, labels = images(cfg["train_images"], cfg["side"], classes,
                          cfg["image_noise"], seed)
    rng = np.random.default_rng(seed)
    out = Placement([], [], [])
    for k in range(cfg["K"]):
        pool = np.flatnonzero(labels == k % classes)
        idx = rng.choice(pool, size=min(cfg["per_device"], pool.size),
                         replace=False)
        true = labels[idx]
        out.images.append(imgs[idx])
        out.true.append(true)
        out.labels.append(_mislabel(true, cfg["mislabel_prop"], classes,
                                    seed + 1000 + k))
    return out


def param_shapes(cfg: dict) -> Dict[str, Dict[str, tuple]]:
    """The CNN's leaves: 5x5 convs (HWIO), dense layers (in, out)."""
    c1, c2 = cfg["conv_channels"]
    f1, f2 = cfg["fc_dims"]
    ks = cfg["conv_kernel"]
    flat = (cfg["side"] // 4) ** 2 * c2
    return {
        "conv1": {"w": (ks, ks, 1, c1), "b": (c1,)},
        "conv2": {"w": (ks, ks, c1, c2), "b": (c2,)},
        "fc1": {"w": (flat, f1), "b": (f1,)},
        "fc2": {"w": (f1, f2), "b": (f2,)},
        "out": {"w": (f2, cfg["num_classes"]), "b": (cfg["num_classes"],)},
    }


def make_params(cfg: dict, seed: int) -> dict:
    """He-normal weights and zero biases, in one jitted call."""
    shapes = param_shapes(cfg)
    dtype = jnp.dtype(cfg["dtype"])

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(shapes))
        out = {}
        for k, (name, leaves) in zip(keys, shapes.items()):
            w = leaves["w"]
            fan_in = int(np.prod(w[:-1]))
            out[name] = {
                "w": (jax.random.normal(k, w, dtype)
                      * np.sqrt(2.0 / fan_in).astype(dtype)),
                "b": jnp.zeros(leaves["b"], dtype)}
        return out

    return build(jax.random.PRNGKey(seed))
