"""The paper's CNN (arXiv:2407.02888 §VI-A) as the benchmark runs it.

A configuration names its model module with ``"model"``; this one is the
default. A model module gives the harness everything that depends on
the model:

* ``samples(cfg, seed)``: the run's data, an ``inputs.Samples``;
* ``dataset(cfg, samples)``: the program's data object built from them;
* ``init_params(cfg, seed)``: the initial weights, on the device;
* ``program(cfg)``: the model object ``FEELTrainer`` takes;
* ``round_flops(cfg, n_selected)``: one round's model FLOPs;
* the reference's part, in plain ``jax.numpy`` at the ``precision``
  passed in and importing nothing of the program: ``forward(params, x,
  precision) -> (h, logits)``, ``sigma(params, x, y, precision)`` per
  sample, and ``weighted_loss(params, x, y, w, precision)``, the
  w-weighted sum of per-sample losses over a flat batch.

The data recipe is the benchmark's own copy, so that a change to the
program cannot change what it is measured on:

* seeded MNIST-shaped images: one smooth prototype per class (fixed,
  the class definition) plus a shift of up to 2 pixels and Gaussian
  pixel noise, clipped to [0, 1];
* the paper's non-IID placement: device k holds ``per_device`` images
  of class ``k % classes``, a ``mislabel_prop`` share of them with a
  wrong label drawn uniformly from the other classes;
* the weights, He-normal, made on the device in one jitted call.

At data seed 0 the images and the placement equal those of
``repro.fed.paper_setup`` (checked by hand, see PERF.md).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

import flops
import inputs

round_flops = flops.round_flops


# ------------------------------------------------------------------ data

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


def samples(cfg: dict, seed: int) -> inputs.Samples:
    """The configuration's train set placed one class per device."""
    classes = cfg["num_classes"]
    imgs, labels = images(cfg["train_images"], cfg["side"], classes,
                          cfg["image_noise"], seed)
    rng = np.random.default_rng(seed)
    out = inputs.Samples([], [], [])
    for k in range(cfg["K"]):
        pool = np.flatnonzero(labels == k % classes)
        idx = rng.choice(pool, size=min(cfg["per_device"], pool.size),
                         replace=False)
        true = labels[idx]
        out.x.append(imgs[idx])
        out.true.append(true)
        out.labels.append(inputs.mislabel(true, cfg["mislabel_prop"],
                                          classes, seed + 1000 + k))
    return out


def dataset(cfg: dict, data: inputs.Samples):
    """The program's ``FederatedDataset`` of the run's images (no test
    set: the window does not evaluate)."""
    from repro.data.federated import FederatedDataset

    side = cfg["side"]
    return FederatedDataset(
        device_images=data.x, device_labels=data.labels,
        device_true=data.true,
        test_images=np.zeros((0, side, side), np.float32),
        test_labels=np.zeros((0,), np.int32),
        num_classes=cfg["num_classes"])


def program(cfg: dict):
    """The program's CNN module."""
    from repro.models import cnn

    return cnn


# --------------------------------------------------------------- weights

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


def init_params(cfg: dict, seed: int) -> dict:
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


# ------------------------------------------------------ reference's part

def forward(params, x, precision):
    """(penultimate h, logits) of the CNN for images x: (B, S, S)."""
    def conv(x, w, b):
        y = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=precision)
        return y + b

    def pool(x):  # 2x2 max-pooling, stride 2
        b, hh, ww, c = x.shape
        return x.reshape(b, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))

    x = x[..., None]
    x = pool(jax.nn.relu(conv(x, params["conv1"]["w"], params["conv1"]["b"])))
    x = pool(jax.nn.relu(conv(x, params["conv2"]["w"], params["conv2"]["b"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(x, params["fc1"]["w"], precision=precision)
                    + params["fc1"]["b"])
    h = jax.nn.relu(jnp.dot(x, params["fc2"]["w"], precision=precision)
                    + params["fc2"]["b"])
    return h, jnp.dot(h, params["out"]["w"], precision=precision) \
        + params["out"]["b"]


def sigma(params, x, y, precision):
    """The squared gradient norm of the output layer, per sample:
    ``|p - y|^2 (|h|^2 + 1)``."""
    h, logits = forward(params, x, precision)
    r = jax.nn.softmax(logits) - jax.nn.one_hot(y, logits.shape[-1],
                                                dtype=logits.dtype)
    return jnp.sum(r * r, axis=-1) * (jnp.sum(h * h, axis=-1) + 1)


def weighted_loss(params, x, y, w, precision):
    """sum_i w_i * CE_i over a flat batch."""
    _, logits = forward(params, x, precision)
    logp = jax.nn.log_softmax(logits)
    ce = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
    return jnp.sum(w * ce)
