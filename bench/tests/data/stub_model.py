"""A stub model module for the tests: multinomial logistic regression on
vectors, with its own part of the reference. ``test_discovery.py`` drops
it into a copied checkout's ``bench/models/`` to show that a model is
added by files alone.

Device k holds ``per_device`` vectors of class ``k % num_classes``, each
its class mean plus unit Gaussian noise in ``dim`` dimensions. The
penultimate features are the inputs themselves, so the last-layer sigma
is the whole gradient's.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np

import inputs


def samples(cfg, seed):
    rng = np.random.default_rng(seed)
    classes, dim = cfg["num_classes"], cfg["dim"]
    means = rng.normal(0.0, 1.0, (classes, dim))
    out = inputs.Samples([], [], [])
    for k in range(cfg["K"]):
        true = np.full(cfg["per_device"], k % classes, np.int32)
        noise = rng.normal(0.0, 1.0, (true.size, dim))
        out.x.append((means[true] + noise).astype(np.float32))
        out.true.append(true)
        out.labels.append(inputs.mislabel(true, cfg["mislabel_prop"],
                                          classes, seed + 1000 + k))
    return out


def dataset(cfg, data):
    from repro.data.federated import FederatedDataset

    return FederatedDataset(
        device_images=data.x, device_labels=data.labels,
        device_true=data.true,
        test_images=np.zeros((0, cfg["dim"]), np.float32),
        test_labels=np.zeros((0,), np.int32),
        num_classes=cfg["num_classes"])


def init_params(cfg, seed):
    @jax.jit
    def build(key):
        w = jax.random.normal(key, (cfg["dim"], cfg["num_classes"]))
        return {"w": 0.1 * w, "b": jnp.zeros((cfg["num_classes"],))}

    return build(jax.random.PRNGKey(seed))


def _features(params, x):
    return x, x @ params["w"] + params["b"]


def _loss(params, x, y):
    logp = jax.nn.log_softmax(_features(params, x)[1])
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def _accuracy(params, x, y):
    return float(jnp.mean(jnp.argmax(_features(params, x)[1], -1) == y))


def program(cfg):
    return types.SimpleNamespace(
        features=_features, apply=lambda p, x: _features(p, x)[1],
        loss_fn=_loss, accuracy=_accuracy)


def round_flops(cfg, n_selected):
    fwd = 2 * cfg["dim"] * cfg["num_classes"]
    return fwd * (cfg["K"] * cfg["d_hat"] + 3 * int(n_selected))


def forward(params, x, precision):
    return x, jnp.dot(x, params["w"], precision=precision) + params["b"]


def sigma(params, x, y, precision):
    h, logits = forward(params, x, precision)
    r = jax.nn.softmax(logits) - jax.nn.one_hot(y, logits.shape[-1],
                                                dtype=logits.dtype)
    return jnp.sum(r * r, axis=-1) * (jnp.sum(h * h, axis=-1) + 1)


def weighted_loss(params, x, y, w, precision):
    _, logits = forward(params, x, precision)
    logp = jax.nn.log_softmax(logits)
    return -jnp.sum(w * jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0])
