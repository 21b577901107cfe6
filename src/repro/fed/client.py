"""Device-side computation (paper §II-B).

* ``per_sample_sigma`` — sigma_{k,j} = ||g_{k,j}||^2 for every sample of
  the sampled sub-dataset D̂_k.  Two modes:
    - "full": vmap(grad) over samples — the literal paper quantity;
    - "last_layer": exact gradient-norm of the *output layer only*:
      for a linear head  logits = h W + b  with CE loss,
        dL/dW_j = h_j^T (p_j - y_j),  dL/db_j = (p_j - y_j)
      so ||g_j||^2 = ||p_j - y_j||^2 * (||h_j||^2 + 1).
      O(batch * d) instead of O(batch * |params|); this is the scorer
      the large-model path uses (see kernels/gradnorm for the fused
      TPU version).
* ``local_gradient`` — eq. (4): gradient of the loss averaged over the
  *selected* subset M_k (selection mask delta).
* ``sequence_sigma`` / ``sequence_local_gradient`` — the same for token
  sequences (``models.control_lm``): a sample's loss is the mean over
  its positions, and sigma is the exact squared norm of the (bias-free)
  output layer's gradient of that loss.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

Array = jax.Array


def _head_residuals(params, images: Array, labels: Array,
                    features_fn: Callable) -> tuple[Array, Array]:
    """(features h, logit residuals p - y) of the linear head."""
    h, logits = features_fn(params, images)
    p = jax.nn.softmax(logits)
    y = jax.nn.one_hot(labels, logits.shape[-1], dtype=p.dtype)
    return h, p - y


def per_sample_sigma(params, images: Array, labels: Array,
                     features_fn: Callable, method: str = "last_layer",
                     loss_fn: Callable | None = None) -> Array:
    """sigma for each sample: (B,)."""
    if method == "last_layer":
        h, d = _head_residuals(params, images, labels, features_fn)
        return jnp.sum(d * d, axis=-1) * (jnp.sum(h * h, axis=-1) + 1.0)
    if method == "full":
        assert loss_fn is not None

        def one(img, lab):
            g = jax.grad(loss_fn)(params, img[None], lab[None])
            return sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g))

        return jax.vmap(one)(images, labels)
    raise ValueError(f"unknown sigma method: {method}")


def batched_sigma(params, images: Array, labels: Array,
                  features_fn: Callable) -> Array:
    """All-device "last_layer" sigma in one fused pass: (K, D̂).

    Flattens the (K, D̂, ...) round batch to one (K*D̂, ...) forward
    pass and scores it with the tiled row-norm kernel
    (``kernels.gradnorm.gradnorm_sigma``) instead of K per-device
    elementwise reductions — the batched sigma path of the scale
    benchmark (``fed.rounds`` selects it for
    ``sigma_method="last_layer_kernel"``).  Equal to the vmapped
    "last_layer" scores up to float32 reduction order.
    """
    from ..kernels import gradnorm as gradnorm_mod

    K, D = labels.shape[:2]
    flat = images.reshape((K * D,) + images.shape[2:])
    h, d = _head_residuals(params, flat, labels.reshape(-1), features_fn)
    return gradnorm_mod.gradnorm_sigma(h, d).reshape(K, D)


def local_gradient(params, images: Array, labels: Array, delta: Array,
                   loss_fn: Callable):
    """eq. (4): grad of (sum_j delta_j l_j) / (sum_j delta_j)."""

    def weighted_loss(p):
        logits_loss = _per_sample_loss(p, images, labels, loss_fn)
        return (jnp.sum(delta * logits_loss)
                / jnp.maximum(jnp.sum(delta), 1e-9))

    return jax.grad(weighted_loss)(params)


def _per_sample_loss(params, images, labels, loss_fn):
    """Vectorized per-sample losses via a batched loss_fn contract:
    loss_fn(params, images, labels) returns the mean loss, so we call
    it per sample through vmap."""
    return jax.vmap(lambda img, lab: loss_fn(params, img[None],
                                             lab[None]))(images, labels)


def sequence_sigma(h: Array, r: Array) -> Array:
    """sigma of each sequence, ``||(1/S) H^T R||_F^2`` for features
    ``h`` (B, S, d) and logit residuals ``r`` (B, S, V), in Gram form:
    ``sum_{t,t'} (h_t . h_t') (r_t . r_t') / S^2`` (O(S^2 (d + V))
    instead of O(S d V))."""
    S = h.shape[1]
    gh = jnp.einsum("bsd,btd->bst", h, h)
    gr = jnp.einsum("bsv,btv->bst", r, r)
    return jnp.sum(gh * gr, axis=(1, 2)) / (S * S)


def sequence_local_gradient(params, x: Array, labels: Array, delta: Array,
                            model):
    """eq. (4) over token sequences: ``(grad, slots)``, the gradient of
    the delta-weighted mean of the sequences' losses and the held
    experts' slots of each sequence (layers, B, n_held)."""

    def weighted_loss(p):
        loss, slots = model.per_sample_loss(p, x, labels)
        return (jnp.sum(delta * loss)
                / jnp.maximum(jnp.sum(delta), 1e-9)), slots

    return jax.grad(weighted_loss, has_aux=True)(params)
