"""A decoder trained through the FEEL round on labelled token sequences.

A sample is S token ids ``x`` and one int label.  The label enters as a
control token at position 0: the input is ``[label, x_0..x_{S-2}]`` and
the targets are ``x_0..x_{S-1}``.  A sample's loss is its mean
next-token cross-entropy over the S positions, and its score sigma is
the squared norm of the output layer's gradient of that loss
(``fed.client.sequence_sigma``, from ``head_residuals``).

The model is ``models.model.make_forward`` of an ``ArchConfig``; an
expert layer runs as the held-expert share (``moe.held_expert_ffn``),
all experts held where the config names no share, and every pass also
returns the held experts' slots (layers, B, n_held).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .config import ArchConfig
from .model import make_forward

Array = jax.Array


class ControlTokenLM:
    """The model object ``fed.FEELTrainer`` takes for token sequences."""

    #: samples are token sequences: sigma and the gradients are per
    #: sequence (``fed.rounds`` picks its sequence programs from this)
    sequence = True

    def __init__(self, cfg: ArchConfig):
        if cfg.n_experts and cfg.experts_held is None:
            cfg = dataclasses.replace(cfg, experts_held=(0, cfg.n_experts))
        self.cfg = cfg
        self._forward = make_forward(cfg, with_slots=True)

    @staticmethod
    def inputs(x: Array, labels: Array) -> Array:
        """The label as the control token, then x without its last id."""
        return jnp.concatenate(
            [labels[:, None].astype(x.dtype), x[:, :-1]], axis=1)

    def _pass(self, params, x: Array, labels: Array):
        logits, hidden, _, slots = self._forward(
            params, {"tokens": self.inputs(x, labels)})
        if slots is None:
            slots = jnp.zeros((0, x.shape[0], 0), jnp.int32)
        return logits, hidden, slots

    def head_residuals(self, params, x: Array, labels: Array):
        """``(h, p - y, slots)``: the final-norm output (B, S, d), the
        logit residuals against the targets (B, S, V), the slots."""
        logits, hidden, slots = self._pass(params, x, labels)
        p = jax.nn.softmax(logits, axis=-1)
        y = jax.nn.one_hot(x, logits.shape[-1], dtype=p.dtype)
        return hidden.astype(jnp.float32), p - y, slots

    def per_sample_loss(self, params, x: Array, labels: Array):
        """``(loss (B,), slots)``: mean next-token CE per sequence."""
        logits, _, slots = self._pass(params, x, labels)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(logp, x[..., None], axis=-1)[..., 0]
        return jnp.mean(ce, axis=-1), slots

    def loss_fn(self, params, x: Array, labels: Array) -> Array:
        """Mean loss over the batch."""
        return jnp.mean(self.per_sample_loss(params, x, labels)[0])

    def accuracy(self, params, x: Array, labels: Array) -> Array:
        """Share of positions whose target is the most likely id."""
        logits, _, _ = self._pass(params, x, labels)
        return jnp.mean(jnp.argmax(logits, axis=-1) == x)
