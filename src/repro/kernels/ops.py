"""Jit'd public wrappers around the Pallas kernels.

``interpret=None`` (the default) compiles on a TPU backend and runs the
Pallas interpreter elsewhere (``kernels.resolve_interpret``); the tests
validate interpret mode against kernels/ref.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention as _flash
from .gradnorm import gradnorm_sigma as _sigma
from .gradnorm import rownorm2 as _rownorm2
from .lru_scan import lru_scan as _lru_scan


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array,
                         causal: bool = True,
                         interpret: bool | None = None) -> jax.Array:
    """q,k,v: (B, S, H, d) MHA layout -> (B, S, H, d).

    GQA callers should broadcast kv heads first (the kernel is
    head-merged; the jnp zoo path stays GQA-native instead)."""
    B, S, H, d = q.shape
    fold = lambda x: jnp.moveaxis(x, 2, 1).reshape(B * H, S, d)
    out = _flash(fold(q), fold(k), fold(v), causal=causal,
                 interpret=interpret)
    return jnp.moveaxis(out.reshape(B, H, S, d), 1, 2)


rownorm2 = _rownorm2
gradnorm_sigma = _sigma
lru_scan = _lru_scan


def sigma_from_head(h: jax.Array, logits: jax.Array, labels: jax.Array,
                    interpret: bool | None = None) -> jax.Array:
    """Exact last-layer sigma from features + logits (fused path).

    h: (N, d) penultimate features; logits: (N, V); labels: (N,).
    """
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    y = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    return gradnorm_sigma(h, p - y, interpret=interpret)
