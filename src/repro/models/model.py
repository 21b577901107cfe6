"""Model-level API: embeddings/heads per modality, losses, and the
train / prefill / decode step functions the launcher jits.

Modalities (DESIGN.md §3):
  text   tokens (B,S) int32 -> embedding table
  vlm    precomputed patch/text embeddings (B,S,d) + M-RoPE position
         ids (B,3,S) — the ViT frontend is the allowed stub
  audio  EnCodec token grid (B, n_codebooks, S) -> summed codebook
         embeddings; n_codebooks parallel LM heads (MusicGen)

The FEEL integration (`make_train_step(..., feel=...)`) implements the
paper's technique inside the jitted step: per-example gradient-norm
scores sigma (exact last-layer row-norm product, kernels/gradnorm),
the exact Problem-4 selector per client, and eq.-(19) inverse-
propensity weighting with Bernoulli availability — the mesh "data"
axis plays the role of the K federated devices.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import selection as sel_mod
from ..core.types import SystemParams
from ..optim import GradientTransformation, apply_updates
from .config import ArchConfig
from .layers import init_dense
from .shard_ctx import constrain
from .transformer import apply_decoder, init_cache, init_decoder

Array = jax.Array


# ---------------------------------------------------------------- params

def init_model(key, cfg: ArchConfig) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    dtype = cfg.act_dtype
    params: dict = {"decoder": init_decoder(k1, cfg)}
    if cfg.modality == "text":
        params["embed"] = (jax.random.normal(
            k2, (cfg.vocab, cfg.d_model), jnp.float32)
            * cfg.d_model ** -0.5).astype(dtype)
        if not cfg.tie_embeddings:
            params["lm_head"] = init_dense(k3, cfg.d_model, cfg.vocab, dtype)
    elif cfg.modality == "vlm":
        params["lm_head"] = init_dense(k3, cfg.d_model, cfg.vocab, dtype)
    elif cfg.modality == "audio":
        params["embed"] = (jax.random.normal(
            k2, (cfg.n_codebooks, cfg.vocab, cfg.d_model), jnp.float32)
            * cfg.d_model ** -0.5).astype(dtype)
        params["lm_head"] = init_dense(k3, cfg.d_model,
                                       cfg.n_codebooks * cfg.vocab, dtype)
    else:
        raise ValueError(cfg.modality)
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ------------------------------------------------------------ embeddings

def embed_input(cfg: ArchConfig, params: dict, batch: Dict[str, Array]
                ) -> Array:
    if cfg.modality == "text":
        return jnp.take(params["embed"], batch["tokens"], axis=0
                        ).astype(cfg.act_dtype)
    if cfg.modality == "vlm":
        return batch["embeds"].astype(cfg.act_dtype)
    if cfg.modality == "audio":
        # sum of per-codebook embeddings: tokens (B, C, S)
        toks = batch["tokens"]
        embs = jnp.take(params["embed"][0], toks[:, 0], axis=0)
        for c in range(1, cfg.n_codebooks):
            embs = embs + jnp.take(params["embed"][c], toks[:, c], axis=0)
        return embs.astype(cfg.act_dtype)
    raise ValueError(cfg.modality)


def _positions(cfg: ArchConfig, batch: Dict[str, Array], B: int, S: int,
               offset: Array | int = 0) -> Array:
    if cfg.modality == "vlm":
        return batch["positions"]  # (B, 3, S)
    pos = offset + jnp.arange(S)
    return jnp.broadcast_to(pos[None, :], (B, S))


def unembed(cfg: ArchConfig, params: dict, hidden: Array) -> Array:
    if cfg.modality == "text" and cfg.tie_embeddings:
        logits = hidden.astype(jnp.float32) @ params["embed"].T.astype(
            jnp.float32)
    else:
        logits = (hidden @ params["lm_head"]).astype(jnp.float32)
    if cfg.modality == "audio":
        B, S, _ = hidden.shape
        logits = logits.reshape(B, S, cfg.n_codebooks, cfg.vocab)
    return constrain(logits, "logits_btv")


# ------------------------------------------------------------------ loss

def per_example_loss(cfg: ArchConfig, logits: Array, batch
                     ) -> Tuple[Array, Array]:
    """Mean CE per example: ((B,), valid-token counts)."""
    labels = batch["labels"]
    if cfg.modality == "audio":
        # labels (B, C, S) -> align with logits (B, S, C, V)
        labels = jnp.swapaxes(labels, 1, 2)
    valid = (labels >= 0)
    safe = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    tok_ll = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    tok_loss = -tok_ll * valid
    axes = tuple(range(1, tok_loss.ndim))
    n = jnp.maximum(jnp.sum(valid, axis=axes), 1)
    return jnp.sum(tok_loss, axis=axes) / n, n


def sigma_scores(cfg: ArchConfig, hidden: Array, logits: Array,
                 batch) -> Array:
    """Per-example last-layer gradient-norm^2 proxy (GraNd-style):
    sum_t ||softmax - onehot||^2 * (||h_t||^2 + 1).  Exact per token;
    the cross-token outer-product terms of the full-sequence last-layer
    norm are dropped (documented adaptation — O(S) not O(S^2))."""
    labels = batch["labels"]
    if cfg.modality == "audio":
        labels = jnp.swapaxes(labels, 1, 2)
    valid = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    p = jax.nn.softmax(logits, axis=-1)
    py = jnp.take_along_axis(p, safe[..., None], axis=-1)[..., 0]
    dnorm2 = jnp.sum(p * p, axis=-1) - 2.0 * py + 1.0  # ||p - y||^2
    if cfg.modality == "audio":
        dnorm2 = jnp.sum(dnorm2 * valid, axis=-1)  # sum codebooks
        valid = valid[..., 0]
    else:
        dnorm2 = dnorm2 * valid
    h2 = jnp.sum(jnp.square(hidden.astype(jnp.float32)), axis=-1) + 1.0
    axes = tuple(range(1, dnorm2.ndim))
    return jnp.sum(dnorm2 * h2, axis=axes) / jnp.maximum(
        jnp.sum(valid, axis=axes), 1.0)


# ----------------------------------------------------------- FEEL wiring

@dataclasses.dataclass(frozen=True)
class FeelIntegration:
    """Paper technique inside the train step.

    ``n_clients`` data-parallel groups act as the K federated devices;
    ``eps`` is each client's availability probability (eq. 19 weights);
    selection is the exact Problem-4 solver over per-example sigmas.
    """
    n_clients: int
    eps: float = 0.8
    lam: float = 1e-3
    q_reward: float = 0.002

    def system(self, per_client: int) -> SystemParams:
        K = self.n_clients
        return SystemParams(
            K=K, N=max(K // 2, 1), Q=2,
            B=jnp.asarray(2e6), T=jnp.asarray(0.5), L=jnp.asarray(1e6),
            N0=jnp.asarray(1e-9), p_max=jnp.full((K,), 10.0),
            q=jnp.full((K,), self.q_reward), c=jnp.full((K,), 5.0),
            f=jnp.full((K,), 1e9), F=jnp.full((K,), 20.0),
            kappa=jnp.asarray(1e-28), eps=jnp.full((K,), self.eps),
            D_hat=jnp.full((K,), float(per_client)),
            lam=jnp.asarray(self.lam))


# ------------------------------------------------------------ step fns

def make_forward(cfg: ArchConfig, with_slots: bool = False):
    """forward(params, batch) -> (logits, hidden, aux); with
    ``with_slots`` also the held-expert layers' slots (layers, B,
    n_held)."""
    def forward(params, batch):
        x = embed_input(cfg, params, batch)
        B, S = x.shape[:2]
        pos = _positions(cfg, batch, B, S)
        out = apply_decoder(cfg, params["decoder"], x, pos, mode="train",
                            with_slots=with_slots)
        hidden, aux = out[0], out[2]
        return (unembed(cfg, params, hidden), hidden, aux) + out[3:]

    return forward


def make_train_step(cfg: ArchConfig, opt: GradientTransformation,
                    feel: Optional[FeelIntegration] = None):
    """Returns train_step(params, opt_state, batch) -> (params,
    opt_state, metrics).  With ``feel``, batch must carry "alpha"
    (n_clients,) availability indicators."""
    forward = make_forward(cfg)

    def loss_fn(params, batch):
        logits, hidden, aux = forward(params, batch)
        ex_loss, _ = per_example_loss(cfg, logits, batch)
        B = ex_loss.shape[0]
        metrics = {}
        if feel is None:
            loss = jnp.mean(ex_loss)
            metrics["selected_frac"] = jnp.asarray(1.0)
        else:
            K = feel.n_clients
            per_client = B // K
            sigma = jax.lax.stop_gradient(
                sigma_scores(cfg, hidden, logits, batch))
            sig_k = sigma.reshape(K, per_client)
            sys_k = feel.system(per_client)
            delta = sel_mod.exact_selection(
                sys_k, sig_k, jnp.ones_like(sig_k))  # (K, per_client)
            m_k = jnp.maximum(jnp.sum(delta, axis=1), 1.0)
            alpha = batch["alpha"].astype(jnp.float32)  # (K,)
            # eq. (19): (1/|D̂|) * (|D̂_k|/eps_k) * alpha_k * mean_sel
            w_k = (per_client / feel.eps) * alpha / (K * per_client)
            # per-sample weight: w_k * delta / m_k; summing gives
            # (1/K) sum_k (alpha_k/eps) mean_selected(loss_k) — an
            # unbiased estimate of the mean loss (Lemma 1)
            w = (delta * (w_k / m_k)[:, None]).reshape(B)
            loss = jnp.sum(w * ex_loss)
            metrics["selected_frac"] = jnp.mean(delta)
            metrics["sigma_mean"] = jnp.mean(sigma)
        total = loss + aux
        metrics["loss"] = loss
        metrics["aux_loss"] = aux
        return total, metrics

    def train_step(params, opt_state, batch):
        (_, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ArchConfig):
    def prefill_step(params, batch):
        x = embed_input(cfg, params, batch)
        B, S = x.shape[:2]
        pos = _positions(cfg, batch, B, S)
        hidden, cache, _ = apply_decoder(cfg, params["decoder"], x, pos,
                                         mode="prefill")
        logits = unembed(cfg, params, hidden[:, -1:])
        return logits, cache

    return prefill_step


def make_decode_step(cfg: ArchConfig, mla_absorbed: bool = False):
    """serve_step: one new token against a seq_len-sized cache."""

    def decode_step(params, cache, batch):
        x = embed_input(cfg, params, batch)
        B = x.shape[0]
        idx = batch["cache_index"]  # scalar int32
        pos = (batch["positions"] if cfg.modality == "vlm"
               else jnp.broadcast_to(idx[None, None], (B, 1)))
        hidden, new_cache, _ = apply_decoder(
            cfg, params["decoder"], x, pos, mode="decode", cache=cache,
            cache_index=idx, mla_absorbed=mla_absorbed)
        logits = unembed(cfg, params, hidden)
        return logits, new_cache

    return decode_step


make_cache = init_cache  # re-export with the model-level name
