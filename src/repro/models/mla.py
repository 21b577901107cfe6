"""Multi-head Latent Attention (DeepSeek-V2/V3).

Queries and keys/values are produced through low-rank bottlenecks; the
KV cache stores only the compressed latent c_kv (kv_lora dims) plus the
shared rotary key k_rope — the paper-family's memory win for decode.

The rope dims rotate as halves (``rotate_half``) in the layout the
projections produce them; DeepSeek-V2's code first permutes them from
interleaved pairs, which under random weights is a fixed permutation of
``w_q``/``w_kr`` columns.  With ``rope_factor > 1`` the rope is YaRN
(``layers.rope_yarn``) and the softmax scale carries its mscale^2.

Two decode paths:
  * naive   — expand k/v from the cached latent every step (simple,
              verifiable against prefill);
  * absorbed — fold W_uk into the query and W_uv into the output
              projection so attention runs directly in the latent
              space; per-step FLOPs drop from O(S * kv_lora * H * dh)
              (re-expansion) to O(S * H * kv_lora) (score/ctx einsums).
              This is the §Perf-tracked optimization for decode shapes.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ArchConfig
from .layers import (_NEG_INF, apply_rope, init_dense, rmsnorm, rope_yarn,
                     yarn_mscale)
from .shard_ctx import constrain

Array = jax.Array


def init_mla(key, cfg: ArchConfig, dtype) -> dict:
    d, H = cfg.d_model, cfg.n_heads
    nope, rope_d, vdim = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 8)
    p = {}
    if cfg.q_lora:
        p["w_dq"] = init_dense(ks[0], d, cfg.q_lora, dtype)
        p["q_norm"] = jnp.ones((cfg.q_lora,), dtype)
        p["w_uq"] = init_dense(ks[1], cfg.q_lora, H * (nope + rope_d), dtype)
    else:
        p["w_q"] = init_dense(ks[1], d, H * (nope + rope_d), dtype)
    p["w_dkv"] = init_dense(ks[2], d, cfg.kv_lora, dtype)
    p["kv_norm"] = jnp.ones((cfg.kv_lora,), dtype)
    p["w_uk"] = init_dense(ks[3], cfg.kv_lora, H * nope, dtype)
    p["w_uv"] = init_dense(ks[4], cfg.kv_lora, H * vdim, dtype)
    p["w_kr"] = init_dense(ks[5], d, rope_d, dtype)
    p["w_o"] = init_dense(ks[6], H * vdim, d, dtype)
    return p


def _queries(cfg: ArchConfig, p: dict, x: Array, positions: Array):
    B, S, _ = x.shape
    H = cfg.n_heads
    nope, rope_d = cfg.qk_nope_dim, cfg.qk_rope_dim
    if cfg.q_lora:
        cq = rmsnorm(x @ p["w_dq"], p["q_norm"])
        q = (cq @ p["w_uq"]).reshape(B, S, H, nope + rope_d)
    else:
        q = (x @ p["w_q"]).reshape(B, S, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta,
                        yarn=rope_yarn(cfg))
    return q_nope, q_rope


def _latents(cfg: ArchConfig, p: dict, x: Array, positions: Array):
    """Compressed latent (already normed) + roped shared key."""
    ckv = rmsnorm(x @ p["w_dkv"], p["kv_norm"])  # (B, S, kv_lora)
    kr = (x @ p["w_kr"])[:, :, None, :]  # (B, S, 1, rope_d)
    kr = apply_rope(kr, positions, cfg.rope_theta,
                    yarn=rope_yarn(cfg))[:, :, 0, :]
    return ckv, kr


def softmax_scale(cfg: ArchConfig) -> float:
    """``(nope + rope)^-0.5``, times YaRN's ``mscale(f, m_all)^2`` where
    the rope is scaled (DeepSeek-V2: 192^-0.5 * 1.2608^2)."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.rope_factor > 1 and cfg.rope_mscale_all_dim:
        scale *= yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    return scale


def mla_attention(cfg: ArchConfig, p: dict, x: Array, positions: Array,
                  mode: str, cache: Optional[dict], cache_index,
                  absorbed: bool = False) -> Tuple[Array, Optional[dict]]:
    """Returns (attn_out (B,S,d), new_cache)."""
    B, S, _ = x.shape
    H = cfg.n_heads
    nope, rope_d, vdim = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    scale = softmax_scale(cfg)
    q_nope, q_rope = _queries(cfg, p, x, positions)

    if mode in ("train", "prefill"):
        from .layers import causal_attend
        ckv, kr = _latents(cfg, p, x, positions)
        new_cache = None
        if mode == "prefill":
            new_cache = {"ckv": ckv, "kr": kr}
        k_nope = (ckv @ p["w_uk"]).reshape(B, S, H, nope)
        v = (ckv @ p["w_uv"]).reshape(B, S, H, vdim)
        # fold the shared rotary key in as extra head dims so the
        # q-chunked attention path (bounded memory at 32k) applies
        q_eff = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_eff = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr[:, :, None, :],
                                      (B, S, H, rope_d))], axis=-1)
        out = causal_attend(q_eff, k_eff, v, scale=scale)
        y = out.reshape(B, S, H * vdim) @ p["w_o"]
        return y, new_cache

    assert mode == "decode" and cache is not None
    ckv_new, kr_new = _latents(cfg, p, x, positions)
    ckv = jax.lax.dynamic_update_slice(
        cache["ckv"], ckv_new.astype(cache["ckv"].dtype),
        (0, cache_index, 0))
    kr = jax.lax.dynamic_update_slice(
        cache["kr"], kr_new.astype(cache["kr"].dtype), (0, cache_index, 0))
    new_cache = {"ckv": ckv, "kr": kr}
    Sc = ckv.shape[1]
    valid = (jnp.arange(Sc) <= cache_index)[None, None, None, :]

    rope_scores = jnp.einsum("bqhd,bkd->bhqk", q_rope, kr,
                             preferred_element_type=jnp.float32)
    if absorbed:
        # fold W_uk into q: (B,1,H,nope) x (kv_lora, H, nope) -> latent q
        w_uk = p["w_uk"].reshape(cfg.kv_lora, H, nope)
        q_lat = jnp.einsum("bqhn,chn->bqhc", q_nope, w_uk)
        q_lat = constrain(q_lat, "act_bthd")
        scores = jnp.einsum("bqhc,bkc->bhqk", q_lat, ckv,
                            preferred_element_type=jnp.float32)
        logits = (scores + rope_scores) * scale
        logits = jnp.where(valid, logits, _NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(ckv.dtype)
        ctx = jnp.einsum("bhqk,bkc->bqhc", probs, ckv)  # latent context
        w_uv = p["w_uv"].reshape(cfg.kv_lora, H, vdim)
        out = jnp.einsum("bqhc,chv->bqhv", ctx, w_uv)
    else:
        k_nope = (ckv @ p["w_uk"]).reshape(B, Sc, H, nope)
        v = (ckv @ p["w_uv"]).reshape(B, Sc, H, vdim)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                            preferred_element_type=jnp.float32)
        logits = (scores + rope_scores) * scale
        logits = jnp.where(valid, logits, _NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    y = out.reshape(B, 1, H * vdim) @ p["w_o"]
    return y, new_cache
