"""Shared neural-net layers: norms, RoPE (incl. M-RoPE), gated MLP,
and GQA attention with global / sliding-window / cached-decode paths.

All functions are pure; parameters are plain dict pytrees.  Attention
uses q-chunking for long sequences (bounded memory, flash-style
blocking — the Pallas kernel in kernels/flash_attention.py is the TPU
version of the same schedule; this jnp path is what the dry-run lowers
so cost_analysis sees real FLOPs).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ArchConfig

Array = jax.Array

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ------------------------------------------------------------------ norms

def rmsnorm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def init_dense(key, d_in: int, d_out: int, dtype) -> Array:
    scale = 1.0 / (d_in ** 0.5)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32)
            * scale).astype(dtype)


# ------------------------------------------------------------------- rope

def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention-temperature factor ``0.1 m ln s + 1`` (1 for
    ``s <= 1``), DeepSeek-V2's ``yarn_get_mscale``."""
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float,
                  beta_slow: float) -> np.ndarray:
    """YaRN inverse frequencies of a ``dim``-wide rotary head
    (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``): pair i blends
    ``1/(factor θ^(2i/d))`` (interpolated) into ``1/θ^(2i/d)``
    (extrapolated) by a linear ramp over the correction range of
    ``beta_fast``/``beta_slow`` rotations at ``original_max`` positions.
    """
    def correction_dim(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(dim // 2, dtype=np.float64)
    extra = 1.0 / theta ** (2 * i / dim)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp            # 1 where a pair keeps its frequency
    return (extra / factor * (1.0 - keep) + extra * keep).astype(np.float32)


def rope_yarn(cfg: ArchConfig) -> Optional[tuple]:
    """``(inv_freq, cos/sin scale)`` of the config's YaRN rope, or None
    where it has no rope scaling."""
    if cfg.rope_factor <= 1:
        return None
    inv = yarn_inv_freq(cfg.qk_rope_dim, cfg.rope_theta, cfg.rope_factor,
                        cfg.rope_original_max, cfg.rope_beta_fast,
                        cfg.rope_beta_slow)
    scale = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
             / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return inv, scale


def _rope_cos_sin(positions: Array, n_pairs: int, theta: float,
                  mrope_sections: Tuple[int, ...] = (),
                  yarn: Optional[tuple] = None):
    """cos/sin tables: positions (B,S) or (B,3,S) for M-RoPE; ``yarn``
    (from ``rope_yarn``) replaces the frequencies and scales the tables.

    Returns (B, S, n_pairs) float32 cos and sin.
    """
    if yarn is not None:
        freqs = jnp.asarray(yarn[0])
    else:
        freqs = theta ** (-jnp.arange(n_pairs, dtype=jnp.float32) / n_pairs)
    if positions.ndim == 2:  # standard 1-D rope
        ang = positions[..., None].astype(jnp.float32) * freqs
    else:
        # M-RoPE (Qwen2-VL): pair i takes its position id from the
        # (temporal|height|width) section it belongs to.
        assert sum(mrope_sections) == n_pairs, (mrope_sections, n_pairs)
        sec_id = jnp.repeat(
            jnp.arange(len(mrope_sections)),
            jnp.asarray(mrope_sections),
            total_repeat_length=n_pairs)  # (n_pairs,) in {0,1,2}
        pos = jnp.take(positions, sec_id, axis=1)  # (B, n_pairs, S)
        ang = jnp.swapaxes(pos, 1, 2).astype(jnp.float32) * freqs
    if yarn is not None and yarn[1] != 1.0:
        return jnp.cos(ang) * yarn[1], jnp.sin(ang) * yarn[1]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: Array, positions: Array, theta: float,
               fraction: float = 1.0,
               mrope_sections: Tuple[int, ...] = (),
               yarn: Optional[tuple] = None) -> Array:
    """x: (B, S, H, Dh). Rotates the first ``fraction * Dh`` dims, the
    pairs laid out as halves (``rotate_half``)."""
    d = x.shape[-1]
    d_rot = int(d * fraction)
    d_rot -= d_rot % 2
    if d_rot == 0:
        return x
    n_pairs = d_rot // 2
    cos, sin = _rope_cos_sin(positions, n_pairs, theta, mrope_sections,
                             yarn)
    cos = cos[:, :, None, :]  # (B, S, 1, n_pairs)
    sin = sin[:, :, None, :]
    xr, xp = x[..., :d_rot], x[..., d_rot:]
    x1, x2 = xr[..., :n_pairs], xr[..., n_pairs:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin,
                           x2f * cos + x1f * sin], axis=-1).astype(x.dtype)
    return jnp.concatenate([out, xp], axis=-1) if d - d_rot else out


# ------------------------------------------------------------------- mlp

def init_mlp(key, d: int, f: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": init_dense(k1, d, f, dtype),
            "w_up": init_dense(k2, d, f, dtype),
            "w_down": init_dense(k3, f, d, dtype)}


def mlp(p: dict, x: Array, act: str = "silu") -> Array:
    a = jax.nn.silu if act == "silu" else functools.partial(
        jax.nn.gelu, approximate=True)
    return (a(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


# -------------------------------------------------------------- attention

def init_attention(key, cfg: ArchConfig, dtype) -> dict:
    d, H, Hk, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 4)
    p = {"wq": init_dense(ks[0], d, H * Dh, dtype),
         "wk": init_dense(ks[1], d, Hk * Dh, dtype),
         "wv": init_dense(ks[2], d, Hk * Dh, dtype),
         "wo": init_dense(ks[3], H * Dh, d, dtype)}
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((Dh,), dtype)
        p["k_norm"] = jnp.ones((Dh,), dtype)
    return p


def _gqa_split(q: Array, n_kv: int) -> Array:
    """(B, S, H, Dh) -> (B, S, Hk, G, Dh): grouped-query layout.

    All attention helpers are GQA-native: keys/values keep their Hk
    heads and queries carry an extra group dim, so the repeated KV is
    never materialized (matters for 32k+ caches)."""
    B, S, H, Dh = q.shape
    return q.reshape(B, S, n_kv, H // n_kv, Dh)


def _softmax_attend(q: Array, k: Array, v: Array, mask: Array,
                    scale: float, softcap: float = 0.0) -> Array:
    """q: (B,Sq,Hk,G,Dh), k: (B,Sk,Hk,Dh), v: (B,Sk,Hk,Dv);
    mask (1|B, 1, 1, Sq, Sk) bool. Returns (B,Sq,Hk*G,Dv)."""
    B, Sq, Hk, G, _ = q.shape
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, Hk * G, v.shape[-1])


def causal_attend(q: Array, k: Array, v: Array, q_offset: int | Array = 0,
                  window: int = 0, scale: Optional[float] = None,
                  softcap: float = 0.0, q_chunk: int = 1024) -> Array:
    """Causal (optionally windowed) GQA attention with q-chunking.

    q: (B,Sq,H,Dh); k/v: (B,Sk,Hk,·).  q positions are
    ``q_offset + arange(Sq)``; k positions ``arange(Sk)``.
    ``window > 0`` limits attention to the last ``window`` keys.
    """
    B, Sq, H, Dh = q.shape
    Hk = k.shape[2]
    Sk = k.shape[1]
    scale = scale if scale is not None else Dh ** -0.5
    kpos = jnp.arange(Sk)
    qg = _gqa_split(q, Hk)

    def chunk_attend(args):
        qc, qpos = args
        mask = qpos[:, None] >= kpos[None, :]
        if window > 0:
            mask = mask & (kpos[None, :] > qpos[:, None] - window)
        return _softmax_attend(qc, k, v, mask[None, None, None],
                               scale, softcap)

    if Sq <= q_chunk:
        qpos = q_offset + jnp.arange(Sq)
        return chunk_attend((qg, qpos))

    n_chunks = -(-Sq // q_chunk)
    pad = n_chunks * q_chunk - Sq
    qp = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    qpos = q_offset + jnp.arange(n_chunks * q_chunk)
    qcs = jnp.moveaxis(
        qp.reshape(B, n_chunks, q_chunk, Hk, H // Hk, Dh), 1, 0)
    out = jax.lax.map(chunk_attend,
                      (qcs, qpos.reshape(n_chunks, q_chunk)))
    out = jnp.moveaxis(out, 0, 1).reshape(B, n_chunks * q_chunk, H,
                                          v.shape[-1])
    return out[:, :Sq]


def local_attend_chunked(q: Array, k: Array, v: Array, window: int,
                         scale: Optional[float] = None,
                         softcap: float = 0.0) -> Array:
    """Sliding-window causal GQA attention in O(S * window) memory.

    Sequence is cut into window-sized chunks; each chunk attends to
    itself and the previous chunk with an exact banded mask.
    """
    B, S, H, Dh = q.shape
    Hk = k.shape[2]
    G = H // Hk
    Dv = v.shape[-1]
    scale = scale if scale is not None else Dh ** -0.5
    W = window
    n = -(-S // W)
    pad = n * W - S

    def padded(x):
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))

    qc = padded(q).reshape(B, n, W, Hk, G, Dh)
    kc = padded(k).reshape(B, n, W, Hk, Dh)
    vc = padded(v).reshape(B, n, W, Hk, Dv)
    # keys for chunk i: chunks (i-1, i)
    k_prev = jnp.concatenate([jnp.zeros_like(kc[:, :1]), kc[:, :-1]], axis=1)
    v_prev = jnp.concatenate([jnp.zeros_like(vc[:, :1]), vc[:, :-1]], axis=1)
    k2 = jnp.concatenate([k_prev, kc], axis=2)  # (B, n, 2W, Hk, Dh)
    v2 = jnp.concatenate([v_prev, vc], axis=2)

    qpos = jnp.arange(W)
    kpos = jnp.arange(2 * W) - W  # relative to chunk start
    mask = (kpos[None, :] <= qpos[:, None]) & \
           (kpos[None, :] > qpos[:, None] - W)  # (W, 2W)
    # first chunk must not see the (zero) previous chunk
    first_mask = mask & (kpos[None, :] >= 0)
    masks = jnp.where(jnp.arange(n)[:, None, None] == 0, first_mask[None],
                      mask[None])  # (n, W, 2W)

    logits = jnp.einsum("bnqhgd,bnkhd->bnhgqk", qc, k2,
                        preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    logits = jnp.where(masks[:, None, None, :][None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bnhgqk,bnkhd->bnqhgd", probs, v2)
    return out.reshape(B, n * W, H, Dv)[:, :S]


def decode_attend(q: Array, k_cache: Array, v_cache: Array,
                  cache_index: Array, window: int = 0,
                  rolling: bool = False, scale: Optional[float] = None,
                  softcap: float = 0.0) -> Array:
    """Single-token GQA decode attention over a (possibly rolling) cache.

    q: (B, 1, H, Dh); caches: (B, C, Hk, ·) (NOT head-repeated).
    ``cache_index``: the new token's position.  For rolling caches
    (local attention), slot t of the buffer holds absolute position
    i - ((i - t) mod C) after writing token i at slot i % C.
    """
    B, _, H, Dh = q.shape
    Hk = k_cache.shape[2]
    C = k_cache.shape[1]
    scale = scale if scale is not None else Dh ** -0.5
    slots = jnp.arange(C)
    if rolling:
        i = cache_index
        pos = i - jnp.mod(i - slots, C)
        valid = pos >= 0
        if window > 0:
            valid &= pos > i - window
    else:
        valid = slots <= cache_index
        if window > 0:
            valid &= slots > cache_index - window
    mask = valid[None, None, None, None, :]  # (1,1,1,1,C)
    qg = _gqa_split(q, Hk)
    return _softmax_attend(qg, k_cache, v_cache, mask, scale, softcap)
