"""Decoder assembly: blocks, repeating-pattern scan, caches.

Layer layout = ``first_dense`` unrolled head layers (DeepSeek's dense
lead-in), then floor((L - first_dense)/P) repetitions of the
``layer_pattern`` lowered as ONE ``jax.lax.scan`` over stacked params
(small HLO, fast multi-pod compiles), then the remainder layers
unrolled from the pattern prefix.

Block kinds and their caches:
    attn        {"k","v"}: (B, S_ctx, Hk, Dh)
    attn_local  {"k","v"}: (B, window, Hk, Dh)  rolling buffer
    mla         {"ckv": (B,S,kv_lora), "kr": (B,S,rope_d)}
    mamba       {"conv": (B,k-1,d_in), "h": (B,d_in,n)}
    rglru       {"conv": (B,k-1,w), "h": (B,w)}
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ArchConfig
from .layers import (apply_rope, causal_attend, decode_attend, init_attention,
                     init_mlp, local_attend_chunked, mlp, rmsnorm)
from .mla import init_mla, mla_attention
from .moe import held_expert_ffn, init_moe, moe_ffn
from .rglru import init_rglru, rglru_mixer
from .ssm import init_mamba, mamba_mixer
from .shard_ctx import constrain

Array = jax.Array


# ------------------------------------------------------------------ blocks

def init_block(key, cfg: ArchConfig, kind: str, use_moe: bool,
               dense_ff: Optional[int] = None) -> dict:
    dtype = cfg.act_dtype
    d = cfg.d_model
    k1, k2, k3 = jax.random.split(key, 3)
    p: dict = {"ln1": jnp.ones((d,), dtype)}
    if kind in ("attn", "attn_local"):
        p["attn"] = init_attention(k1, cfg, dtype)
    elif kind == "mla":
        p["attn"] = init_mla(k1, cfg, dtype)
    elif kind == "mamba":
        p["mixer"] = init_mamba(k1, cfg, dtype)
        return p  # mamba blocks have no separate FFN
    elif kind == "rglru":
        p["mixer"] = init_rglru(k1, cfg, dtype)
    else:
        raise ValueError(kind)
    p["ln2"] = jnp.ones((d,), dtype)
    if use_moe:
        p["ffn"] = init_moe(k2, cfg, dtype)
    else:
        p["ffn"] = init_mlp(k2, d, dense_ff or cfg.d_ff, dtype)
    return p


def _attn_apply(cfg: ArchConfig, kind: str, p: dict, x: Array,
                positions: Array, mode: str, cache, cache_index,
                mla_absorbed: bool):
    """Attention sublayer dispatch; returns (out, new_cache)."""
    if kind == "mla":
        return mla_attention(cfg, p["attn"], x, positions, mode, cache,
                             cache_index, absorbed=mla_absorbed)

    B, S, _ = x.shape
    H, Hk, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ap = p["attn"]
    local = kind == "attn_local"
    theta = (cfg.rope_theta_local
             if local and cfg.rope_theta_local else cfg.rope_theta)
    softcap = cfg.attn_logit_softcap

    q = (x @ ap["wq"]).reshape(B, S, H, Dh)
    k = (x @ ap["wk"]).reshape(B, S, Hk, Dh)
    v = (x @ ap["wv"]).reshape(B, S, Hk, Dh)
    if cfg.qk_norm:
        q = rmsnorm(q, ap["q_norm"])
        k = rmsnorm(k, ap["k_norm"])
    q = apply_rope(q, positions, theta, cfg.rope_fraction,
                   cfg.mrope_sections)
    k = apply_rope(k, positions, theta, cfg.rope_fraction,
                   cfg.mrope_sections)
    q = constrain(q, "act_bthd")

    new_cache = None
    if mode == "train":
        if local:
            out = local_attend_chunked(q, k, v, cfg.window, softcap=softcap)
        else:
            out = causal_attend(q, k, v, softcap=softcap)
    elif mode == "prefill":
        if local:
            W = cfg.window
            out = local_attend_chunked(q, k, v, W, softcap=softcap)
            # rolling cache holds the last W tokens at slot pos % W
            take = min(S, W)
            kw = k[:, S - take:]
            vw = v[:, S - take:]
            slots = jnp.mod(jnp.arange(S - take, S), W)
            k_buf = jnp.zeros((B, W, Hk, Dh), k.dtype).at[:, slots].set(kw)
            v_buf = jnp.zeros((B, W, Hk, Dh), v.dtype).at[:, slots].set(vw)
            new_cache = {"k": k_buf, "v": v_buf}
        else:
            out = causal_attend(q, k, v, softcap=softcap)
            new_cache = {"k": constrain(k, "kv_cache"),
                         "v": constrain(v, "kv_cache")}
    else:  # decode
        assert cache is not None
        C = cache["k"].shape[1]
        slot = jnp.mod(cache_index, C) if local else cache_index
        kc = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
        kc = constrain(kc, "kv_cache")
        vc = constrain(vc, "kv_cache")
        new_cache = {"k": kc, "v": vc}
        out = decode_attend(q, kc, vc, cache_index,
                            window=cfg.window if local else 0,
                            rolling=local, softcap=softcap)
    y = out.reshape(B, S, H * Dh) @ ap["wo"]
    return y, new_cache


def apply_block(cfg: ArchConfig, kind: str, use_moe: bool, p: dict,
                x: Array, positions: Array, mode: str, cache,
                cache_index, mla_absorbed: bool = False
                ) -> Tuple[Array, Any, Array, Optional[Array]]:
    """Pre-norm residual block. Returns (x, new_cache, aux_loss, slots):
    ``slots`` (B, n_held) of a held-expert layer, else None."""
    aux = jnp.zeros((), jnp.float32)
    slots = None
    h = rmsnorm(x, p["ln1"])
    if kind in ("attn", "attn_local", "mla"):
        y, new_cache = _attn_apply(cfg, kind, p, h, positions, mode, cache,
                                   cache_index, mla_absorbed)
    elif kind == "mamba":
        y, new_cache = mamba_mixer(cfg, p["mixer"], h, mode, cache)
        return constrain(x + y, "act_btd"), new_cache, aux, slots
    elif kind == "rglru":
        y, new_cache = rglru_mixer(cfg, p["mixer"], h, mode, cache)
    else:
        raise ValueError(kind)
    x = x + y
    h = rmsnorm(x, p["ln2"])
    if use_moe and cfg.experts_held is not None:
        f, slots = held_expert_ffn(cfg, p["ffn"], h)
    elif use_moe:
        f, aux = moe_ffn(cfg, p["ffn"], h)
    else:
        f = mlp(p["ffn"], h, cfg.act)
    return constrain(x + f, "act_btd"), new_cache, aux, slots


# ----------------------------------------------------------- decoder stack

def _layer_plan(cfg: ArchConfig):
    """(head_kinds, n_body, pattern, tail_kinds)."""
    P = len(cfg.layer_pattern)
    fd = cfg.first_dense
    L_rest = cfg.n_layers - fd
    n_body = L_rest // P
    tail = cfg.layer_pattern[:L_rest % P]
    head = tuple(cfg.layer_pattern[i % P] for i in range(fd))
    return head, n_body, cfg.layer_pattern, tail


def _uses_moe(cfg: ArchConfig) -> bool:
    return cfg.n_experts > 0


def init_decoder(key, cfg: ArchConfig) -> dict:
    head, n_body, pattern, tail = _layer_plan(cfg)
    ks = jax.random.split(key, 4)
    params: dict = {}
    # DeepSeek's dense lead-in layers use the wide dense FFN
    dense_ff = cfg.d_ff if not _uses_moe(cfg) else None
    params["head"] = [
        init_block(jax.random.fold_in(ks[0], i), cfg, kind, use_moe=False,
                   dense_ff=cfg.d_ff)
        for i, kind in enumerate(head)]
    body = {}
    for pos, kind in enumerate(pattern):
        keys = jax.random.split(jax.random.fold_in(ks[1], pos), n_body)
        body[f"pos{pos}"] = jax.vmap(
            lambda k: init_block(k, cfg, kind, use_moe=_uses_moe(cfg),
                                 dense_ff=dense_ff))(keys)
    params["body"] = body
    params["tail"] = [
        init_block(jax.random.fold_in(ks[2], 100 + i), cfg, kind,
                   use_moe=_uses_moe(cfg), dense_ff=dense_ff)
        for i, kind in enumerate(tail)]
    params["final_norm"] = jnp.ones((cfg.d_model,), cfg.act_dtype)
    return params


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=None) -> dict:
    """Zero-filled cache pytree matching the decoder layout."""
    dtype = dtype or cfg.act_dtype
    head, n_body, pattern, tail = _layer_plan(cfg)

    def one(kind):
        B = batch
        Hk, Dh = cfg.n_kv_heads, cfg.head_dim_
        if kind == "attn":
            shape = (B, max_len, Hk, Dh)
            return {"k": jnp.zeros(shape, dtype),
                    "v": jnp.zeros(shape, dtype)}
        if kind == "attn_local":
            # rolling buffer is always window-sized (prefill fills
            # slot pos % window even when max_len < window)
            shape = (B, cfg.window, Hk, Dh)
            return {"k": jnp.zeros(shape, dtype),
                    "v": jnp.zeros(shape, dtype)}
        if kind == "mla":
            return {"ckv": jnp.zeros((B, max_len, cfg.kv_lora), dtype),
                    "kr": jnp.zeros((B, max_len, cfg.qk_rope_dim), dtype)}
        if kind == "mamba":
            return {"conv": jnp.zeros((B, cfg.ssm_conv - 1,
                                       cfg.ssm_d_inner), dtype),
                    "h": jnp.zeros((B, cfg.ssm_d_inner, cfg.ssm_state),
                                   jnp.float32)}
        if kind == "rglru":
            k = cfg.ssm_conv or 4
            return {"conv": jnp.zeros((B, k - 1, cfg.lru_width_), dtype),
                    "h": jnp.zeros((B, cfg.lru_width_), jnp.float32)}
        raise ValueError(kind)

    stack = lambda kind: jax.tree.map(
        lambda z: jnp.broadcast_to(z[None], (n_body,) + z.shape), one(kind))
    return {"head": [one(k) for k in head],
            "body": {f"pos{i}": stack(k) for i, k in enumerate(pattern)},
            "tail": [one(k) for k in tail]}


def apply_decoder(cfg: ArchConfig, params: dict, x: Array, positions: Array,
                  mode: str, cache: Optional[dict] = None,
                  cache_index: Array | int = 0,
                  mla_absorbed: bool = False, with_slots: bool = False):
    """Returns (hidden (B,S,d), new_cache, aux_loss_sum), and with
    ``with_slots`` also the held-expert layers' slots (layers, B,
    n_held), in layer order."""
    head, n_body, pattern, tail = _layer_plan(cfg)
    use_moe = _uses_moe(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    new_cache: dict = {"head": [], "body": {}, "tail": []}
    slots: list = []

    blk = functools.partial(apply_block, cfg)
    # head layers: dense FFN even in MoE configs (DeepSeek lead-in)
    for i, kind in enumerate(head):
        c = cache["head"][i] if cache is not None else None
        x, nc, aux, sl = blk(kind, False, params["head"][i], x, positions,
                             mode, c, cache_index, mla_absorbed)
        aux_total += aux
        new_cache["head"].append(nc)

    # body: one scan over the stacked pattern repeats
    if n_body:
        body_params = tuple(params["body"][f"pos{i}"]
                            for i in range(len(pattern)))

        def step(x, xs):
            if cache is not None:
                p_slices, c_slices = xs
            else:
                p_slices, c_slices = xs, (None,) * len(pattern)
            aux_step = jnp.zeros((), jnp.float32)
            ncs, sls = [], []
            for pos, kind in enumerate(pattern):
                x, nc, aux, sl = blk(kind, use_moe, p_slices[pos], x,
                                     positions, mode, c_slices[pos],
                                     cache_index, mla_absorbed)
                aux_step += aux
                ncs.append(nc)
                if sl is not None:
                    sls.append(sl)
            if mode == "train":
                return x, (aux_step, tuple(sls))
            return x, (tuple(ncs), aux_step)

        if cfg.remat and mode == "train":
            step = jax.checkpoint(step, prevent_cse=False)

        unroll = n_body if cfg.unroll_layers else cfg.scan_unroll
        if mode == "train":
            x, (auxs, body_slots) = jax.lax.scan(step, x, body_params,
                                                 unroll=unroll)
            # (repeats, B, n_held) per pattern position -> layer order
            if body_slots:
                slots.extend(jnp.stack(body_slots, axis=1).reshape(
                    (-1,) + body_slots[0].shape[1:]))
        elif mode == "prefill":
            x, (nc_body, auxs) = jax.lax.scan(step, x, body_params,
                                              unroll=unroll)
            new_cache["body"] = {f"pos{i}": nc_body[i]
                                 for i in range(len(pattern))}
        else:  # decode
            body_cache = tuple(cache["body"][f"pos{i}"]
                               for i in range(len(pattern)))
            x, (nc_body, auxs) = jax.lax.scan(step, x,
                                              (body_params, body_cache),
                                              unroll=unroll)
            new_cache["body"] = {f"pos{i}": nc_body[i]
                                 for i in range(len(pattern))}
        aux_total += jnp.sum(auxs)

    for i, kind in enumerate(tail):
        c = cache["tail"][i] if cache is not None else None
        x, nc, aux, sl = blk(kind, use_moe, params["tail"][i], x, positions,
                             mode, c, cache_index, mla_absorbed)
        aux_total += aux
        new_cache["tail"].append(nc)
        if sl is not None:
            slots.append(sl)

    x = rmsnorm(x, params["final_norm"])
    out = (x, (new_cache if cache is not None or mode == "prefill"
               else None), aux_total)
    if with_slots:
        return out + (jnp.stack(slots) if slots else None,)
    return out
