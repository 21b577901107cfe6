"""Per-architecture smoke tests: REDUCED same-family configs run one
forward/train step + prefill + decode on CPU; shapes and finiteness
asserted. (Full configs are exercised only by the dry-run.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs import ARCHS, get_config, smoke_config
from repro.models import (init_model, make_cache, make_decode_step,
                          make_prefill_step, make_train_step, param_count)

B, S = 2, 16


def batch_for(cfg, B=B, S=S, labels=True):
    key = jax.random.PRNGKey(1)
    if cfg.modality == "text":
        t = jax.random.randint(key, (B, S), 0, cfg.vocab)
        b = {"tokens": t}
        if labels:
            b["labels"] = t
    elif cfg.modality == "vlm":
        b = {"embeds": jax.random.normal(key, (B, S, cfg.d_model),
                                         cfg.act_dtype),
             "positions": jnp.broadcast_to(
                 jnp.arange(S)[None, None, :], (B, 3, S)).astype(jnp.int32)}
        if labels:
            b["labels"] = jax.random.randint(jax.random.fold_in(key, 1),
                                             (B, S), 0, cfg.vocab)
    else:
        t = jax.random.randint(key, (B, cfg.n_codebooks, S), 0, cfg.vocab)
        b = {"tokens": t}
        if labels:
            b["labels"] = t
    return b


def decode_batch(cfg, B=B, index=S):
    if cfg.modality == "text":
        return {"tokens": jnp.zeros((B, 1), jnp.int32),
                "cache_index": jnp.int32(index)}
    if cfg.modality == "vlm":
        return {"embeds": jnp.zeros((B, 1, cfg.d_model), cfg.act_dtype),
                "positions": jnp.full((B, 3, 1), index, jnp.int32),
                "cache_index": jnp.int32(index)}
    return {"tokens": jnp.zeros((B, cfg.n_codebooks, 1), jnp.int32),
            "cache_index": jnp.int32(index)}


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_reduced_variant(arch):
    cfg = smoke_config(arch)
    assert cfg.n_layers <= 6 and cfg.d_model <= 512
    assert cfg.n_experts <= 4
    params = init_model(jax.random.PRNGKey(0), cfg)
    opt = optim.adamw(1e-3)
    state = opt.init(params)
    step = jax.jit(make_train_step(cfg, opt))
    batch = batch_for(cfg)
    params2, state2, metrics = step(params, state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    # params actually changed
    delta = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(params2)))
    assert delta > 0

    # prefill + decode produce sane shapes
    logits, cache = jax.jit(make_prefill_step(cfg))(params, batch_for(
        cfg, labels=False))
    if cfg.modality == "audio":
        assert logits.shape == (B, 1, cfg.n_codebooks, cfg.vocab)
    else:
        assert logits.shape == (B, 1, cfg.vocab)
    full = make_cache(cfg, B, S + 4)

    def graft(dst, src):
        if dst.shape == src.shape:
            return src
        pad = [(0, d - s) for d, s in zip(dst.shape, src.shape)]
        return jnp.pad(src, pad).astype(dst.dtype)

    cache = jax.tree.map(graft, full, cache)
    dl, _ = jax.jit(make_decode_step(cfg))(params, cache, decode_batch(cfg))
    assert np.all(np.isfinite(np.asarray(dl, np.float32)))


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_card_dims(arch):
    """The full configs carry the exact assignment-card dimensions."""
    cfg = get_config(arch)
    card = {
        "qwen2-vl-2b": (28, 1536, 12, 2, 8960, 151936),
        "deepseek-v3-671b": (61, 7168, 128, 128, None, 129280),
        "deepseek-v2-236b": (60, 5120, 128, 128, None, 102400),
        "deepseek-v2-lite": (27, 2048, 16, 16, 10944, 102400),
        "stablelm-12b": (40, 5120, 32, 8, 13824, 100352),
        "command-r-35b": (40, 8192, 64, 8, 22528, 256000),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "llama3_2-3b": (28, 3072, 24, 8, 8192, 128256),
        "falcon-mamba-7b": (64, 4096, None, None, None, 65024),
        "gemma3-12b": (48, 3840, 16, 8, 15360, 262144),
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
    }[arch]
    L, d, H, Hk, ff, V = card
    assert cfg.n_layers == L and cfg.d_model == d and cfg.vocab == V
    if H is not None:
        assert cfg.n_heads == H and cfg.n_kv_heads == Hk
    if ff is not None:
        assert cfg.d_ff == ff
    if arch.startswith("deepseek"):
        assert cfg.kv_lora == 512
        assert (cfg.n_experts, cfg.topk, cfg.moe_d_ff) == {
            "deepseek-v3-671b": (256, 8, 2048),
            "deepseek-v2-236b": (160, 6, 1536),
            "deepseek-v2-lite": (64, 6, 1408)}[arch]
    if arch == "falcon-mamba-7b":
        assert cfg.ssm_state == 16 and cfg.layer_pattern == ("mamba",)
    if arch == "recurrentgemma-9b":
        assert cfg.layer_pattern == ("rglru", "rglru", "attn_local")
    if arch == "gemma3-12b":
        assert cfg.layer_pattern.count("attn_local") == 5
    if arch == "qwen2-vl-2b":
        assert cfg.mrope_sections == (16, 24, 24)
    if arch == "musicgen-medium":
        assert cfg.n_codebooks == 4


def test_param_counts_match_cards():
    """Full-size param counts are in the advertised ballpark."""
    import repro.launch.shapes  # noqa: F401  (for eval_shape path)
    expect = {"llama3.2-3b": (2.8e9, 4.0e9),
              "falcon-mamba-7b": (6.5e9, 8.5e9),
              "gemma3-12b": (10e9, 14e9),
              "command-r-35b": (32e9, 40e9),
              "deepseek-v2-236b": (200e9, 260e9),
              "deepseek-v3-671b": (620e9, 720e9)}
    for arch, (lo, hi) in expect.items():
        cfg = get_config(arch)
        from repro.models import init_model
        abstract = jax.eval_shape(lambda k, c=cfg: init_model(k, c),
                                  jax.random.PRNGKey(0))
        n = sum(np.prod(x.shape) for x in jax.tree.leaves(abstract))
        assert lo < n < hi, (arch, f"{n:.3g}")
