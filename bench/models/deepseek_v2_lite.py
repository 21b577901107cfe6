"""DeepSeek-V2-Lite (arXiv:2405.04434) as the benchmark trains it through
the FEEL round: one chip's share of an expert-parallel deployment.

The configuration (``configs/deepseek_v2_lite_k10.json``) holds the
published config with three cuts (``reduced``): the dense layer and 3
expert layers of 27, routed experts 0-7 of the router's 64 (chip 0 of the
8 that share each expert layer), and an eighth of the vocabulary. Every
width is published. The module gives the harness what ``cnn.py`` gives:

* ``samples(cfg, seed)``: per device, ``per_device`` token sequences of
  one topic (device k: topic ``k % topics``). Topic c is a first-order
  Markov chain over ids [topics, vocab), fixed by c alone (16 successors
  a state, Dirichlet(0.3) weights, a uniform first id); a sequence's
  label is its topic's code (ids 0..topics-1), a ``mislabel_prop`` share
  of them another topic's;
* ``dataset``, ``program``: the program's ``FederatedDataset`` and its
  ``models.control_lm.ControlTokenLM`` over the configuration;
* ``init_params(cfg, seed)``: the program's parameter tree, normal
  weights scaled by fan-in^-1/2, in one jitted call;
* ``round_flops(cfg, n_selected)``: a forward per scored sequence and
  three per selected one, from the widths, causal attention and the held
  experts' expected share of a token's slots (topk * held / router);
* the reference's part, plain ``jax.numpy`` at the ``precision`` passed
  and in the weights' dtype, importing nothing of the program: MLA with
  k/v expanded from the latent, YaRN from its formulas, each held expert
  computed over every token and weighted by a gate that is 0 where the
  token was not routed to it, sigma as the squared norm of the
  materialized head gradient ``H^T (P - Y) / S``; ``sigma`` and
  ``weighted_loss`` take the flat batch in chunks (``lax.map``; a
  ``lax.scan`` of ``jax.checkpoint`` steps), so 80 sequences fit. The
  harness passes them no configuration: they read the one the module
  was last given (by ``samples``, ``init_params`` or ``program``).

A sample is S ids ``x`` and its label: the label is the control token at
position 0, the input ``[label, x_0..x_{S-2}]``, the targets ``x``, and
its loss the mean next-token cross-entropy over the S positions.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import inputs

#: successors of each state of a topic's chain
FANOUT = 16
#: sequences per step of the reference's chunked passes
CHUNK = 8
#: the configuration the module was last given
_given: dict = {}


def _remember(cfg: dict) -> dict:
    _given["cfg"] = cfg
    return cfg


# ------------------------------------------------------------------ sizes

def widths(cfg: dict) -> dict:
    """The configuration's sizes under short names."""
    rope = cfg["rope_scaling"]
    dep = cfg["deployment"]
    return dict(
        d=cfg["hidden_size"], H=cfg["num_attention_heads"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v=cfg["v_head_dim"], kv=cfg["kv_lora_rank"],
        dense_ff=cfg["intermediate_size"], f=cfg["moe_intermediate_size"],
        shared=cfg["n_shared_experts"], topk=cfg["num_experts_per_tok"],
        E=dep["router_experts"], first=dep["experts_held_first"],
        held=cfg["n_routed_experts"], V=cfg["vocab_size"],
        layers=cfg["num_hidden_layers"],
        dense=cfg["first_k_dense_replace"], S=cfg["seq_len"],
        theta=float(cfg["rope_theta"]), factor=float(rope["factor"]),
        original=rope["original_max_position_embeddings"],
        beta_fast=float(rope["beta_fast"]),
        beta_slow=float(rope["beta_slow"]),
        mscale=float(rope["mscale"]),
        mscale_all=float(rope["mscale_all_dim"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        scaling=float(cfg["routed_scaling_factor"]),
        eps=float(cfg["rms_norm_eps"]))


# ------------------------------------------------------------------- data

def _chain(topic: int, topics: int, vocab: int):
    """(successors, cumulative weights) of topic's chain, fixed by it."""
    rng = np.random.default_rng(733_000 + topic)
    n = vocab - topics
    succ = rng.integers(topics, vocab, (n, FANOUT)).astype(np.int32)
    cum = np.cumsum(rng.dirichlet(np.full(FANOUT, 0.3), n), axis=1)
    return succ, cum


def sequences(topic: int, n: int, cfg: dict,
              rng: np.random.Generator) -> np.ndarray:
    """n sequences of ``seq_len`` ids from topic's chain."""
    topics, vocab = cfg["topics"], cfg["vocab_size"]
    succ, cum = _chain(topic, topics, vocab)
    out = np.empty((n, cfg["seq_len"]), np.int32)
    out[:, 0] = rng.integers(topics, vocab, n)
    for t in range(1, cfg["seq_len"]):
        state = out[:, t - 1] - topics
        j = (cum[state] < rng.random(n)[:, None]).sum(axis=1)
        out[:, t] = succ[state, np.minimum(j, FANOUT - 1)]
    return out


def samples(cfg: dict, seed: int) -> inputs.Samples:
    """Device k holds ``per_device`` sequences of topic ``k % topics``."""
    topics, K, n = _remember(cfg)["topics"], cfg["K"], cfg["per_device"]
    rng = np.random.default_rng(seed)
    out = inputs.Samples([], [], [])
    for k in range(K):
        topic = k % topics
        true = np.full(n, topic, np.int32)
        out.x.append(sequences(topic, n, cfg, rng))
        out.true.append(true)
        out.labels.append(inputs.mislabel(true, cfg["mislabel_prop"],
                                          topics, seed + 1000 + k))
    return out


def dataset(cfg: dict, data: inputs.Samples):
    """The program's ``FederatedDataset`` of the run's sequences (no test
    set: the window does not evaluate)."""
    from repro.data.federated import FederatedDataset

    return FederatedDataset(
        device_images=data.x, device_labels=data.labels,
        device_true=data.true,
        test_images=np.zeros((0, cfg["seq_len"]), np.int32),
        test_labels=np.zeros((0,), np.int32), num_classes=cfg["topics"])


def arch(cfg: dict):
    """The program's ``ArchConfig`` of the configuration: DeepSeek-V2-Lite
    with every size the configuration gives."""
    from repro.configs import get_config

    w = widths(cfg)
    assert cfg["q_lora_rank"] is None and w["eps"] == 1e-6
    assert cfg["rope_scaling"]["type"] == "yarn"
    assert cfg["hidden_act"] == "silu" and not cfg["tie_word_embeddings"]
    return get_config("deepseek-v2-lite").scaled(
        n_layers=w["layers"], d_model=w["d"], n_heads=w["H"],
        n_kv_heads=w["H"], d_ff=w["dense_ff"], vocab=w["V"],
        first_dense=w["dense"], n_experts=w["E"],
        n_shared_experts=w["shared"], topk=w["topk"], moe_d_ff=w["f"],
        norm_topk_prob=w["norm_topk"], routed_scaling_factor=w["scaling"],
        kv_lora=w["kv"], qk_nope_dim=w["nope"], qk_rope_dim=w["rope"],
        v_head_dim=w["v"], rope_theta=w["theta"], rope_factor=w["factor"],
        rope_original_max=w["original"], rope_beta_fast=w["beta_fast"],
        rope_beta_slow=w["beta_slow"], rope_mscale=w["mscale"],
        rope_mscale_all_dim=w["mscale_all"],
        experts_held=(w["first"], w["held"]), dtype=cfg["dtype"])


def program(cfg: dict):
    """The program's model object: ``ControlTokenLM`` over the config."""
    from repro.models.control_lm import ControlTokenLM

    return ControlTokenLM(arch(_remember(cfg)))


# ---------------------------------------------------------------- weights

def param_shapes(cfg: dict) -> dict:
    """The program's tree: the dense layer under ``head``, the expert
    layers stacked under ``body/pos0``, the embedding and the head."""
    w = widths(cfg)
    d, H = w["d"], w["H"]

    def attn():
        return {"w_q": (d, H * (w["nope"] + w["rope"])),
                "w_dkv": (d, w["kv"]), "kv_norm": (w["kv"],),
                "w_uk": (w["kv"], H * w["nope"]),
                "w_uv": (w["kv"], H * w["v"]),
                "w_kr": (d, w["rope"]), "w_o": (H * w["v"], d)}

    def mlp(f):
        return {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}

    dense = [{"ln1": (d,), "attn": attn(), "ln2": (d,),
              "ffn": mlp(w["dense_ff"])} for _ in range(w["dense"])]
    n = w["layers"] - w["dense"]
    Eh, f = w["held"], w["f"]
    moe = {"ln1": (d,), "attn": attn(), "ln2": (d,),
           "ffn": {"router": (d, w["E"]), "w_gate": (Eh, d, f),
                   "w_up": (Eh, d, f), "w_down": (Eh, f, d),
                   "shared": mlp(w["shared"] * f)}}
    body = jax.tree.map(lambda s: (n,) + s, moe,
                        is_leaf=lambda s: isinstance(s, tuple))
    return {"decoder": {"head": dense, "body": {"pos0": body}, "tail": [],
                        "final_norm": (d,)},
            "embed": (w["V"], d), "lm_head": (d, w["V"])}


def _is_shape(s):
    return isinstance(s, tuple) and all(isinstance(i, int) for i in s)


def init_params(cfg: dict, seed: int) -> dict:
    """Norm scales 1; the embedding N(0, 1/d); every other weight
    N(0, 1/fan_in), fan_in its second-to-last size."""
    shapes = param_shapes(_remember(cfg))
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=_is_shape)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=_is_shape)[0]]
    dtype = jnp.dtype(cfg["dtype"])
    d = widths(cfg)["d"]

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, shape, path in zip(keys, leaves, paths):
            if "norm" in path or path.endswith("['ln1']") \
                    or path.endswith("['ln2']"):
                out.append(jnp.ones(shape, dtype))
                continue
            fan_in = d if "embed" in path else shape[-2]
            out.append((jax.random.normal(k, shape, jnp.float32)
                        * fan_in ** -0.5).astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return build(jax.random.PRNGKey(seed))


# ------------------------------------------------------------------ FLOPs

def forward_flops(cfg: dict) -> float:
    """Model FLOPs of one sequence's forward pass."""
    w = widths(cfg)
    d, H, S = w["d"], w["H"], w["S"]
    proj = 2 * (d * H * (w["nope"] + w["rope"]) + d * w["kv"]
                + d * w["rope"] + w["kv"] * H * (w["nope"] + w["v"])
                + H * w["v"] * d)
    attend = S * (S + 1) / 2 * 2 * H * (w["nope"] + w["rope"] + w["v"])
    dense_ffn = 6 * d * w["dense_ff"]
    held_slots = w["topk"] * w["held"] / w["E"]   # a token's, on average
    moe_ffn = (2 * d * w["E"] + held_slots * 6 * d * w["f"]
               + 6 * d * w["shared"] * w["f"])
    n_moe = w["layers"] - w["dense"]
    per_token = (w["layers"] * proj + w["dense"] * dense_ffn
                 + n_moe * moe_ffn + 2 * d * w["V"])
    return S * per_token + w["layers"] * attend


def round_flops(cfg: dict, n_selected: int) -> float:
    """sigma's forward of every scored sequence, and a forward and a
    backward (three forwards) of every selected one."""
    f = forward_flops(cfg)
    return f * (cfg["K"] * cfg["d_hat"] + 3 * n_selected)


# ------------------------------------------------------ reference's part

def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn(w: dict):
    """(inv_freq (rope/2,), cos/sin scale, softmax scale) of YaRN, from
    DeepSeek-V2's formulas."""
    dim, base = w["rope"], w["theta"]

    def corr(rot):
        return dim * math.log(w["original"] / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    lo = max(math.floor(corr(w["beta_fast"])), 0)
    hi = min(math.ceil(corr(w["beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    pos = np.arange(0, dim, 2) / dim
    extra = 1.0 / base ** pos
    inter = 1.0 / (w["factor"] * base ** pos)
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
    mask = 1.0 - ramp
    inv = inter * (1 - mask) + extra * mask
    cs = _mscale(w["factor"], w["mscale"]) / _mscale(w["factor"],
                                                     w["mscale_all"])
    soft = (w["nope"] + w["rope"]) ** -0.5 \
        * _mscale(w["factor"], w["mscale_all"]) ** 2
    return inv, cs, soft


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _dot(a, b, precision):
    return jnp.matmul(a, b, precision=precision)


def _rotate(x, cos, sin):
    """Rope over the last dim as two halves: (x1, x2) -> (x1 cos - x2
    sin, x2 cos + x1 sin)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, w, precision):
    B, S, _ = x.shape
    H, nope, rope, vd = w["H"], w["nope"], w["rope"], w["v"]
    inv, cs, soft = yarn(w)
    ang = np.arange(S)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang) * cs, x.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang) * cs, x.dtype)[None, :, None, :]
    q = _dot(x, p["w_q"], precision).reshape(B, S, H, nope + rope)
    q_nope, q_rope = q[..., :nope], _rotate(q[..., nope:], cos, sin)
    c = _rms(_dot(x, p["w_dkv"], precision), p["kv_norm"], w["eps"])
    k_nope = _dot(c, p["w_uk"], precision).reshape(B, S, H, nope)
    v = _dot(c, p["w_uv"], precision).reshape(B, S, H, vd)
    k_rope = _rotate(_dot(x, p["w_kr"], precision)[:, :, None, :], cos, sin)
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                         precision=precision)
              + jnp.einsum("bqhd,bkhd->bhqk", q_rope, k_rope,
                           precision=precision)) * soft
    causal = np.tril(np.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=precision)
    return _dot(out.reshape(B, S, H * vd), p["w_o"], precision)


def _mlp(p, x, precision):
    return _dot(jax.nn.silu(_dot(x, p["w_gate"], precision))
                * _dot(x, p["w_up"], precision), p["w_down"], precision)


def _experts(p, x, w, precision):
    """The held experts' part, each over every token with its gate (0
    where the token did not route to it), plus the shared experts."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    probs = jax.nn.softmax(_dot(xf, p["router"], precision), axis=-1)
    vals, idx = jax.lax.top_k(probs, w["topk"])
    if w["norm_topk"]:
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    vals = vals * w["scaling"]
    held = w["first"] + np.arange(w["held"])
    gates = jnp.sum(jnp.where(idx[:, :, None] == held, vals[:, :, None], 0),
                    axis=1)                                # (tokens, held)
    gate = jnp.einsum("gd,edf->egf", xf, p["w_gate"], precision=precision)
    up = jnp.einsum("gd,edf->egf", xf, p["w_up"], precision=precision)
    out = jnp.einsum("egf,efd->egd", jax.nn.silu(gate) * up, p["w_down"],
                     precision=precision)
    y = _mlp(p["shared"], xf, precision) + jnp.einsum(
        "ge,egd->gd", gates, out, precision=precision)
    return y.reshape(B, S, d)


def forward(params, tokens, precision, cfg):
    """(final-norm h (B, S, d), logits (B, S, V)) of input ids."""
    w = widths(cfg)
    dec = params["decoder"]

    @functools.partial(jax.checkpoint, static_argnums=(2,))
    def block(x, p, routed):
        x = x + _attention(p["attn"], _rms(x, p["ln1"], w["eps"]), w,
                           precision)
        h = _rms(x, p["ln2"], w["eps"])
        return x + (_experts(p["ffn"], h, w, precision) if routed
                    else _mlp(p["ffn"], h, precision))

    x = params["embed"][tokens]
    for p in dec["head"]:
        x = block(x, p, False)
    x, _ = jax.lax.scan(lambda x, p: (block(x, p, True), None), x,
                        dec["body"]["pos0"])
    h = _rms(x, dec["final_norm"], w["eps"])
    return h, _dot(h, params["lm_head"], precision)


def _tokens(x, y):
    return jnp.concatenate([y[:, None].astype(x.dtype), x[:, :-1]], axis=1)


def _chunks(a, n):
    return a.reshape((a.shape[0] // n, n) + a.shape[1:])


def _chunk(size: int) -> int:
    return max(c for c in range(1, CHUNK + 1) if size % c == 0)


def _cfg() -> dict:
    if "cfg" not in _given:
        raise RuntimeError("the reference's part needs the configuration: "
                           "call samples, init_params or program first")
    return _given["cfg"]


def sigma(params, x, y, precision):
    """||H^T (P - Y) / S||^2 per sequence of a flat batch, the head's
    gradient materialized."""
    cfg, S = _cfg(), x.shape[1]

    def one(args):
        xc, yc = args
        h, logits = forward(params, _tokens(xc, yc), precision, cfg)
        r = jax.nn.softmax(logits, axis=-1) - jax.nn.one_hot(
            xc, logits.shape[-1], dtype=logits.dtype)
        g = jnp.einsum("bsd,bsv->bdv", h, r, precision=precision) / S
        return jnp.sum(g * g, axis=(1, 2))

    c = _chunk(x.shape[0])
    return jax.lax.map(one, (_chunks(x, c), _chunks(y, c))).reshape(-1)


def weighted_loss(params, x, y, wt, precision):
    """sum_i w_i CE_i over a flat batch, CE_i the sequence's mean
    next-token cross-entropy."""
    cfg = _cfg()

    @jax.checkpoint
    def one(args):
        xc, yc, wc = args
        _, logits = forward(params, _tokens(xc, yc), precision, cfg)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.mean(jnp.take_along_axis(logp, xc[..., None],
                                           axis=-1)[..., 0], axis=-1)
        return jnp.sum(wc * ce)

    def step(total, args):
        return total + one(args), None

    c = _chunk(x.shape[0])
    total, _ = jax.lax.scan(step, jnp.zeros((), wt.dtype),
                            (_chunks(x, c), _chunks(y, c), _chunks(wt, c)))
    return total
