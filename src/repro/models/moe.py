"""Mixture-of-Experts FFN (DeepSeek-V2/V3 style: shared + routed
experts, token-choice top-k routing; the top-k gates renormalized where
``norm_topk_prob``, then times ``routed_scaling_factor``).

Two layers:

* ``held_expert_ffn``, the FEEL round's: the chip's share of an
  expert-parallel layer.  It routes over all ``n_experts`` (the router
  keeps its published width, in float32 at HIGHEST), keeps the
  assignments to the experts it holds (``experts_held``), sorts them by
  expert and runs one grouped product per projection over the held
  experts' weights (the Pallas grouped matmul that ships with JAX,
  ``megablox.gmm``), then un-sorts and weights by the gate.  No token is
  dropped; what the absent experts would add is left out, as on a chip
  of an expert-parallel deployment before its exchange.  The products
  and their gradients take their operands as the backend's float32
  ``jnp.dot`` would (``operand_dtype``) and return float32, in row
  tiles the expected load of a held expert fills (``row_tile``).
* ``moe_ffn``, the launch path's, below.

TPU adaptation (DESIGN.md §2): dispatch is *capacity-based gather*
rather than a (tokens x experts x capacity) one-hot einsum — each
expert takes the top-C tokens that routed to it (priority by gate
value), giving fixed shapes, MXU-aligned per-expert matmuls, and an
expert-sharded (E, C, d) working set.  Tokens beyond capacity are
dropped (standard drop policy; capacity_factor controls slack).
The expert dim E shards over the mesh "model" axis (expert
parallelism) — the gather/scatter lower to the all-to-all-like
collectives the roofline analysis attributes to MoE.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
# the kernels themselves: the package's ``gmm`` is its own custom VJP
from jax.experimental.pallas.ops.tpu.megablox.ops import backend as kernels

from .config import ArchConfig
from .layers import init_dense, mlp
from .shard_ctx import constrain

Array = jax.Array


def init_moe(key, cfg: ArchConfig, dtype) -> dict:
    """Router over all ``n_experts``; weights of the held experts only."""
    d, E, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    Eh = cfg.n_held
    ks = jax.random.split(key, 5)
    scale = 1.0 / (d ** 0.5)
    p = {
        "router": init_dense(ks[0], d, E, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (Eh, d, f), jnp.float32)
                   * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (Eh, d, f), jnp.float32)
                 * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (Eh, f, d), jnp.float32)
                   * (1.0 / f ** 0.5)).astype(dtype),
    }
    if cfg.n_shared_experts:
        from .layers import init_mlp
        p["shared"] = init_mlp(ks[4], d, cfg.n_shared_experts * f, dtype)
    return p


def route(cfg: ArchConfig, router_logits: Array) -> Tuple[Array, Array]:
    """Top-k gates and expert ids of float32 router logits (G, E)."""
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, cfg.topk)  # (G, K)
    if cfg.norm_topk_prob:  # DeepSeek normalization
        top_vals = top_vals / jnp.maximum(
            jnp.sum(top_vals, -1, keepdims=True), 1e-9)
    return top_vals * cfg.routed_scaling_factor, top_idx


def _tile(n: int) -> int:
    """A contraction or output tile of the grouped products: 512 wide
    (a 128 x 512 x 512 step keeps the MXU busy where 128-wide steps are
    mostly per-step overhead), the whole dimension where it is less."""
    return min(512, n)


def row_tile(assignments: int, n_experts: int) -> int:
    """The grouped products' row tile: the largest of 128, 256 and 512
    that the expected rows of a held group (``assignments`` spread over
    all ``n_experts`` the router chooses from) fill.  Each row tile
    reads its group's whole weight block, so a wider tile does more
    work per weight byte, until tiles straddling two groups waste it."""
    expected = assignments // n_experts
    return max([t for t in (256, 512) if t <= expected], default=128)


def operand_dtype():
    """What the grouped products feed the MXU: bfloat16 where a float32
    ``jnp.dot`` runs as one bfloat16 pass with float32 accumulation (the
    TPU at JAX's default matmul precision), else float32 (off the TPU,
    or where ``jax_default_matmul_precision`` asks for more)."""
    if jax.default_backend() != "tpu":
        return jnp.float32
    if jax.config.jax_default_matmul_precision not in (
            None, "default", "bfloat16", "fastest"):
        return jnp.float32
    return jnp.bfloat16


def grouped_path(cfg: ArchConfig, tokens: int) -> dict:
    """Which grouped products a pass over ``tokens`` tokens runs: their
    row tile and the dtype they feed the MXU."""
    return {"row_tile": row_tile(tokens * cfg.topk, cfg.n_experts),
            "operand_dtype": jnp.dtype(operand_dtype()).name}


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(x: Array, w: Array, sizes: Array, tm: int, dtype) -> Array:
    """``megablox``'s grouped product with operands rounded to ``dtype``
    and float32 accumulation and output; the gradients likewise, each
    product's tiles sized from its own widths."""
    return _gmm_fwd(x, w, sizes, tm, dtype)[0]


def _gmm_fwd(x, w, sizes, tm, dtype):
    xl, wl = x.astype(dtype), w.astype(dtype)
    y = kernels.gmm(xl, wl, sizes, jnp.float32,
                    (tm, _tile(x.shape[1]), _tile(w.shape[2])),
                    interpret=_interpret())
    return y, (xl, wl, sizes)


def _gmm_bwd(tm, dtype, res, dy):
    xl, wl, sizes = res
    dyl = dy.astype(dtype)
    dx = kernels.gmm(dyl, wl, sizes, jnp.float32,
                     (tm, _tile(wl.shape[2]), _tile(wl.shape[1])),
                     transpose_rhs=True, interpret=_interpret())
    dw = kernels.tgmm(xl.swapaxes(0, 1), dyl, sizes, jnp.float32,
                      (tm, _tile(wl.shape[1]), _tile(wl.shape[2])),
                      num_actual_groups=wl.shape[0],
                      interpret=_interpret())
    return dx, dw, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _grouped(x: Array, w: Array, sizes: Array, tm: int,
             dtype=None) -> Array:
    """Rows of x grouped by expert (``sizes`` rows each, the rest past
    the groups) times each group's expert weights: (rows, w.shape[-1])
    in float32, fed to the MXU in ``dtype`` (``operand_dtype()`` where
    None).  Rows past the groups are not computed and read as garbage."""
    f32 = jnp.float32
    return _gmm(x.astype(f32), w.astype(f32), sizes, tm,
                dtype or operand_dtype()).astype(x.dtype)


def held_expert_ffn(cfg: ArchConfig, p: dict, x: Array
                    ) -> Tuple[Array, Array]:
    """x: (B, S, d) -> (y, slots): y the held experts' part plus the
    shared experts, ``slots`` (B, n_held) int32 the (token, held expert)
    assignments of each sequence."""
    B, S, d = x.shape
    G, K = B * S, cfg.topk
    first, Eh = cfg.experts_held or (0, cfg.n_experts)
    xf = x.reshape(G, d)
    router_logits = jnp.dot(xf.astype(jnp.float32),
                            p["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
    gates, experts = route(cfg, router_logits)        # (G, K)
    local = experts - first
    held = (local >= 0) & (local < Eh)
    seq = jnp.arange(G) // S
    slots = jnp.zeros((B, Eh), jnp.int32).at[
        seq[:, None], jnp.where(held, local, 0)].add(held.astype(jnp.int32))

    # the held assignments sorted by expert, the others after them
    group = jnp.where(held, local, Eh).reshape(-1)    # (G*K,)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.sum(slots, axis=0)
    tm = row_tile(G * K, cfg.n_experts)
    rows = -(-G * K // tm) * tm                       # whole row tiles
    pad = rows - G * K
    order = jnp.concatenate([order, jnp.full((pad,), G * K, order.dtype)])
    valid = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
    tok = jnp.minimum(order // K, G - 1)
    xs = jnp.where(valid, xf[tok], 0)
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    h = act(_grouped(xs, p["w_gate"], sizes, tm)) \
        * _grouped(xs, p["w_up"], sizes, tm)
    ys = jnp.where(valid, _grouped(h, p["w_down"], sizes, tm), 0)
    gate = jnp.concatenate([jnp.where(held, gates, 0.0).reshape(-1),
                            jnp.zeros((1,), gates.dtype)])[order]
    yf = jnp.zeros((G, d), x.dtype).at[tok].add(
        ys * gate[:, None].astype(x.dtype))
    if cfg.n_shared_experts:
        yf = yf + mlp(p["shared"], xf, cfg.act)
    return yf.reshape(B, S, d), slots


def capacity(cfg: ArchConfig, n_tokens: int) -> int:
    c = int(n_tokens * cfg.topk / cfg.n_experts * cfg.capacity_factor)
    return min(max(8, -(-c // 8) * 8), n_tokens)  # 8-aligned, <= tokens


def moe_ffn(cfg: ArchConfig, p: dict, x: Array) -> Tuple[Array, Array]:
    """x: (B, S, d) -> (y, aux_loss)."""
    B, S, d = x.shape
    G = B * S
    E, K = cfg.n_experts, cfg.topk
    xf = x.reshape(G, d)

    # matmul in the activation dtype so the (G, d) gradient flowing
    # back through the router stays bf16 (halves the dispatch-grad
    # all-reduce, §Perf pair B iter 3); softmax still f32.
    router_logits = (xf @ p["router"].astype(xf.dtype)
                     ).astype(jnp.float32)  # (G, E)
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_vals, top_idx = route(cfg, router_logits)  # (G, K)

    # gate matrix (G, E): gate value where expert chosen, else 0
    gate_mat = jnp.zeros((G, E), jnp.float32).at[
        jnp.arange(G)[:, None], top_idx].set(top_vals)

    # ---- expert-side capacity selection (priority = gate value) ----
    C = capacity(cfg, G)
    w_ec, idx_ec = jax.lax.top_k(gate_mat.T, C)  # (E, C) over tokens
    x_ec = jnp.take(xf, idx_ec, axis=0)  # (E, C, d)
    x_ec = constrain(x_ec, "moe_ecd")

    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    h = act(jnp.einsum("ecd,edf->ecf", x_ec, p["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", x_ec, p["w_up"])
    y_ec = jnp.einsum("ecf,efd->ecd", h, p["w_down"])  # (E, C, d)
    y_ec = constrain(y_ec, "moe_ecd")

    # ---- combine: scatter-add back to tokens, weighted by gates ----
    # keep the combine in the activation dtype: a f32 combine promotes
    # the cross-expert all-reduce to f32 and doubles its bytes
    # (measured 37.6 GB/layer -> see EXPERIMENTS.md §Perf pair B)
    contrib = (y_ec.astype(x.dtype)
               * w_ec[..., None].astype(x.dtype)).reshape(E * C, d)
    yf = jnp.zeros((G, d), x.dtype).at[idx_ec.reshape(-1)].add(
        contrib, mode="drop")

    if cfg.n_shared_experts:
        yf = yf + mlp(p["shared"], xf, cfg.act)

    # ---- switch-style load-balance auxiliary loss ----
    me = jnp.mean(probs, axis=0)                      # router mass / expert
    ce = jnp.mean(gate_mat > 0, axis=0)               # token fraction / expert
    aux = cfg.router_aux_weight * E * jnp.sum(me * ce)
    return yf.reshape(B, S, d), aux
