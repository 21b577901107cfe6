"""Architecture configuration.

One frozen dataclass describes every assigned architecture; the
decoder in ``transformer.py`` is driven entirely by it.  Layer layout
is expressed as a repeating ``layer_pattern`` of block kinds:

    "attn"        global causal attention + (dense or MoE) FFN
    "attn_local"  sliding-window attention + FFN
    "mla"         multi-head latent attention (DeepSeek) + FFN
    "mamba"       Mamba-1 selective-SSM mixer (no separate FFN)
    "rglru"       RG-LRU recurrent mixer + FFN

The pattern repeats floor(L / len(pattern)) times (lowered as a
jax.lax.scan over stacked parameters); the L % len(pattern) remainder
layers are applied unrolled from the pattern prefix.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    arch_type: str  # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int

    # --- layer layout -------------------------------------------------
    layer_pattern: Tuple[str, ...] = ("attn",)
    window: int = 0               # sliding window for attn_local
    ffn_in_pattern: bool = True   # mamba blocks have no FFN

    # --- attention ----------------------------------------------------
    head_dim: Optional[int] = None    # default d_model // n_heads
    rope_theta: float = 1e4
    rope_theta_local: Optional[float] = None  # gemma3 local layers
    rope_fraction: float = 1.0        # stablelm partial rotary
    qk_norm: bool = False             # gemma3
    mrope_sections: Tuple[int, ...] = ()  # qwen2-vl M-RoPE (t, h, w) split
    attn_logit_softcap: float = 0.0
    # YaRN rope scaling of the MLA rope dims (DeepSeek-V2); a factor of
    # 1 is plain rope.  mscale / mscale_all_dim set the cos/sin scale
    # mscale(f, m)/mscale(f, m_all) and the softmax scale mscale(f, m_all)^2
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0

    # --- MoE ------------------------------------------------------------
    n_experts: int = 0
    n_shared_experts: int = 0
    topk: int = 0
    moe_d_ff: int = 0
    first_dense: int = 0          # leading dense layers (DeepSeek)
    capacity_factor: float = 1.25
    router_aux_weight: float = 1e-3
    norm_topk_prob: bool = True   # renormalize the top-k gates to sum 1
    routed_scaling_factor: float = 1.0   # times the routed experts' gates
    # (first id, count) of the routed experts this chip holds: the layer
    # routes over all n_experts and computes only its own experts' part,
    # dropping no token (moe.held_expert_ffn).  None holds all of them
    # in the capacity-based layer of the launch path (moe.moe_ffn).
    experts_held: Optional[Tuple[int, int]] = None

    # --- MLA ------------------------------------------------------------
    q_lora: int = 0
    kv_lora: int = 0
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128

    # --- SSM (mamba-1) ----------------------------------------------------
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_dt_rank: int = 0          # default ceil(d_model / 16)

    # --- RG-LRU -----------------------------------------------------------
    lru_width: int = 0            # default d_model

    # --- modality ---------------------------------------------------------
    modality: str = "text"        # text | vlm | audio
    n_codebooks: int = 4          # audio codebooks (musicgen)

    # --- lowering -----------------------------------------------------------
    # scan-over-layers keeps HLO small (fast compiles); the dry-run
    # sets unroll_layers=True because XLA cost_analysis counts a scan
    # body once — unrolling makes HLO_FLOPs/collective_bytes exact.
    # scan_unroll=k partially unrolls (k body copies per iteration):
    # the dry-run compiles k=1 and k=2 and extrapolates exact totals
    # (F(k) = outside + k*body is affine in k).
    unroll_layers: bool = False
    scan_unroll: int = 1

    # --- numerics / training ----------------------------------------------
    dtype: str = "bfloat16"
    optimizer: str = "adamw"
    learning_rate: float = 3e-4
    remat: bool = True
    use_bias: bool = False
    norm: str = "rmsnorm"
    act: str = "silu"             # silu (gated) | gelu (gated)
    tie_embeddings: bool = False
    citation: str = ""

    # ------------------------------------------------------------------
    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def n_held(self) -> int:
        """Routed experts this chip holds."""
        return (self.experts_held[1] if self.experts_held is not None
                else self.n_experts)

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_dt_rank_(self) -> int:
        return self.ssm_dt_rank or -(-self.d_model // 16)

    @property
    def lru_width_(self) -> int:
        return self.lru_width or self.d_model

    @property
    def n_pattern_repeats(self) -> int:
        return self.n_layers // len(self.layer_pattern)

    @property
    def n_tail_layers(self) -> int:
        return self.n_layers % len(self.layer_pattern)

    @property
    def act_dtype(self):
        return jnp.dtype(self.dtype)

    def scaled(self, **overrides) -> "ArchConfig":
        """Reduced variant of the same family (smoke tests)."""
        return dataclasses.replace(self, **overrides)

    def validate(self) -> None:
        assert self.n_layers >= 1
        if "mla" in self.layer_pattern:
            assert self.kv_lora > 0
        if self.n_experts:
            assert self.topk > 0 and self.moe_d_ff > 0
        if self.experts_held is not None:
            first, count = self.experts_held
            assert 0 <= first and count > 0 and \
                first + count <= self.n_experts
        if "attn_local" in self.layer_pattern:
            assert self.window > 0
        if self.modality == "vlm":
            assert len(self.mrope_sections) == 3
