"""DeepSeek-V2-Lite 15.7B-A2.4B [arXiv:2405.04434;
https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json].

27L, d_model=2048, 16 heads, MLA without q_lora (kv_lora=512,
qk_nope=128, qk_rope=64, v_head=128), YaRN rope (factor 40 over 4,096
original positions, beta 32/1, mscale = mscale_all_dim = 0.707, theta
1e4), vocab=102400, untied head, RMSNorm eps 1e-6.  MoE: 2 shared + 64
routed experts of width 1408, top-6 softmax routing, gates not
renormalized (``norm_topk_prob: false``), routed scaling 1; the first
layer dense (d_ff=10944).  The rope dims rotate as halves where the
published code first de-interleaves them: a fixed permutation of the
rope columns of w_q / w_kr (models/mla.py).
"""
from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-v2-lite", arch_type="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10944, vocab=102400,
    layer_pattern=("mla",), first_dense=1,
    n_experts=64, n_shared_experts=2, topk=6, moe_d_ff=1408,
    norm_topk_prob=False, routed_scaling_factor=1.0,
    q_lora=0, kv_lora=512, qk_nope_dim=128, qk_rope_dim=64,
    v_head_dim=128,
    rope_theta=1e4, rope_factor=40.0, rope_original_max=4096,
    rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=0.707,
    rope_mscale_all_dim=0.707,
    citation="arXiv:2405.04434",
)


def smoke() -> ArchConfig:
    return CONFIG.scaled(n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
                         d_ff=256, vocab=512, first_dense=1,
                         n_experts=4, topk=2, moe_d_ff=64,
                         kv_lora=32, qk_nope_dim=16, qk_rope_dim=8,
                         v_head_dim=16, n_shared_experts=1,
                         capacity_factor=8.0)
