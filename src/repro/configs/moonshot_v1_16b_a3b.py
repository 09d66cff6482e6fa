"""moonshot-v1-16b-a3b [moe] — Moonlight-16B-A3B (16B, 3B active), the
DeepSeek-V3 architecture (arXiv:2412.19437) at its published values.

27L, d_model=2048, vocab=163840 (untied), max positions 8192.  Attention:
multi-head latent attention (arXiv:2405.04434), 16 heads, no query
compression, kv_lora_rank 512, qk_nope 128 + qk_rope 64, v 128, rope theta
50000.  Layer 0 dense (d_ff 11264); layers 1-26 MoE: 64 routed experts of
width 1408, top-6, plus 2 shared experts; sigmoid router with a correction
bias for the choice (noaux_tc, n_group = topk_group = 1), normalised gates
scaled by 2.446; dropless dispatch; RMSNorm eps 1e-5.
[hf:moonshotai/Moonlight-16B-A3B config.json]
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1408,
    vocab_size=163840,
    num_experts=64,
    experts_per_token=6,
    moe_capacity_factor=0.0,
    n_shared_experts=2,
    first_k_dense_replace=1,
    dense_d_ff=11264,
    scoring_func="sigmoid",
    routed_scaling_factor=2.446,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50000.0,
    rms_norm_eps=1e-5,
    optimizer="adamw",
    decode_rules=(("kv_seq", ("model",)),),
    source="hf:moonshotai/Moonlight-16B-A3B; hf",
)
