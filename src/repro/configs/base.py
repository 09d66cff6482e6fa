"""ModelConfig — the single config type every architecture instantiates.

Configs are frozen dataclasses; ``smoke()`` returns the reduced variant used
by CPU smoke tests (same family, tiny dims).  Input shapes (the assigned
4-shape grid) live in :mod:`repro.configs.shapes`.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | encdec | vlm | hybrid | ssm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // num_heads

    # MoE
    num_experts: int = 0                 # the router's width
    experts_per_token: int = 0
    moe_capacity_factor: float = 1.25    # 0: dropless (grouped matmuls)
    moe_dense_residual: bool = False     # arctic: dense FFN residual alongside MoE
    experts_held: int = 0                # routed experts this chip holds; 0: all
    expert_offset: int = 0               # id of the first expert held
    n_shared_experts: int = 0            # always-on experts: one MLP n x d_ff wide
    first_k_dense_replace: int = 0       # leading layers with a dense MLP ...
    dense_d_ff: int = 0                  # ... this wide
    scoring_func: str = "softmax"        # softmax | sigmoid (+ a correction
                                         # bias for the choice only: noaux_tc)
    routed_scaling_factor: float = 1.0   # routed experts' gates scaled by this

    # multi-head latent attention (deepseek-v2/v3): kv_lora_rank > 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # SSM (mamba2 / zamba2)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    ssm_conv_width: int = 4

    # attention details
    qkv_bias: bool = False               # qwen1.5
    gated_mlp: bool = True               # False -> LayerNorm+GeLU (starcoder2)
    norm: str = "rmsnorm"                # rmsnorm | layernorm
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_embeddings: bool = False

    # enc-dec
    enc_layers: int = 0                  # >0 -> encoder-decoder
    src_ratio: int = 4                   # encoder frames = seq // src_ratio

    # hybrid (zamba2)
    attn_every: int = 0                  # shared attention block period

    # modality frontend stubs ([audio]/[vlm]): precomputed embeddings
    frontend: str = "none"               # none | audio | vision
    frontend_tokens: int = 0             # patches prepended to the text seq

    # numerics / execution
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    remat: str = "dots"                  # none | dots | full
    optimizer: str = "adamw"             # adamw | adafactor
    micro_batches: int = 1               # gradient-accumulation steps

    # sharding rule overrides (logical axis -> mesh axes tuple / None),
    # applied on top of launch.mesh defaults; decode overrides stack on top.
    rules: Tuple[Tuple[str, Optional[Tuple[str, ...]]], ...] = ()
    decode_rules: Tuple[Tuple[str, Optional[Tuple[str, ...]]], ...] = ()
    # keep FSDP (embed->data) weight sharding at inference: only for models
    # whose TP-only shard does not fit one chip (EXPERIMENTS.md §Perf #2)
    inference_embed_fsdp: bool = False

    # documentation
    source: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(1, self.num_heads))

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def is_encdec(self) -> bool:
        return self.enc_layers > 0

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """long_500k runs only for sub-quadratic families (DESIGN.md §4.2)."""
        return self.family in ("ssm", "hybrid")

    def smoke(self) -> "ModelConfig":
        """Reduced same-family config for CPU smoke tests."""
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            num_layers=min(self.num_layers, 2),
            enc_layers=min(self.enc_layers, 2) if self.enc_layers else 0,
            d_model=64,
            num_heads=4,
            num_kv_heads=min(self.num_kv_heads, 2) if self.num_kv_heads else 0,
            head_dim=16,
            d_ff=96,
            vocab_size=257,
            num_experts=min(self.num_experts, 4) if self.num_experts else 0,
            experts_per_token=min(self.experts_per_token, 2) if self.experts_per_token else 0,
            experts_held=min(self.experts_held, 2) if self.experts_held else 0,
            expert_offset=2 if self.expert_offset else 0,
            first_k_dense_replace=min(self.first_k_dense_replace, 1),
            dense_d_ff=128 if self.dense_d_ff else 0,
            kv_lora_rank=32 if self.kv_lora_rank else 0,
            qk_nope_head_dim=16 if self.qk_nope_head_dim else 0,
            qk_rope_head_dim=8 if self.qk_rope_head_dim else 0,
            v_head_dim=16 if self.v_head_dim else 0,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_head_dim=16,
            ssm_chunk=8,
            attn_every=2 if self.attn_every else 0,
            frontend_tokens=min(self.frontend_tokens, 8) if self.frontend_tokens else 0,
            param_dtype="float32",
            compute_dtype="float32",
            remat="none",
            micro_batches=1,
        )


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    mode: str            # train | prefill | decode

    def smoke(self) -> "InputShape":
        return InputShape(self.name + "-smoke", seq_len=32, global_batch=2,
                          mode=self.mode)
