"""The DeepSeek-V3-style MoE decoder (``family: moe`` with latent
attention): a token table and an untied head, ``first_k_dense_replace``
dense blocks, then MoE blocks, each block an RMS norm, multi-head latent
attention, an RMS norm and its feed-forward part.

A dense block's MLP is ``dense_d_ff`` wide.  A MoE block has the router
over all ``num_experts`` with its correction bias, the ``experts_held``
routed experts of this chip (``d_ff`` wide each) and the shared experts,
one MLP ``n_shared_experts x d_ff`` wide.  The cache keeps, per layer and
slot, the normed latent ``c_kv`` and the rotated key ``k_pe`` of every
position.

Values follow Hugging Face's DeepseekV3 initialisation: every matrix and
the token table normal with std 0.02 (``initializer_range``), norm scales
one, the router's correction bias zero.
"""
from __future__ import annotations

from typing import Any, Dict

STD = 0.02


def _stack(n: int, tree):
    return {k: _stack(n, v) if isinstance(v, dict) else (n,) + v
            for k, v in tree.items()}


def _mlp(d: int, f: int) -> Dict[str, Any]:
    return {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def _block(cfg: dict, ffn: Dict[str, Any]) -> Dict[str, Any]:
    d, h, r = cfg["d_model"], cfg["num_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    attn = {"wq": (d, h, dn + dr), "wkv_a": (d, r + dr),
            "kv_norm": {"scale": (r,)}, "wk_b": (r, h, dn),
            "wv_b": (r, h, dv), "wo": (h, dv, d)}
    return dict({"ln1": {"scale": (d,)}, "attn": attn,
                 "ln2": {"scale": (d,)}}, **ffn)


def param_shapes(cfg: dict) -> Dict[str, Any]:
    d, f, e = cfg["d_model"], cfg["d_ff"], cfg["num_experts"]
    held = cfg.get("experts_held") or e
    k = cfg["first_k_dense_replace"]
    moe = {"router": (d, e), "router_bias": (e,),
           "w_gate": (held, d, f), "w_up": (held, d, f),
           "w_down": (held, f, d),
           "shared": _mlp(d, cfg["n_shared_experts"] * f)}
    tree: Dict[str, Any] = {
        "embed": {"tok": (cfg["vocab_size"], d),
                  "lm_head": (d, cfg["vocab_size"])},
        "final_norm": {"scale": (d,)},
        "blocks": _stack(cfg["num_layers"] - k, _block(cfg, {"moe": moe}))}
    if k:
        tree["dense_blocks"] = _stack(
            k, _block(cfg, {"mlp": _mlp(d, cfg["dense_d_ff"])}))
    return tree


def cache_shapes(cfg: dict, slots: int, max_seq: int) -> Dict[str, Any]:
    lead = (cfg["num_layers"], slots, max_seq)
    return {"pos": ((slots,), "int32"),
            "c_kv": (lead + (cfg["kv_lora_rank"],), cfg["compute_dtype"]),
            "k_pe": (lead + (cfg["qk_rope_head_dim"],), cfg["compute_dtype"])}


def init_leaf(path: str, shape, key, cfg: dict):
    import jax
    import jax.numpy as jnp

    if path.endswith("scale"):
        return jnp.ones(shape, jnp.float32)
    if path.endswith("router_bias"):
        return jnp.zeros(shape, jnp.float32)
    return STD * jax.random.normal(key, shape, jnp.float32)
