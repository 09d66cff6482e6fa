"""The Zamba2-style hybrid (``family: hybrid``): the Mamba2 stack of
``ssm.py`` plus one weight-shared attention block (RMS norms, attention
with rotary embeddings, gated MLP) applied before every ``attn_every``-th
Mamba2 layer, each application with a KV cache of its own.  Weights
follow ``ssm.py``'s rules."""
from __future__ import annotations

from typing import Any, Dict

from bench.families import ssm


def _apps(cfg: dict) -> int:
    return -(-cfg["num_layers"] // cfg["attn_every"])


def _head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["num_heads"]


def param_shapes(cfg: dict) -> Dict[str, Any]:
    d, H, KV, F = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                   cfg["d_ff"])
    hd = _head_dim(cfg)
    tree = ssm.param_shapes(cfg)
    tree["shared_attn"] = {
        "ln1": {"scale": (d,)}, "ln2": {"scale": (d,)},
        "attn": {"wq": (d, H, hd), "wk": (d, KV, hd), "wv": (d, KV, hd),
                 "wo": (H, hd, d)},
        "mlp": {"w_gate": (d, F), "w_up": (d, F), "w_down": (F, d)}}
    return tree


def cache_shapes(cfg: dict, slots: int, max_seq: int) -> Dict[str, Any]:
    out = ssm.cache_shapes(cfg, slots, max_seq)
    kv = ((_apps(cfg), slots, max_seq, cfg["num_kv_heads"], _head_dim(cfg)),
          cfg["compute_dtype"])
    out.update(k=kv, v=kv)
    return out


init_leaf = ssm.init_leaf
