"""The Mamba2 stack (``family: ssm``): ``L`` blocks of an RMS norm and a
Mamba2 SSD mixer between a token table and a final norm.

Values follow Mamba2's published initialisation (arXiv:2405.21060, the
reference ``mamba_ssm`` module): ``A = -U[1, 16]``, ``dt`` log-uniform in
[1e-3, 1e-1] through the inverse softplus in ``dt_bias``, ``D = 1``,
depthwise conv weights uniform in +-1/sqrt(width), matrices normal with
std 1/sqrt(fan_in), and the residual output projections further scaled by
1/sqrt(num_layers) (``rescale_prenorm_residual``).  The token table has
std 0.02.  ``hybrid.py`` reuses these rules for its shared block.
"""
from __future__ import annotations

import math
from typing import Any, Dict

DT_MIN, DT_MAX = 1e-3, 1e-1


def _dims(cfg: dict):
    d = cfg["d_model"]
    di = cfg["ssm_expand"] * d
    return (d, di, cfg["ssm_state"], di // cfg["ssm_head_dim"],
            cfg["ssm_conv_width"], cfg["num_layers"])


def param_shapes(cfg: dict) -> Dict[str, Any]:
    d, di, n, nh, w, L = _dims(cfg)
    tree: Dict[str, Any] = {
        "embed": {"tok": (cfg["vocab_size"], d)},
        "final_norm": {"scale": (d,)},
        "blocks": {
            "ln1": {"scale": (L, d)},
            "ssm": {"wz": (L, d, di), "wx": (L, d, di), "wB": (L, d, n),
                    "wC": (L, d, n), "wdt": (L, d, nh), "dt_bias": (L, nh),
                    "A_log": (L, nh), "D": (L, nh), "conv_w": (L, w, di),
                    "conv_b": (L, di), "out_norm": (L, di),
                    "wo": (L, di, d)}},
    }
    if not cfg.get("tie_embeddings"):
        tree["embed"]["lm_head"] = (d, cfg["vocab_size"])
    return tree


def cache_shapes(cfg: dict, slots: int, max_seq: int) -> Dict[str, Any]:
    """The SSM state (float32) and the conv window over the x branch (the
    program's conv covers x only) of every layer, and each slot's
    position."""
    d, di, n, nh, w, L = _dims(cfg)
    return {"pos": ((slots,), "int32"),
            "state": ((L, slots, nh, cfg["ssm_head_dim"], n), "float32"),
            "conv": ((L, slots, w - 1, di), cfg["compute_dtype"])}


def init_leaf(path: str, shape, key, cfg: dict):
    import jax
    import jax.numpy as jnp

    name = path.rsplit(".", 1)[-1]
    if name in ("scale", "out_norm", "D"):
        return jnp.ones(shape, jnp.float32)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name in ("conv_w", "conv_b"):
        lim = 1.0 / math.sqrt(cfg["ssm_conv_width"])
        return jax.random.uniform(key, shape, jnp.float32, -lim, lim)
    if name == "tok":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    # matrices: fan_in is every axis but the output ones
    if path.endswith("attn.wo"):
        fan_in = shape[0] * shape[1]
    elif path.startswith("blocks."):
        fan_in = shape[1]
    else:
        fan_in = shape[0]
    std = 1.0 / math.sqrt(fan_in)
    if name in ("wo", "w_down"):
        std /= math.sqrt(cfg["num_layers"])
    return std * jax.random.normal(key, shape, jnp.float32)
