"""Multi-head latent attention (MLA; DeepSeek-V2 §2.1, arXiv:2405.04434),
without a query compression (``q_lora_rank`` null), as Moonlight-16B-A3B
and DeepSeek-V3's smaller siblings use it.

Per token and layer the cache keeps one row that all heads share: the
RMS-normed latent ``c_kv`` (``kv_lora_rank`` wide) and the rotated key
``k_pe`` (``qk_rope_head_dim`` wide).  Prefill expands the latent into each
head's keys and values (``wk_b``, ``wv_b``); one query per sequence
(decode) uses the absorbed form instead: the query is taken into the
latent space through ``wk_b``, scores and the context are computed against
the latent rows themselves, and ``wv_b`` is applied to the context, so the
cache is never expanded.  Both give the same scores:
``q_nope . (c wk_b) = (q_nope wk_b^T) . c``.

Rotary embeddings follow Hugging Face's DeepseekV3 convention: the rope
dims of ``q_pe`` and ``k_pe`` are de-interleaved (even dims, then odd)
before the rotate-half rotation of ``layers.rope``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import layers as L
from .specs import ParamSpec
from ..configs.base import ModelConfig


def mla_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": ParamSpec((d, h, dn + dr), ("embed", "heads", "head_dim")),
        # the latent and the shared rope key, one projection
        "wkv_a": ParamSpec((d, r + dr), ("embed", None)),
        "kv_norm": {"scale": ParamSpec((r,), (None,), init="ones")},
        "wk_b": ParamSpec((r, h, dn), (None, "heads", "head_dim")),
        "wv_b": ParamSpec((r, h, dv), (None, "heads", "head_dim")),
        "wo": ParamSpec((h, dv, d), ("heads", "head_dim", "embed")),
    }


def rope_deinterleaved(x: jax.Array, positions: jax.Array,
                       theta: float) -> jax.Array:
    """x: (..., S, H, d): even dims then odd dims, then rotate halves."""
    d = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (d // 2, 2)).swapaxes(-1, -2)
    return L.rope(x.reshape(x.shape[:-2] + (d,)), positions, theta)


def _dot(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """Scores and context in float32, as ``layers`` computes them."""
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32))


def mla_attention(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array, *,
                  positions: jax.Array,
                  cache: Optional[Dict[str, Any]] = None,
                  kv_valid_len: Optional[jax.Array] = None,
                  block_q: int = 512
                  ) -> Tuple[jax.Array, Optional[Dict[str, Any]]]:
    """x: (B, S, D) -> (B, S, D), and the cache ({"c_kv": (B, S_max, r),
    "k_pe": (B, S_max, dr)}) with this call's rows written at
    ``positions``.  Causal; keys at or beyond ``kv_valid_len`` are
    masked."""
    B, S, _ = x.shape
    dt = x.dtype
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    scale = 1.0 / np.sqrt(dn + cfg.qk_rope_head_dim)
    with jax.named_scope("mla.attend"):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
        q_nope = q[..., :dn]
        q_pe = rope_deinterleaved(q[..., dn:], positions, cfg.rope_theta)
        kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(dt))
        c_kv = L.rms_norm(kv[..., :r], p["kv_norm"]["scale"],
                          cfg.rms_norm_eps).astype(dt)
        k_pe = rope_deinterleaved(kv[..., None, r:], positions,
                                  cfg.rope_theta)[:, :, 0]
        new_cache = None
        if cache is not None:
            c_kv = L.cache_write(cache["c_kv"], c_kv, positions)
            k_pe = L.cache_write(cache["k_pe"], k_pe, positions)
            new_cache = {"c_kv": c_kv, "k_pe": k_pe}
        c_kv, k_pe = c_kv.astype(dt), k_pe.astype(dt)
        Sk = c_kv.shape[1]
        k_pos = jnp.arange(Sk)

        def probs(scores, qpos):
            mask = k_pos[None, None, None, :] <= qpos[:, None, :, None]
            if kv_valid_len is not None:
                mask = mask & (k_pos[None, None, None, :]
                               < kv_valid_len[:, None, None, None])
            return L._masked_softmax(scores * scale, mask)

        if S == 1 and cache is not None:
            # absorbed: scores and context against the latent rows
            q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["wk_b"].astype(dt))
            pr = probs(_dot("bshr,btr->bhst", q_lat, c_kv)
                       + _dot("bshk,btk->bhst", q_pe, k_pe),
                       jnp.broadcast_to(positions, (B, S)))
            ctx_lat = _dot("bhst,btr->bshr", pr, c_kv).astype(dt)
            ctx = jnp.einsum("bshr,rhk->bshk", ctx_lat, p["wv_b"].astype(dt))
        else:
            # expanded: each head's keys and values from the latent
            k_nope = jnp.einsum("btr,rhk->bthk", c_kv, p["wk_b"].astype(dt))
            v = jnp.einsum("btr,rhk->bthk", c_kv, p["wv_b"].astype(dt))

            def block(qs, qpos):
                qn, qp = qs
                pr = probs(_dot("bshk,bthk->bhst", qn, k_nope)
                           + _dot("bshk,btk->bhst", qp, k_pe), qpos)
                return _dot("bhst,bthk->bshk", pr, v).astype(dt)

            ctx = L.over_query_blocks(block, (q_nope, q_pe),
                                      jnp.broadcast_to(positions, (B, S)),
                                      block_q)
        out = jnp.einsum("bshk,hkd->bsd", ctx, p["wo"].astype(dt))
    return out, new_cache
