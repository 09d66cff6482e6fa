"""Composable transformer layers (pure functions over param pytrees).

Attention is *blockwise* over query blocks (lax.scan + per-block softmax):
memory O(block_q * S) instead of O(S^2), which is what lets prefill_32k
lower without materializing (B,H,S,S).  The models run this jnp path on
every backend; the Pallas kernels in ``repro.kernels`` are tested against
the same semantics but no model calls them.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .specs import ParamSpec
from ..configs.base import ModelConfig


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def norm_specs(cfg: ModelConfig, d: Optional[int] = None) -> Dict[str, ParamSpec]:
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return {"scale": ParamSpec((d,), ("embed",), init="ones"),
                "bias": ParamSpec((d,), ("embed",), init="zeros")}
    return {"scale": ParamSpec((d,), ("embed",), init="ones")}


def apply_norm(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        y = rms_norm(xf, p["scale"], cfg.rms_norm_eps)
    return y.astype(x.dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMS norm in float32 (the caller casts the result back)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs      # (...,S,half)
    sin = jnp.sin(angles)[..., None, :]                            # (...,S,1,half)
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, optional biases, optional KV cache, blockwise softmax)
# ---------------------------------------------------------------------------

def attention_specs(cfg: ModelConfig, cross: bool = False) -> Dict[str, ParamSpec]:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    s: Dict[str, ParamSpec] = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamSpec((h, hd), ("heads", "head_dim"), init="zeros")
        s["bk"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"), init="zeros")
        s["bv"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"), init="zeros")
    return s


def _gqa_scores_block(qb, k, scale):
    # qb: (B, bq, KV, G, hd)  k: (B, Sk, KV, hd) -> (B, KV, G, bq, Sk) f32
    return jnp.einsum("bqkgh,bskh->bkgqs", qb.astype(jnp.float32),
                      k.astype(jnp.float32)) * scale


def _masked_softmax(scores, mask):
    scores = jnp.where(mask, scores, -1e30)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - jax.lax.stop_gradient(m))
    return e / (jnp.sum(e, axis=-1, keepdims=True) + 1e-30)


def cache_write(cache: jax.Array, new: jax.Array,
                positions: jax.Array) -> jax.Array:
    """``cache`` (B, S_max, ...) with ``new`` (B, S, ...) written at each
    row's positions (``positions``: (B, S) or broadcastable, contiguous per
    row from its first).

    No batch-indexed scatter: a scatter keyed on global batch indices forces
    GSPMD to all-gather the whole cache over the batch axis (~8.6 GB/layer
    at 32k prefill — EXPERIMENTS.md §Perf #3).  Positions are contiguous per
    row (offset + arange(S)), so the update is a gather along the UNSHARDED
    step dim + mask blend, which partitions cleanly over batch and kv_seq.
    """
    B, S = new.shape[:2]
    S_max = cache.shape[1]
    offset = jnp.broadcast_to(positions, (B, S)).astype(jnp.int32)[:, 0]
    idx = jnp.arange(S_max, dtype=jnp.int32)[None, :] - offset[:, None]
    in_range = (idx >= 0) & (idx < S)                            # (B, S_max)
    tail = (1,) * (new.ndim - 2)
    take = jnp.clip(idx, 0, S - 1).reshape((B, S_max) + tail)
    src = jnp.take_along_axis(new.astype(cache.dtype), take, axis=1)
    return jnp.where(in_range.reshape((B, S_max) + tail), src, cache)


def over_query_blocks(fn, qs, pos: jax.Array, block_q: int):
    """``fn(qs, pos)`` one block of ``block_q`` queries at a time
    (``lax.map``), so scores take O(block_q * Sk) memory and not O(S * Sk).
    ``qs``: a tuple of arrays with the queries on axis 1, ``pos``: (B, S);
    ``fn`` returns (B, bq, ...)."""
    B, S = pos.shape
    if S <= block_q:
        return fn(qs, pos)
    nb = -(-S // block_q)
    pad = nb * block_q - S

    def split(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return a.reshape((B, nb, block_q) + a.shape[2:]).swapaxes(0, 1)

    out = jax.lax.map(lambda args: fn(*args),
                      (tuple(split(q) for q in qs), split(pos)))
    return out.swapaxes(0, 1).reshape((B, nb * block_q) + out.shape[3:])[:, :S]


def multihead_attention(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array, *,
                        positions: jax.Array,
                        kv_cache: Optional[Dict[str, Any]] = None,
                        causal: bool = True,
                        kv_x: Optional[jax.Array] = None,
                        kv_valid_len: Optional[jax.Array] = None,
                        block_q: int = 512) -> Tuple[jax.Array, Optional[Dict[str, Any]]]:
    """GQA attention.

    x: (B, S, D).  ``kv_x`` switches to cross-attention (keys/values from the
    encoder; no cache update, no causal mask).  ``kv_cache``:
    {"k": (B, S_max, KV, hd), "v": ..., } plus per-batch write position in
    ``positions`` — decode updates the cache by scatter at ``positions``.
    """
    B, S, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    g = h // kv
    scale = 1.0 / np.sqrt(hd)

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)

    if kv_x is None:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
        if "bk" in p:
            k = k + p["bk"].astype(k.dtype)
            v = v + p["bv"].astype(v.dtype)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    else:
        # cross-attention: no rope on encoder memory, keys computed fresh
        k = jnp.einsum("bsd,dhk->bshk", kv_x, p["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", kv_x, p["wv"].astype(x.dtype))
        causal = False

    new_cache = None
    if kv_cache is not None and kv_x is None:
        ck = cache_write(kv_cache["k"], k, positions)
        cv = cache_write(kv_cache["v"], v, positions)
        new_cache = {"k": ck, "v": cv}
        k, v = ck, cv

    Sk = k.shape[1]
    k_pos = jnp.arange(Sk)

    qr = q.reshape(B, S, kv, g, hd)

    def block_attn(qb, qpos):
        # qb: (B, bq, KV, G, hd), qpos: (B, bq)
        scores = _gqa_scores_block(qb, k, scale)                # (B,KV,G,bq,Sk)
        mask = jnp.ones((B, 1, 1, qb.shape[1], Sk), bool)
        if causal:
            mask = mask & (k_pos[None, None, None, None, :]
                           <= qpos[:, None, None, :, None])
        if kv_valid_len is not None:
            mask = mask & (k_pos[None, None, None, None, :]
                           < kv_valid_len[:, None, None, None, None])
        probs = _masked_softmax(scores, mask)
        return jnp.einsum("bkgqs,bskh->bqkgh", probs,
                          v.astype(jnp.float32)).astype(x.dtype)

    ctx = over_query_blocks(lambda qs, qpos: block_attn(qs[0], qpos), (qr,),
                            jnp.broadcast_to(positions, (B, S)), block_q)
    ctx = ctx.reshape(B, S, h, hd)
    out = jnp.einsum("bshk,hkd->bsd", ctx, p["wo"].astype(x.dtype))
    return out, new_cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_specs(cfg: ModelConfig, d_ff: Optional[int] = None
              ) -> Dict[str, ParamSpec]:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.gated_mlp:
        return {"w_gate": ParamSpec((d, f), ("embed", "mlp")),
                "w_up": ParamSpec((d, f), ("embed", "mlp")),
                "w_down": ParamSpec((f, d), ("mlp", "embed"))}
    return {"w_up": ParamSpec((d, f), ("embed", "mlp")),
            "b_up": ParamSpec((f,), ("mlp",), init="zeros"),
            "w_down": ParamSpec((f, d), ("mlp", "embed")),
            "b_down": ParamSpec((d,), ("embed",), init="zeros")}


def apply_mlp(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array) -> jax.Array:
    if cfg.gated_mlp:
        gate = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype)))
        up = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
        return jnp.einsum("bsf,fd->bsd", gate * up, p["w_down"].astype(x.dtype))
    h = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype)) + p["b_up"].astype(x.dtype)
    h = jax.nn.gelu(h)
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype)) \
        + p["b_down"].astype(x.dtype)


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------

def embed_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    s = {"tok": ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                          scale=0.02)}
    if not cfg.tie_embeddings:
        s["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    return s


def embed_tokens(cfg: ModelConfig, p: Dict[str, Any], tokens: jax.Array) -> jax.Array:
    return jnp.take(p["tok"], tokens, axis=0).astype(dtype_of(cfg))


def unembed(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array) -> jax.Array:
    w = p["tok"].T if cfg.tie_embeddings else p["lm_head"]
    return jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype)).astype(jnp.float32)
