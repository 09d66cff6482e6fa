"""Decoder-only language models: dense / MoE / VLM / SSM / hybrid.

One parameterized implementation composes the block zoo:
  dense   — [norm, GQA attn, norm, (Swi)GLU MLP] x L        (llama/qwen/granite/starcoder/phi3)
  moe     — MLP replaced by top-k expert layer (+ optional dense residual, arctic;
            leading dense layers, shared experts and latent attention (MLA),
            moonlight)
  vlm     — dense backbone; precomputed patch embeddings prepended (phi-3-vision)
  ssm     — [norm, Mamba2 SSD] x L                           (mamba2)
  hybrid  — Mamba2 stack + one weight-SHARED attention block every
            ``attn_every`` layers (zamba2)

Layers are stacked on a leading axis and executed with ``lax.scan`` so HLO
size is depth-independent; remat policy per config.  All functions are pure;
state (KV caches, SSM states) is explicit — the nested Train/Serve state
trees are exactly the pointer-chain trees the deep-copy engine manages.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import layers as L
from . import mla as MLA
from . import moe as MOE
from . import ssm as SSM
from .pspec import constrain
from .specs import ParamSpec, init_params, abstract_params, param_axes, is_spec
from ..configs.base import ModelConfig


# ---------------------------------------------------------------------------
# parameter spec trees
# ---------------------------------------------------------------------------

def _stack(spec_tree: Any, n: int) -> Any:
    return jax.tree_util.tree_map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init,
                            s.scale, s.dtype),
        spec_tree, is_leaf=is_spec)


def _attn_block_specs(cfg: ModelConfig, moe: bool = True) -> Dict[str, Any]:
    """One attention block; ``moe=False`` is a leading dense layer of a MoE
    model (``first_k_dense_replace``), ``dense_d_ff`` wide."""
    attn = MLA.mla_specs(cfg) if cfg.kv_lora_rank else L.attention_specs(cfg)
    block = {"ln1": L.norm_specs(cfg), "attn": attn, "ln2": L.norm_specs(cfg)}
    if cfg.family != "moe":
        block["mlp"] = L.mlp_specs(cfg)
    elif not moe:
        block["mlp"] = L.mlp_specs(cfg, cfg.dense_d_ff)
    else:
        block["moe"] = MOE.moe_specs(cfg)
        if cfg.moe_dense_residual:
            block["mlp"] = L.mlp_specs(cfg)
    return block


def _n_dense(cfg: ModelConfig) -> int:
    return cfg.first_k_dense_replace if cfg.family == "moe" else 0


def _ssm_block_specs(cfg: ModelConfig) -> Dict[str, Any]:
    return {"ln1": L.norm_specs(cfg), "ssm": SSM.ssm_specs(cfg)}


def spec_tree(cfg: ModelConfig) -> Dict[str, Any]:
    tree: Dict[str, Any] = {"embed": L.embed_specs(cfg),
                            "final_norm": L.norm_specs(cfg)}
    if cfg.family in ("dense", "moe", "vlm"):
        k = _n_dense(cfg)
        if k:
            tree["dense_blocks"] = _stack(_attn_block_specs(cfg, moe=False), k)
        tree["blocks"] = _stack(_attn_block_specs(cfg), cfg.num_layers - k)
    elif cfg.family == "ssm":
        tree["blocks"] = _stack(_ssm_block_specs(cfg), cfg.num_layers)
    elif cfg.family == "hybrid":
        tree["blocks"] = _stack(_ssm_block_specs(cfg), cfg.num_layers)
        shared = {"ln1": L.norm_specs(cfg), "attn": L.attention_specs(cfg),
                  "ln2": L.norm_specs(cfg), "mlp": L.mlp_specs(cfg)}
        tree["shared_attn"] = shared
    else:
        raise ValueError(f"lm.py does not build family {cfg.family!r}")
    if cfg.frontend == "vision":
        tree["vision_proj"] = {
            "w": ParamSpec((cfg.d_model, cfg.d_model), ("embed", "embed_out"))}
    return tree


def init(cfg: ModelConfig, key) -> Any:
    return init_params(spec_tree(cfg), key, jnp.dtype(cfg.param_dtype))


def abstract(cfg: ModelConfig) -> Any:
    return abstract_params(spec_tree(cfg), jnp.dtype(cfg.param_dtype))


def axes(cfg: ModelConfig) -> Any:
    return param_axes(spec_tree(cfg))


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _n_shared_apps(cfg: ModelConfig) -> int:
    return -(-cfg.num_layers // cfg.attn_every) if cfg.attn_every else 0


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, abstract_only=False):
    """Serve-state tree: the pointer-chain tree the decode step touches."""
    kv_dtype = jnp.dtype(cfg.compute_dtype)
    mk = (jax.ShapeDtypeStruct if abstract_only
          else lambda sh, dt: jnp.zeros(sh, dt))
    kvhd = (cfg.num_kv_heads, cfg.resolved_head_dim)
    cache: Dict[str, Any] = {"pos": mk((batch,), jnp.int32)}
    if cfg.family in ("dense", "moe", "vlm") and cfg.kv_lora_rank:
        # one latent row per token and layer, shared by every head
        lead = (cfg.num_layers, batch, max_seq)
        cache["c_kv"] = mk(lead + (cfg.kv_lora_rank,), kv_dtype)
        cache["k_pe"] = mk(lead + (cfg.qk_rope_head_dim,), kv_dtype)
    elif cfg.family in ("dense", "moe", "vlm"):
        cache["k"] = mk((cfg.num_layers, batch, max_seq) + kvhd, kv_dtype)
        cache["v"] = mk((cfg.num_layers, batch, max_seq) + kvhd, kv_dtype)
    elif cfg.family == "ssm":
        cache["state"] = mk((cfg.num_layers, batch, cfg.ssm_heads,
                             cfg.ssm_head_dim, cfg.ssm_state), jnp.float32)
        cache["conv"] = mk((cfg.num_layers, batch, cfg.ssm_conv_width - 1,
                            cfg.d_inner), kv_dtype)
    elif cfg.family == "hybrid":
        napps = _n_shared_apps(cfg)
        cache["state"] = mk((cfg.num_layers, batch, cfg.ssm_heads,
                             cfg.ssm_head_dim, cfg.ssm_state), jnp.float32)
        cache["conv"] = mk((cfg.num_layers, batch, cfg.ssm_conv_width - 1,
                            cfg.d_inner), kv_dtype)
        cache["k"] = mk((napps, batch, max_seq) + kvhd, kv_dtype)
        cache["v"] = mk((napps, batch, max_seq) + kvhd, kv_dtype)
    return cache


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _attn_block(cfg, p, x, *, positions, cache, kv_valid_len, aux):
    h = L.apply_norm(cfg, p["ln1"], x)
    if cfg.kv_lora_rank:
        attn_out, new_cache = MLA.mla_attention(
            cfg, p["attn"], h, positions=positions, cache=cache,
            kv_valid_len=kv_valid_len)
    else:
        attn_out, new_cache = L.multihead_attention(
            cfg, p["attn"], h, positions=positions, kv_cache=cache,
            kv_valid_len=kv_valid_len)
    x = x + attn_out
    h = L.apply_norm(cfg, p["ln2"], x)
    if "moe" in p:
        out, moe_aux = MOE.apply_moe(cfg, p["moe"], h)
        aux = aux + moe_aux["moe_aux_loss"]
        if "mlp" in p:        # arctic's dense residual
            out = out + L.apply_mlp(cfg, p["mlp"], h)
    else:
        out = L.apply_mlp(cfg, p["mlp"], h)
    x = constrain(x + out, "batch", None, None)
    return x, new_cache, aux


def _ssm_block(cfg, p, x, *, cache):
    h = L.apply_norm(cfg, p["ln1"], x)
    out, new_cache = SSM.apply_ssm(cfg, p["ssm"], h, cache=cache)
    x = constrain(x + out, "batch", None, None)
    return x, new_cache


def _layer_cache(cache, keys):
    if cache is None:
        return None
    return {k: cache[k] for k in keys if k in cache}


def _scan_attn_blocks(cfg, blocks, x, aux, layer_cache, *, positions,
                      kv_valid_len):
    """lax.scan over one stack of attention blocks."""
    block_fn = _remat(cfg, functools.partial(
        _attn_block, cfg, positions=positions, kv_valid_len=kv_valid_len))

    if layer_cache is None:
        def body_nc(carry, p):
            x, aux = carry
            x, _, aux = block_fn(p, x, cache=None, aux=aux)
            return (x, aux), None
        (x, aux), _ = jax.lax.scan(body_nc, (x, aux), blocks)
        return x, None, aux

    def body(carry, xs):
        x, aux = carry
        p, c = xs
        x, new_c, aux = block_fn(p, x, cache=c, aux=aux)
        return (x, aux), new_c

    (x, aux), new_cache = jax.lax.scan(body, (x, aux), (blocks, layer_cache))
    return x, new_cache, aux


def _run_attn_stack(cfg, params, x, *, positions, cache, kv_valid_len):
    """The leading dense blocks (if any), then the stacked attention blocks
    (dense/moe/vlm): one scan each, over their layers of the cache."""
    aux = jnp.zeros((), jnp.float32)
    keys = ("c_kv", "k_pe") if cfg.kv_lora_rank else ("k", "v")
    layer_cache = _layer_cache(cache, keys)
    k = _n_dense(cfg)
    parts = []
    for name, lo, hi in (("dense_blocks", 0, k),
                         ("blocks", k, cfg.num_layers)):
        if hi == lo:
            continue
        part = (None if layer_cache is None
                else {n: v[lo:hi] for n, v in layer_cache.items()})
        x, new_part, aux = _scan_attn_blocks(
            cfg, params[name], x, aux, part, positions=positions,
            kv_valid_len=kv_valid_len)
        parts.append(new_part)
    if layer_cache is None:
        return x, None, aux
    if len(parts) == 1:
        return x, parts[0], aux
    return x, {n: jnp.concatenate([p[n] for p in parts]) for n in keys}, aux


def _run_ssm_stack(cfg, params, x, *, positions, cache, kv_valid_len):
    """Scan over Mamba2 blocks; for hybrid, the shared attention block is
    applied every ``attn_every`` layers with per-application KV caches."""
    hybrid = cfg.family == "hybrid"
    shared = params.get("shared_attn")
    blocks = params["blocks"]
    nl = cfg.num_layers

    layer_cache = _layer_cache(cache, ("state", "conv"))
    attn_cache = _layer_cache(cache, ("k", "v")) if hybrid else None

    def apply_shared(x, app_idx, attn_cache):
        h = L.apply_norm(cfg, shared["ln1"], x)
        c = None
        if attn_cache is not None:
            c = {"k": jax.lax.dynamic_index_in_dim(attn_cache["k"], app_idx, 0,
                                                   keepdims=False),
                 "v": jax.lax.dynamic_index_in_dim(attn_cache["v"], app_idx, 0,
                                                   keepdims=False)}
        out, new_c = L.multihead_attention(cfg, shared["attn"], h,
                                           positions=positions, kv_cache=c,
                                           kv_valid_len=kv_valid_len)
        x = x + out
        h = L.apply_norm(cfg, shared["ln2"], x)
        x = x + L.apply_mlp(cfg, shared["mlp"], h)
        if attn_cache is not None and new_c is not None:
            attn_cache = {
                "k": jax.lax.dynamic_update_index_in_dim(
                    attn_cache["k"], new_c["k"].astype(attn_cache["k"].dtype),
                    app_idx, 0),
                "v": jax.lax.dynamic_update_index_in_dim(
                    attn_cache["v"], new_c["v"].astype(attn_cache["v"].dtype),
                    app_idx, 0)}
        return x, attn_cache

    def body(carry, xs):
        x, attn_c, i = carry
        p, c = xs
        if hybrid:
            def with_attn(operands):
                x, attn_c = operands
                return apply_shared(x, i // cfg.attn_every, attn_c)
            x, attn_c = jax.lax.cond(
                jnp.equal(jnp.mod(i, cfg.attn_every), 0) if cfg.attn_every else False,
                with_attn, lambda o: o, (x, attn_c))
        x, new_c = _remat(cfg, functools.partial(_ssm_block, cfg))(p, x, cache=c)
        if new_c is None:
            new_c = 0
        return (x, attn_c, i + 1), new_c

    if layer_cache is None:
        def body_nc(carry, p):
            x, attn_c, i = carry
            if hybrid:
                def with_attn(operands):
                    x, attn_c = operands
                    return apply_shared(x, i // cfg.attn_every, attn_c)
                x, attn_c = jax.lax.cond(
                    jnp.equal(jnp.mod(i, cfg.attn_every), 0),
                    with_attn, lambda o: o, (x, attn_c))
            x, _ = _remat(cfg, functools.partial(_ssm_block, cfg))(p, x, cache=None)
            return (x, attn_c, i + 1), 0
        (x, attn_c, _), _ = jax.lax.scan(
            body_nc, (x, attn_cache, jnp.int32(0)), blocks)
        return x, None, jnp.zeros((), jnp.float32)

    (x, attn_c, _), new_layer_cache = jax.lax.scan(
        body, (x, attn_cache, jnp.int32(0)), (blocks, layer_cache))
    new_cache = dict(new_layer_cache)
    if hybrid and attn_c is not None:
        new_cache.update(attn_c)
    return x, new_cache, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, params, tokens, *, positions=None, cache=None,
            patches=None, kv_valid_len=None
            ) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    """tokens: (B, S) -> logits (B, S, V), new_cache, aux_loss."""
    B, S = tokens.shape
    x = L.embed_tokens(cfg, params["embed"], tokens)
    if cfg.frontend == "vision" and patches is not None:
        pe = jnp.einsum("bpd,de->bpe", patches.astype(x.dtype),
                        params["vision_proj"]["w"].astype(x.dtype))
        x = jnp.concatenate([pe, x], axis=1)
        S = x.shape[1]
    if positions is None:
        positions = jnp.arange(S)[None, :]
    x = constrain(x, "batch", None, None)

    if cfg.family in ("dense", "moe", "vlm"):
        x, new_cache, aux = _run_attn_stack(cfg, params, x,
                                            positions=positions, cache=cache,
                                            kv_valid_len=kv_valid_len)
    else:
        x, new_cache, aux = _run_ssm_stack(cfg, params, x,
                                           positions=positions, cache=cache,
                                           kv_valid_len=kv_valid_len)

    x = L.apply_norm(cfg, params["final_norm"], x)
    if cfg.frontend == "vision" and patches is not None:
        x = x[:, patches.shape[1]:]      # logits over text positions only
    logits = L.unembed(cfg, params["embed"], x)
    logits = constrain(logits, "batch", None, "vocab")
    if new_cache is not None and cache is not None:
        new_cache["pos"] = cache["pos"] + S
    return logits, new_cache, aux


def loss_fn(cfg: ModelConfig, params, batch, rng=None):
    """Cross-entropy LM loss. batch: {"tokens", "labels", optional "patches"}."""
    logits, _, aux = forward(cfg, params, batch["tokens"],
                             patches=batch.get("patches"))
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    labels = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux_loss": aux,
                   "tokens": jnp.sum(mask)}


def prefill(cfg: ModelConfig, params, tokens, cache, *, patches=None):
    """Fill the KV/SSM caches from a prompt; returns last-token logits."""
    B, S = tokens.shape
    extra = patches.shape[1] if patches is not None else 0
    positions = jnp.arange(S + extra)[None, :] + cache["pos"][:, None]
    core = {k: v for k, v in cache.items() if k != "pos"}
    valid = cache["pos"] + S + extra
    logits, new_core, _ = forward(cfg, params, tokens, positions=positions,
                                  cache=dict(core, pos=cache["pos"]),
                                  patches=patches, kv_valid_len=valid)
    new_core = new_core or {}
    new_cache = dict(new_core)
    new_cache["pos"] = valid
    return logits[:, -1:], new_cache


def decode_step(cfg: ModelConfig, params, tokens, cache):
    """One token per sequence against the cache. tokens: (B, 1)."""
    positions = cache["pos"][:, None]
    core = {k: v for k, v in cache.items() if k != "pos"}
    valid = cache["pos"] + 1
    logits, new_core, _ = forward(cfg, params, tokens, positions=positions,
                                  cache=dict(core, pos=cache["pos"]),
                                  kv_valid_len=valid)
    new_cache = dict(new_core or {})
    new_cache["pos"] = valid
    return logits, new_cache
