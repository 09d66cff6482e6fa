"""Per-layer cost probes — correcting XLA's scan-body-counted-once.

``compiled.cost_analysis()`` counts a ``while``-loop body ONCE regardless of
trip count (verified empirically; see EXPERIMENTS.md §Dry-run caveats), so a
scan-over-layers model under-reports flops/bytes/collectives by ~L.  For
each (arch, shape, mesh) cell we additionally lower ONE layer block with the
same sharding rules and mode — train probes fwd+bwd, decode probes include
the per-layer KV/SSM cache traffic (the dominant decode term) — giving:

    corrected_term = raw_term + (trips - 1) * body_term        (per body kind)

Hybrid models have two body kinds (mamba x L, shared-attn x ceil(L/k) — the
scan's lax.cond embeds each branch once in the raw HLO); enc-dec has enc/dec
bodies.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from ..configs.base import InputShape, ModelConfig
from ..models import layers as L
from ..models import encdec as encdec_mod
from ..models import lm as lm_mod
from ..models.registry import ModelApi
from ..models.specs import abstract_params, param_axes
from . import hlo_analysis
from .mesh import tree_shardings


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _cost(fn, args, in_sh) -> Dict[str, float]:
    lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
    compiled = lowered.compile()
    cost = hlo_analysis.cost_dict(compiled)
    coll = hlo_analysis.collective_stats(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "collective_bytes": float(coll["total_bytes"]),
            "collective_count": int(coll["total_count"])}


def _grad_wrap(apply_fn, n_diff: int, cfg=None):
    """fwd+bwd probe: differentiate wrt the first n_diff args.  The config's
    remat policy is applied so recompute flops appear in the probe exactly as
    they do inside the real scan body."""
    if cfg is not None:
        apply_fn = lm_mod._remat(cfg, apply_fn)

    def probe(*args):
        def loss(*a):
            return jnp.sum(apply_fn(*a).astype(jnp.float32))
        return jax.grad(loss, argnums=tuple(range(n_diff)))(*args)
    return probe


def layer_bodies(api: ModelApi, shape: InputShape, mesh, rules
                 ) -> List[Dict[str, Any]]:
    """Lower each distinct layer body once; return [{kind, trips, costs}]."""
    cfg = api.cfg
    mode = shape.mode
    B = shape.global_batch
    S = 1 if mode == "decode" else shape.seq_len
    S_cache = shape.seq_len
    cdt = jnp.dtype(cfg.compute_dtype)
    pdt = jnp.dtype(cfg.param_dtype)
    out: List[Dict[str, Any]] = []

    def sh(axes, abs_tree):
        return tree_shardings(mesh, axes, rules, abs_tree)

    x_abs = _sds((B, S, cfg.d_model), cdt)
    x_sh = sh(("batch", None, None), x_abs)
    pos_abs = _sds((B, S), jnp.int32)
    pos_sh = sh(("batch", None), pos_abs)
    kv_shape = (B, S_cache, cfg.num_kv_heads, cfg.resolved_head_dim)
    kv_axes = ("batch", "kv_seq", "kv_heads", "head_dim")

    def attn_cache():
        if cfg.kv_lora_rank:        # latent attention: one row per token
            lat = ("batch", "kv_seq", None)
            abs_c = {"c_kv": _sds((B, S_cache, cfg.kv_lora_rank), cdt),
                     "k_pe": _sds((B, S_cache, cfg.qk_rope_head_dim), cdt)}
            return abs_c, sh({"c_kv": lat, "k_pe": lat}, abs_c)
        abs_c = {"k": _sds(kv_shape, cdt), "v": _sds(kv_shape, cdt)}
        sh_c = sh({"k": kv_axes, "v": kv_axes}, abs_c)
        return abs_c, sh_c

    def record(kind, trips, fn, args, in_sh):
        out.append({"kind": kind, "trips": trips, **_cost(fn, args, in_sh)})

    # ---------------- dense / moe / vlm ----------------
    if cfg.family in ("dense", "moe", "vlm"):
        k = lm_mod._n_dense(cfg)
        for kind, moe, trips in (("dense_block", False, k),
                                 ("attn_block", True, cfg.num_layers - k)):
            if not trips:
                continue
            spec = lm_mod._attn_block_specs(cfg, moe=moe)
            p_abs = abstract_params(spec, pdt)
            p_sh = sh(param_axes(spec), p_abs)

            if mode == "train":
                def apply_fn(p, x, pos):
                    y, _, _ = lm_mod._attn_block(
                        cfg, p, x, positions=pos, cache=None,
                        kv_valid_len=None, aux=jnp.zeros((), jnp.float32))
                    return y
                record(kind, trips, _grad_wrap(apply_fn, 2, cfg),
                       (p_abs, x_abs, pos_abs), (p_sh, x_sh, pos_sh))
            else:
                c_abs, c_sh = attn_cache()

                def apply_fn(p, x, pos, cache):
                    y, _, _ = lm_mod._attn_block(
                        cfg, p, x, positions=pos, cache=cache,
                        kv_valid_len=pos[:, -1] + 1,
                        aux=jnp.zeros((), jnp.float32))
                    return y
                record(kind, trips, apply_fn,
                       (p_abs, x_abs, pos_abs, c_abs),
                       (p_sh, x_sh, pos_sh, c_sh))

    # ---------------- ssm / hybrid ----------------
    elif cfg.family in ("ssm", "hybrid"):
        spec = lm_mod._ssm_block_specs(cfg)
        p_abs = abstract_params(spec, pdt)
        p_sh = sh(param_axes(spec), p_abs)

        if mode == "train":
            def apply_ssm(p, x):
                y, _ = lm_mod._ssm_block(cfg, p, x, cache=None)
                return y
            record("ssm_block", cfg.num_layers, _grad_wrap(apply_ssm, 2, cfg),
                   (p_abs, x_abs), (p_sh, x_sh))
        else:
            sc_abs = {"state": _sds((B, cfg.ssm_heads, cfg.ssm_head_dim,
                                     cfg.ssm_state), jnp.float32),
                      "conv": _sds((B, cfg.ssm_conv_width - 1, cfg.d_inner), cdt)}
            sc_sh = sh({"state": ("batch", "ssm_heads", None, None),
                        "conv": ("batch", None, "ssm_inner")}, sc_abs)

            def apply_ssm(p, x, cache):
                y, _ = lm_mod._ssm_block(cfg, p, x, cache=cache)
                return y
            record("ssm_block", cfg.num_layers, apply_ssm,
                   (p_abs, x_abs, sc_abs), (p_sh, x_sh, sc_sh))

        if cfg.family == "hybrid":
            aspec = {"ln1": L.norm_specs(cfg), "attn": L.attention_specs(cfg),
                     "ln2": L.norm_specs(cfg), "mlp": L.mlp_specs(cfg)}
            pa_abs = abstract_params(aspec, pdt)
            pa_sh = sh(param_axes(aspec), pa_abs)
            napps = lm_mod._n_shared_apps(cfg)

            if mode == "train":
                def apply_attn(p, x, pos):
                    h = L.apply_norm(cfg, p["ln1"], x)
                    o, _ = L.multihead_attention(cfg, p["attn"], h,
                                                 positions=pos)
                    x = x + o
                    h = L.apply_norm(cfg, p["ln2"], x)
                    return x + L.apply_mlp(cfg, p["mlp"], h)
                record("shared_attn", napps, _grad_wrap(apply_attn, 2, cfg),
                       (pa_abs, x_abs, pos_abs), (pa_sh, x_sh, pos_sh))
            else:
                c_abs, c_sh = attn_cache()

                def apply_attn(p, x, pos, cache):
                    h = L.apply_norm(cfg, p["ln1"], x)
                    o, _ = L.multihead_attention(cfg, p["attn"], h,
                                                 positions=pos, kv_cache=cache,
                                                 kv_valid_len=pos[:, -1] + 1)
                    x = x + o
                    h = L.apply_norm(cfg, p["ln2"], x)
                    return x + L.apply_mlp(cfg, p["mlp"], h)
                record("shared_attn", napps, apply_attn,
                       (pa_abs, x_abs, pos_abs, c_abs),
                       (pa_sh, x_sh, pos_sh, c_sh))

    # ---------------- enc-dec ----------------
    elif cfg.family == "encdec":
        tree = encdec_mod.spec_tree(cfg)

        def unstack(t):
            return jax.tree_util.tree_map(
                lambda s: _sds(s.shape[1:], s.dtype), t)

        def unstack_axes(t):
            return jax.tree_util.tree_map(
                lambda a: tuple(a[1:]), t,
                is_leaf=lambda v: isinstance(v, tuple))

        enc_abs = unstack(abstract_params(tree["enc_blocks"], pdt))
        enc_sh = sh(unstack_axes(param_axes(tree["enc_blocks"])), enc_abs)
        dec_abs = unstack(abstract_params(tree["dec_blocks"], pdt))
        dec_sh = sh(unstack_axes(param_axes(tree["dec_blocks"])), dec_abs)
        src = max(1, S_cache // cfg.src_ratio)
        xe_abs = _sds((B, src, cfg.d_model), cdt)
        xe_sh = sh(("batch", None, None), xe_abs)
        spos_abs = _sds((B, src), jnp.int32)
        spos_sh = sh(("batch", None), spos_abs)

        def apply_enc(p, x, pos):
            h = L.apply_norm(cfg, p["ln1"], x)
            o, _ = L.multihead_attention(cfg, p["attn"], h, positions=pos,
                                         causal=False)
            x = x + o
            h = L.apply_norm(cfg, p["ln2"], x)
            return x + L.apply_mlp(cfg, p["mlp"], h)

        if mode == "train":
            record("enc_block", cfg.enc_layers, _grad_wrap(apply_enc, 2, cfg),
                   (enc_abs, xe_abs, spos_abs), (enc_sh, xe_sh, spos_sh))
        else:
            # encoder runs once at prefill; decode never re-runs it
            if mode == "prefill":
                record("enc_block", cfg.enc_layers, apply_enc,
                       (enc_abs, xe_abs, spos_abs), (enc_sh, xe_sh, spos_sh))

        def dec_core(p, x, pos, enc_out, cache):
            h = L.apply_norm(cfg, p["ln1"], x)
            o, _ = L.multihead_attention(
                cfg, p["attn"], h, positions=pos, kv_cache=cache,
                kv_valid_len=None if cache is None else pos[:, -1] + 1)
            x = x + o
            h = L.apply_norm(cfg, p["lnx"], x)
            o, _ = L.multihead_attention(cfg, p["xattn"], h, positions=pos,
                                         kv_x=enc_out)
            x = x + o
            h = L.apply_norm(cfg, p["ln2"], x)
            return x + L.apply_mlp(cfg, p["mlp"], h)

        if mode == "train":
            def dec_probe(p, x, e, pos):
                def loss(p, x, e):
                    return jnp.sum(dec_core(p, x, pos, e, None)
                                   .astype(jnp.float32))
                return jax.grad(loss, argnums=(0, 1, 2))(p, x, e)
            record("dec_block", cfg.num_layers, dec_probe,
                   (dec_abs, x_abs, xe_abs, pos_abs),
                   (dec_sh, x_sh, xe_sh, pos_sh))
        else:
            c_abs, c_sh = attn_cache()
            record("dec_block", cfg.num_layers,
                   lambda p, x, pos, e, c: dec_core(p, x, pos, e, c),
                   (dec_abs, x_abs, pos_abs, xe_abs, c_abs),
                   (dec_sh, x_sh, pos_sh, xe_sh, c_sh))

    return out


def corrected_terms(raw: Dict[str, Any], bodies: List[Dict[str, Any]]
                    ) -> Dict[str, float]:
    out = {"flops": float(raw.get("flops", 0.0)),
           "bytes": float(raw.get("bytes_accessed", 0.0)),
           "collective_bytes": float(
               raw.get("collectives", {}).get("total_bytes", 0.0))}
    for b in bodies:
        extra = max(0, b["trips"] - 1)
        out["flops"] += extra * b["flops"]
        out["bytes"] += extra * b["bytes"]
        out["collective_bytes"] += extra * b["collective_bytes"]
    return out
