"""Model registry: one uniform API over all families + input/cache specs.

``get_model(cfg)`` returns a :class:`ModelApi` whose methods close over the
config; ``input_specs`` produces ShapeDtypeStruct stand-ins (weak-type
correct, shardable, no allocation) for every (shape × mode) cell, which is
what the multi-pod dry-run lowers against.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from . import encdec, lm
from ..configs.base import InputShape, ModelConfig

ARCH_IDS = (
    "seamless-m4t-medium",
    "phi-3-vision-4.2b",
    "arctic-480b",
    "moonshot-v1-16b-a3b",
    "llama3.2-1b",
    "qwen1.5-110b",
    "granite-3-8b",
    "starcoder2-3b",
    "zamba2-2.7b",
    "mamba2-1.3b",
)


def _cfg_module(arch_id: str) -> str:
    return "repro.configs." + arch_id.replace("-", "_").replace(".", "_")


def load_config(arch_id: str) -> ModelConfig:
    if arch_id not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch_id!r}; options: {ARCH_IDS}")
    return importlib.import_module(_cfg_module(arch_id)).CONFIG


@dataclasses.dataclass(frozen=True)
class ModelApi:
    cfg: ModelConfig
    init: Callable
    abstract: Callable
    axes: Callable
    loss_fn: Callable
    prefill: Callable
    decode_step: Callable
    init_cache: Callable

    # ------------------------------------------------------------------
    def input_specs(self, shape: InputShape) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for the step function's data inputs."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        cdt = jnp.dtype(cfg.compute_dtype)
        tok = lambda b, s: jax.ShapeDtypeStruct((b, s), jnp.int32)
        if shape.mode == "train":
            if cfg.is_encdec:
                return {"frames": jax.ShapeDtypeStruct(
                            (B, max(1, S // cfg.src_ratio), cfg.d_model), cdt),
                        "tokens": tok(B, S), "labels": tok(B, S)}
            if cfg.frontend == "vision":
                text = S - cfg.frontend_tokens
                return {"patches": jax.ShapeDtypeStruct(
                            (B, cfg.frontend_tokens, cfg.d_model), cdt),
                        "tokens": tok(B, text), "labels": tok(B, text)}
            return {"tokens": tok(B, S), "labels": tok(B, S)}
        if shape.mode == "prefill":
            out = {"tokens": tok(B, S)}
            if cfg.is_encdec:
                out["frames"] = jax.ShapeDtypeStruct(
                    (B, max(1, S // cfg.src_ratio), cfg.d_model), cdt)
            elif cfg.frontend == "vision":
                out["tokens"] = tok(B, S - cfg.frontend_tokens)
                out["patches"] = jax.ShapeDtypeStruct(
                    (B, cfg.frontend_tokens, cfg.d_model), cdt)
            return out
        if shape.mode == "decode":
            return {"tokens": tok(B, 1)}
        raise ValueError(f"unknown mode {shape.mode}")

    def input_axes(self, shape: InputShape) -> Dict[str, Any]:
        """Logical axes for input_specs (batch dim -> data parallel)."""
        specs = self.input_specs(shape)
        return {k: ("batch",) + (None,) * (len(v.shape) - 1)
                for k, v in specs.items()}

    def abstract_cache(self, shape: InputShape) -> Dict[str, Any]:
        return self.init_cache(shape.global_batch, shape.seq_len,
                               abstract_only=True)

    def cache_axes(self, shape: InputShape) -> Dict[str, Any]:
        cache = self.abstract_cache(shape)
        out: Dict[str, Any] = {}
        for k, v in cache.items():
            if k in ("k", "v"):
                out[k] = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
            elif k in ("c_kv", "k_pe"):
                out[k] = ("layers", "batch", "kv_seq", None)
            elif k == "state":
                out[k] = ("layers", "batch", "ssm_heads", None, "ssm_state")
            elif k == "conv":
                out[k] = ("layers", "batch", None, "ssm_inner")
            elif k == "enc_out":
                out[k] = ("batch", None, None)
            elif k == "pos":
                out[k] = ("batch",)
            else:
                out[k] = tuple([None] * len(v.shape))
        return out


def get_model(cfg: ModelConfig) -> ModelApi:
    if cfg.is_encdec:
        return ModelApi(
            cfg=cfg,
            init=lambda key: encdec.init(cfg, key),
            abstract=lambda: encdec.abstract(cfg),
            axes=lambda: encdec.axes(cfg),
            loss_fn=lambda params, batch, rng=None: encdec.loss_fn(cfg, params, batch, rng),
            prefill=lambda params, tokens, cache, **kw: encdec.prefill(
                cfg, params, tokens, cache, **kw),
            decode_step=lambda params, tokens, cache: encdec.decode_step(
                cfg, params, tokens, cache),
            init_cache=lambda b, s, abstract_only=False: encdec.init_cache(
                cfg, b, s, abstract_only),
        )
    return ModelApi(
        cfg=cfg,
        init=lambda key: lm.init(cfg, key),
        abstract=lambda: lm.abstract(cfg),
        axes=lambda: lm.axes(cfg),
        loss_fn=lambda params, batch, rng=None: lm.loss_fn(cfg, params, batch, rng),
        prefill=lambda params, tokens, cache, **kw: lm.prefill(
            cfg, params, tokens, cache, **kw),
        decode_step=lambda params, tokens, cache: lm.decode_step(
            cfg, params, tokens, cache),
        init_cache=lambda b, s, abstract_only=False: lm.init_cache(
            cfg, b, s, abstract_only),
    )


def get(arch_id: str, smoke: bool = False) -> ModelApi:
    cfg = load_config(arch_id)
    if smoke:
        cfg = cfg.smoke()
    return get_model(cfg)
