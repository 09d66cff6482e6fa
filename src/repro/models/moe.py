"""Mixture-of-Experts layer: top-k router + capacity-bucketed scatter dispatch.

Dispatch is sort-free scatter (cumsum position within expert), which keeps
memory at O(tokens·k + E·C·D) instead of the O(tokens·E·C) one-hot combine
tensor.  Expert weights are stacked on a leading "expert" axis — the paper's
Dense scenario (an *array* of structures, fanout q = num_experts) realized
as real model state; top-k routing *is* selective deep copy over that array.

Sharding: "expert" -> data axis (expert parallelism), "expert_mlp" -> model
axis (per-expert tensor parallelism); XLA inserts the all-to-all.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from . import pspec
from .pspec import constrain
from .specs import ParamSpec
from ..configs.base import ModelConfig


def moe_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    s = {
        # router is replicated (tiny): top-k needs all E logits everywhere
        "router": ParamSpec((d, e), ("embed", None)),
        "w_gate": ParamSpec((e, d, f), ("expert", "expert_embed", "expert_mlp")),
        "w_up": ParamSpec((e, d, f), ("expert", "expert_embed", "expert_mlp")),
        "w_down": ParamSpec((e, f, d), ("expert", "expert_mlp", "expert_embed")),
    }
    return s


def capacity(cfg: ModelConfig, num_tokens: int) -> int:
    c = int(cfg.moe_capacity_factor * cfg.experts_per_token * num_tokens
            / max(1, cfg.num_experts))
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly shapes


def _route_and_rank(cfg, router_w, xt):
    """Top-k routing + sort-based within-expert ranks for N local tokens."""
    E, K = cfg.num_experts, cfg.experts_per_token
    N = xt.shape[0]
    logits = jnp.einsum("nd,de->ne", xt.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)              # (N,K)
    gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], E), axis=0)
    aux_loss = E * jnp.sum(me * ce)
    flat_expert = expert_ids.reshape(-1)
    sorted_idx = jnp.argsort(flat_expert)
    counts = jnp.bincount(flat_expert, length=E)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = (jnp.arange(N * K, dtype=jnp.int32)
                  - starts[flat_expert[sorted_idx]])
    pos = jnp.zeros((N * K,), jnp.int32).at[sorted_idx].set(pos_sorted)
    return flat_expert, pos, gate_vals, aux_loss


def apply_moe_sharded(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
                      mesh, ep_axes, tp_axes
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Expert-parallel dispatch under shard_map (EXPERIMENTS.md §Perf #4).

    The pjit dense-buffer dispatch makes GSPMD all-reduce (E, C, D)-sized
    partial scatters across every chip (~18 GB/device/layer at 1M tokens).
    Real expert parallelism is LOCAL rank/scatter + one all-to-all each way:

      per shard: route local tokens -> local (E, C_loc, D) buffer
      all_to_all over the expert axis: (E, C_loc, D) -> (E_loc, C_glob, D)
      per-expert FFN (expert-TP over ``tp_axes``, one psum)
      all_to_all back, local gather+combine.
    """
    from jax.sharding import PartitionSpec as P

    E, K = cfg.num_experts, cfg.experts_per_token
    D = x.shape[-1]
    ep = tuple(ep_axes) if isinstance(ep_axes, (list, tuple)) else (ep_axes,)
    n_ep = 1
    for ax in ep:
        n_ep *= dict(zip(mesh.axis_names, mesh.devices.shape))[ax]
    tp = tuple(tp_axes) if isinstance(tp_axes, (list, tuple)) and tp_axes \
        else ((tp_axes,) if isinstance(tp_axes, str) else ())

    def local(x_l, router_w, wg, wu, wd):
        B_l, S, _ = x_l.shape
        N_l = B_l * S
        C_l = capacity(cfg, N_l)
        xt = x_l.reshape(N_l, D)
        flat_expert, pos, gate_vals, aux = _route_and_rank(cfg, router_w, xt)
        keep = pos < C_l
        safe_pos = jnp.where(keep, pos, C_l - 1)
        buf = jnp.zeros((E, C_l, D), x_l.dtype)
        src = jnp.repeat(xt, K, axis=0)
        buf = buf.at[flat_expert, safe_pos].add(
            jnp.where(keep[:, None], src, 0).astype(x_l.dtype), mode="drop")
        # dispatch: every shard sends its slice of each expert's tokens.
        # tiled all_to_all: split dim E -> E/n, concat dim C_l -> n*C_l
        # (block-ordered by source shard); it is its own inverse with the
        # axes swapped, and its VJP is exact.
        buf = jax.lax.all_to_all(buf, ep, split_axis=0, concat_axis=1,
                                 tiled=True)                  # (E_l, n*C_l, D)
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg.astype(x_l.dtype)))
        u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(x_l.dtype))
        ob = jnp.einsum("ecf,efd->ecd", g * u, wd.astype(x_l.dtype))
        if tp:
            ob = jax.lax.psum(ob, tp)        # expert-TP partial contraction
        # inverse all-to-all restores each shard's slots exactly
        ob = jax.lax.all_to_all(ob, ep, split_axis=1, concat_axis=0,
                                tiled=True)                   # (E, C_l, D)
        gathered = ob[flat_expert, safe_pos]
        gathered = jnp.where(keep[:, None], gathered, 0)
        combined = (gathered.reshape(N_l, K, D)
                    * gate_vals[..., None].astype(x_l.dtype)).sum(axis=1)
        return combined.reshape(B_l, S, D), jax.lax.pmean(aux, ep)

    batch_spec = P(ep, None, None)
    w_spec = P(ep, None, tp if tp else None)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(batch_spec, P(None, None), w_spec, w_spec,
                                 P(ep, tp if tp else None, None)),
                       out_specs=(batch_spec, P()),
                       check_vma=False)
    out, aux = fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out, {"moe_aux_loss": aux}


def _sharded_config(cfg, x):
    """Use the shard_map path when a mesh is active and shapes divide."""
    ctx = pspec.active_rules()
    if ctx is None:
        return None
    mesh_ctx = pspec._tls.ctx
    mesh, rules = mesh_ctx["mesh"], mesh_ctx["rules"]
    ep = rules.get("expert")
    if not ep:
        return None
    ep = ep if isinstance(ep, tuple) else (ep,)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_ep = 1
    for ax in ep:
        n_ep *= sizes[ax]
    if cfg.num_experts % n_ep or x.shape[0] % n_ep:
        return None
    tp = rules.get("expert_mlp")
    if tp:
        tp = tp if isinstance(tp, tuple) else (tp,)
        n_tp = 1
        for ax in tp:
            n_tp *= sizes[ax]
        if cfg.d_ff % n_tp:
            tp = None
    return mesh, ep, tp


def apply_moe(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, D) -> (B, S, D), aux metrics (load-balance loss)."""
    sharded = _sharded_config(cfg, x)
    if sharded is not None:
        return apply_moe_sharded(cfg, p, x, *sharded)
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    N = B * S
    C = capacity(cfg, N)
    xt = x.reshape(N, D)

    logits = jnp.einsum("nd,de->ne", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)              # (N,K)
    gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)

    # load-balance auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)                                  # (E,)
    ce = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], E), axis=0)
    aux_loss = E * jnp.sum(me * ce)

    # position of each (token, k) within its expert.  NOT the textbook
    # one-hot cumsum: cumsum over (N*K, E) lowers to an O(N^2) reduce-window
    # (measured 1.6e14 flops/device at 1M tokens — EXPERIMENTS.md §Perf #1).
    # Sort-based ranking is O(N log N): stable-sort token slots by expert,
    # rank within the sorted run, scatter ranks back.
    flat_expert = expert_ids.reshape(-1)                          # (N*K,)
    NK = flat_expert.shape[0]
    sorted_idx = jnp.argsort(flat_expert)                         # stable
    sorted_experts = flat_expert[sorted_idx]
    counts = jnp.bincount(flat_expert, length=E)                  # (E,)
    starts = jnp.cumsum(counts) - counts                          # (E,) tiny cumsum
    pos_sorted = jnp.arange(NK, dtype=jnp.int32) - starts[sorted_experts]
    pos = jnp.zeros((NK,), jnp.int32).at[sorted_idx].set(pos_sorted)
    keep = pos < C                                                # drop overflow

    # scatter tokens into the (E, C, D) expert buffer.  The sharding
    # constraints are load-bearing: without them XLA resolves the
    # token->expert scatter by replicating the buffer on every chip and the
    # expert FFN runs unsharded (~100x flops; see EXPERIMENTS.md §Perf #1).
    # Constraining buf to ("expert"->data, mlp dims -> model) forces the
    # dispatch to lower as an all-to-all instead.
    buf = jnp.zeros((E, C, D), x.dtype)
    src = jnp.repeat(xt, K, axis=0)                               # (N*K, D)
    safe_pos = jnp.where(keep, pos, C - 1)
    buf = buf.at[flat_expert, safe_pos].add(
        jnp.where(keep[:, None], src, 0).astype(x.dtype), mode="drop")
    buf = constrain(buf, "expert", None, None)

    # expert FFN (per-expert SwiGLU), batched einsum over the expert axis
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(x.dtype)))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(x.dtype))
    g = constrain(g, "expert", None, "expert_mlp")
    u = constrain(u, "expert", None, "expert_mlp")
    out_buf = jnp.einsum("ecf,efd->ecd", g * u, p["w_down"].astype(x.dtype))
    out_buf = constrain(out_buf, "expert", None, None)

    # gather back and combine with gates
    gathered = out_buf[flat_expert, safe_pos]                     # (N*K, D)
    gathered = jnp.where(keep[:, None], gathered, 0)
    combined = (gathered.reshape(N, K, D)
                * gate_vals[..., None].astype(x.dtype)).sum(axis=1)
    return combined.reshape(B, S, D), {"moe_aux_loss": aux_loss}
