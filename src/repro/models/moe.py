"""Mixture-of-Experts layer: top-k router, then the experts this chip holds.

Routers: ``softmax`` (top-k of the softmax, Switch-style balance loss) and
``sigmoid`` (DeepSeek-V3's ``noaux_tc``: top-k of the sigmoid scores plus a
correction bias, gates from the scores without it).  Gates are divided by
their sum (``norm_topk_prob``, true for every model here) and scaled
(``routed_scaling_factor``).

The layer routes over all ``num_experts`` and computes only the part of the
result that its ``experts_held`` experts (ids ``expert_offset`` on) give;
``n_shared_experts`` always-on experts add one MLP of ``n x d_ff``.  Two
dispatches:

* capacity (``moe_capacity_factor`` > 0): a sort-based rank within each
  expert and a scatter into an (E, C, D) buffer, which keeps memory at
  O(tokens·k + E·C·D) instead of the O(tokens·E·C) one-hot combine
  tensor; tokens beyond an expert's capacity are dropped.
* dropless (``moe_capacity_factor`` 0): the token copies are sorted by
  expert and each held expert's FFN is a grouped matmul
  (``lax.ragged_dot``) over its own rows; no token is dropped.

Expert weights are stacked on a leading "expert" axis — the paper's Dense
scenario (an *array* of structures, fanout q = num_experts) realized as
real model state; top-k routing *is* selective deep copy over that array.

Sharding: "expert" -> data axis (expert parallelism), "expert_mlp" -> model
axis (per-expert tensor parallelism); XLA inserts the all-to-all.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from . import pspec
from .pspec import constrain
from .specs import ParamSpec
from ..configs.base import ModelConfig


def moe_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d, f, e, h = cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.held_experts
    s: Dict[str, Any] = {
        # router is replicated (tiny): top-k needs all E logits everywhere
        "router": ParamSpec((d, e), ("embed", None)),
        "w_gate": ParamSpec((h, d, f), ("expert", "expert_embed", "expert_mlp")),
        "w_up": ParamSpec((h, d, f), ("expert", "expert_embed", "expert_mlp")),
        "w_down": ParamSpec((h, f, d), ("expert", "expert_mlp", "expert_embed")),
    }
    if cfg.scoring_func == "sigmoid":
        s["router_bias"] = ParamSpec((e,), (None,), init="zeros")
    if cfg.n_shared_experts:
        s["shared"] = L.mlp_specs(cfg, cfg.n_shared_experts * f)
    return s


def route(cfg: ModelConfig, p: Dict[str, Any], xt: jax.Array
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k over all ``num_experts`` for N tokens (N, D): expert ids (N, K),
    gates (N, K) float32, and the balance loss (0 for the sigmoid router,
    whose balance is the bias, a training-time rule)."""
    E, K = cfg.num_experts, cfg.experts_per_token
    logits = jnp.einsum("nd,de->ne", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if cfg.scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, expert_ids = jax.lax.top_k(
            scores + p["router_bias"].astype(jnp.float32), K)
        gate_vals = jnp.take_along_axis(scores, expert_ids, axis=-1)
        aux_loss = jnp.zeros((), jnp.float32)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, K)          # (N,K)
        # load-balance auxiliary loss (Switch-style)
        me = jnp.mean(probs, axis=0)                              # (E,)
        ce = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], E), axis=0)
        aux_loss = E * jnp.sum(me * ce)
    gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)
    return expert_ids, gate_vals * cfg.routed_scaling_factor, aux_loss


def capacity(cfg: ModelConfig, num_tokens: int) -> int:
    c = int(cfg.moe_capacity_factor * cfg.experts_per_token * num_tokens
            / max(1, cfg.num_experts))
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly shapes


def _rank(flat_expert: jax.Array, n_groups: int) -> jax.Array:
    """Position of each (token, k) within its expert.  NOT the textbook
    one-hot cumsum: cumsum over (N*K, E) lowers to an O(N^2) reduce-window
    (measured 1.6e14 flops/device at 1M tokens — EXPERIMENTS.md §Perf #1).
    Sort-based ranking is O(N log N): stable-sort token slots by expert,
    rank within the sorted run, scatter ranks back."""
    NK = flat_expert.shape[0]
    sorted_idx = jnp.argsort(flat_expert)                         # stable
    counts = jnp.bincount(flat_expert, length=n_groups)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = (jnp.arange(NK, dtype=jnp.int32)
                  - starts[flat_expert[sorted_idx]])
    return jnp.zeros((NK,), jnp.int32).at[sorted_idx].set(pos_sorted)


def _route_and_rank(cfg, p, xt):
    """Top-k routing + sort-based within-expert ranks for N local tokens."""
    expert_ids, gate_vals, aux_loss = route(cfg, p, xt)
    flat_expert = expert_ids.reshape(-1)
    return flat_expert, _rank(flat_expert, cfg.num_experts), gate_vals, aux_loss


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

    def local(x_l, router, wg, wu, wd):
        B_l, S, _ = x_l.shape
        N_l = B_l * S
        C_l = capacity(cfg, N_l)
        xt = x_l.reshape(N_l, D)
        flat_expert, pos, gate_vals, aux = _route_and_rank(cfg, router, xt)
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
    router = {k: p[k] for k in ("router", "router_bias") if k in p}
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(batch_spec,
                                 jax.tree_util.tree_map(lambda _: P(), router),
                                 w_spec, w_spec,
                                 P(ep, tp if tp else None, None)),
                       out_specs=(batch_spec, P()),
                       check_vma=False)
    out, aux = fn(x, router, p["w_gate"], p["w_up"], p["w_down"])
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            out = out + L.apply_mlp(cfg, p["shared"], x)
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
    if cfg.held_experts != cfg.num_experts or cfg.moe_capacity_factor <= 0:
        return None      # a chip's share, or dropless: the local path
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
    K, H = cfg.experts_per_token, cfg.held_experts
    N = B * S
    xt = x.reshape(N, D)
    with jax.named_scope("moe.route"):
        expert_ids, gate_vals, aux_loss = route(cfg, p, xt)
        # the held experts' local ids; H marks an expert held elsewhere
        local = expert_ids - cfg.expert_offset
        held = (local >= 0) & (local < H)
        flat_expert = jnp.where(held, local, H).reshape(-1)       # (N*K,)
    with jax.named_scope("moe.experts"):
        if cfg.moe_capacity_factor > 0:
            gathered, keep = _capacity_experts(cfg, p, xt, flat_expert)
        else:
            gathered, keep = _grouped_experts(p, xt, flat_expert, H, K)
        gathered = jnp.where(keep[:, None], gathered, 0)
        combined = (gathered.reshape(N, K, D)
                    * gate_vals[..., None].astype(x.dtype)).sum(axis=1)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            combined = combined + L.apply_mlp(cfg, p["shared"], xt[None])[0]
    return combined.reshape(B, S, D), {"moe_aux_loss": aux_loss}


def _capacity_experts(cfg, p, xt, flat_expert):
    """Scatter into an (E, C, D) buffer, drop what overflows C."""
    N, D = xt.shape
    E, K = cfg.held_experts, cfg.experts_per_token
    C = capacity(cfg, N)
    pos = _rank(flat_expert, E + 1)
    keep = (flat_expert < E) & (pos < C)                          # drop overflow

    # scatter tokens into the (E, C, D) expert buffer.  The sharding
    # constraints are load-bearing: without them XLA resolves the
    # token->expert scatter by replicating the buffer on every chip and the
    # expert FFN runs unsharded (~100x flops; see EXPERIMENTS.md §Perf #1).
    # Constraining buf to ("expert"->data, mlp dims -> model) forces the
    # dispatch to lower as an all-to-all instead.
    buf = jnp.zeros((E, C, D), xt.dtype)
    src = jnp.repeat(xt, K, axis=0)                               # (N*K, D)
    safe_pos = jnp.where(keep, pos, C - 1)
    buf = buf.at[flat_expert, safe_pos].add(
        jnp.where(keep[:, None], src, 0).astype(xt.dtype), mode="drop")
    buf = constrain(buf, "expert", None, None)

    # expert FFN (per-expert SwiGLU), batched einsum over the expert axis
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(xt.dtype)))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(xt.dtype))
    g = constrain(g, "expert", None, "expert_mlp")
    u = constrain(u, "expert", None, "expert_mlp")
    out_buf = jnp.einsum("ecf,efd->ecd", g * u, p["w_down"].astype(xt.dtype))
    out_buf = constrain(out_buf, "expert", None, None)
    # gather back (mode="fill": an expert held elsewhere reads zeros)
    return out_buf.at[flat_expert, safe_pos].get(mode="fill", fill_value=0), keep


def _grouped_experts(p, xt, flat_expert, H, K):
    """Each held expert's SwiGLU over exactly the token copies routed to it:
    copies sorted by expert, one grouped matmul per weight; the copies for
    experts held elsewhere sort last, past every group."""
    dt = xt.dtype
    order = jnp.argsort(flat_expert)                              # stable
    sizes = jnp.bincount(flat_expert, length=H + 1)[:H].astype(jnp.int32)
    rows = xt[order // K]                                         # (N*K, D)
    g = jax.nn.silu(jax.lax.ragged_dot(rows, p["w_gate"].astype(dt), sizes))
    u = jax.lax.ragged_dot(rows, p["w_up"].astype(dt), sizes)
    out = jax.lax.ragged_dot(g * u, p["w_down"].astype(dt), sizes)
    unsort = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    return out[unsort], flat_expert < H
