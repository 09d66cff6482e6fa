"""Production mesh + logical-axis sharding rules.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single pod: (16, 16) = 256 v5e chips, axes
("data", "model").  Multi-pod: (2, 16, 16) = 512 chips, axes
("pod", "data", "model") — the "pod" axis is the slow (DCN/ICI-bridge)
dimension and carries only data parallelism.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec

from ..jaxenv import make_mesh
from ..models.pspec import logical_to_spec


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CPU tests (requires forced host device count)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def default_rules(mesh) -> Dict[str, Optional[Tuple[str, ...]]]:
    multi = "pod" in mesh.axis_names
    dp = ("pod", "data") if multi else ("data",)
    return {
        # activations
        "batch": dp,
        "seq": None,
        # dense params: 2-D sharded (FSDP over data x TP over model)
        "embed": dp,
        "embed_out": None,
        "vocab": ("model",),
        "heads": ("model",),
        "kv_heads": None,
        "head_dim": None,
        "mlp": ("model",),
        # MoE: expert parallelism over data, per-expert TP over model
        "expert": dp,
        "expert_router": ("model",),
        "expert_embed": None,
        "expert_mlp": ("model",),
        # SSM
        "ssm_inner": ("model",),
        "ssm_state": None,
        "ssm_heads": ("model",),
        "conv": None,
        # stacking / caches
        "layers": None,
        "kv_seq": None,
        "frame": None,
    }


def rules_for(cfg, mesh, mode: str = "train"
              ) -> Dict[str, Optional[Tuple[str, ...]]]:
    rules = default_rules(mesh)
    if mode != "train" and not cfg.inference_embed_fsdp:
        # inference: no optimizer state to amortize FSDP against — replicate
        # the embed dim over data (pure TP) and kill the per-layer weight
        # all-gathers (EXPERIMENTS.md §Perf #2).  Experts stay sharded over
        # data (EP all-to-all; weights too big to replicate).
        rules["embed"] = None
    for k, v in cfg.rules:
        rules[k] = tuple(v) if isinstance(v, (list, tuple)) else v
    if mode == "decode":
        for k, v in cfg.decode_rules:
            rules[k] = tuple(v) if isinstance(v, (list, tuple)) else v
    return rules


def adapt_batch_rule(rules: Dict, mesh, global_batch: int) -> Dict:
    """Shrink the batch sharding when the batch doesn't divide the dp axes
    (e.g. long_500k has global_batch=1): GSPMD would pad a size-1 dim to the
    full axis, replicating the KV cache axis-size times."""
    dp = rules.get("batch")
    if not dp:
        return rules
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    keep = []
    for ax in dp:
        if global_batch % sizes[ax] == 0:
            keep.append(ax)
            global_batch //= sizes[ax]
    out = dict(rules)
    out["batch"] = tuple(keep) if keep else None
    return out


def _demote_spec(spec: PartitionSpec, shape, mesh) -> PartitionSpec:
    """Drop mesh axes that do not evenly divide their tensor dim.

    jit *arguments* (unlike intermediates, which GSPMD pads) must divide
    exactly — e.g. arctic's 56 heads or granite's 49155 vocab cannot shard
    16-way.  We keep the largest in-order prefix of each entry's axes whose
    product divides the dim and drop the rest (documented per arch in
    EXPERIMENTS.md §Dry-run)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    entries = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            entries.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        keep = []
        rem = int(dim)
        for ax in axes:
            if rem % sizes[ax] == 0:
                keep.append(ax)
                rem //= sizes[ax]
        entries.append(tuple(keep) if len(keep) > 1
                       else (keep[0] if keep else None))
    return PartitionSpec(*entries)


def tree_shardings(mesh, axes_tree: Any, rules: Dict,
                   abstract_tree: Any = None) -> Any:
    """Map a logical-axes tree to NamedShardings.

    With ``abstract_tree`` (ShapeDtypeStructs of the actual arguments),
    shardings are demoted per-leaf to respect divisibility."""
    is_axes = lambda x: isinstance(x, tuple)
    axes_leaves, treedef = jax.tree_util.tree_flatten(axes_tree, is_leaf=is_axes)
    if abstract_tree is None:
        shs = [NamedSharding(mesh, logical_to_spec(tuple(a), rules))
               for a in axes_leaves]
        return jax.tree_util.tree_unflatten(treedef, shs)
    abs_leaves = jax.tree_util.tree_leaves(abstract_tree)
    if len(abs_leaves) != len(axes_leaves):
        raise ValueError(f"axes tree ({len(axes_leaves)} leaves) does not "
                         f"match abstract tree ({len(abs_leaves)} leaves)")
    shs = []
    for a, v in zip(axes_leaves, abs_leaves):
        spec = logical_to_spec(tuple(a), rules)
        shs.append(NamedSharding(mesh, _demote_spec(spec, v.shape, mesh)))
    return jax.tree_util.tree_unflatten(treedef, shs)


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())
