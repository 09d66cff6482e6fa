"""Plain reference of the DeepSeek-V3 decoder as Moonlight-16B-A3B uses it
(arXiv:2412.19437; multi-head latent attention from arXiv:2405.04434),
for one sequence: float32 throughout, matrix products at the highest
precision, no cache, no batching, no kernels, no capacity.

It reads the program's params tree (``embed``, ``dense_blocks``,
``blocks``, ``final_norm``) and the configuration as a dict of the
program's keys (``bench/configs/<name>.json`` or a ``ModelConfig`` as a
dict).  Per layer: ``x += attn(rms(x))``, then ``x += ffn(rms(x))``, where
``ffn`` is a SiLU-gated MLP for the first ``first_k_dense_replace`` layers
and the MoE for the rest.

* Attention, expanded form: ``q = x wq`` split into ``q_nope`` and
  ``q_pe``; ``[c, k_pe] = x wkv_a``, ``c = rms(c)``; per head
  ``k = [c wk_b, k_pe]``, ``v = c wv_b``; causal softmax of ``q k /
  sqrt(qk_nope + qk_rope)``; ``o = (p v) wo``.
* Rotary embeddings: Hugging Face's DeepseekV3 convention.  The rope dims
  of ``q_pe`` and ``k_pe`` are de-interleaved (``x[0::2]`` then
  ``x[1::2]``) and then rotated by halves (``x cos + rotate_half(x) sin``,
  inverse frequencies ``theta^(-2i/d)``).  With seeded weights this is a
  fixed permutation of the rope dims; the program uses the same one.
* MoE: router ``s = sigmoid(x W_r)`` over all ``num_experts``; the top
  ``experts_per_token`` of ``s + bias`` are chosen (``noaux_tc`` with
  ``n_group = topk_group = 1``, so the group limit chooses every group);
  gates are ``s`` at the chosen experts, divided by their sum plus 1e-20
  (``norm_topk_prob`` true, as published) and times
  ``routed_scaling_factor``.  Then a dense
  loop over the experts held here (ids ``expert_offset`` to ``expert_offset
  + experts_held - 1``): each runs on every token and is weighted by its
  gate where it was chosen, 0 elsewhere; plus the shared experts, one MLP
  ``n_shared_experts x d_ff`` wide.

Departures from the published model, each also the program's:

* A chip's share: only the held experts' part of the routed sum is
  computed (the others live on other chips of the stated deployment), and
  the vocabulary may be a slice (embedding and head rows alike).
* The training-time parts of the router are left out: the update of the
  correction bias and the sequence-wise balance loss (``seq_aux``).
* Weights are the params tree's, in whatever precision they are stored;
  they are cast to float32 here.

``block_q`` > 0 computes attention in blocks of that many queries, so that
an 8,192-token forward fits on one chip; ``logits_at`` keeps the head to
the positions asked for.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _ein(spec: str, *args):
    return jnp.einsum(spec, *args, precision=HIGHEST)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x: (S, ..., d) rope dims at positions ``pos`` (S,), HF DeepseekV3's
    de-interleaved rotate-half convention."""
    d = x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    inv_freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
    freqs = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    emb = emb.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d,))
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def attention(cfg: Dict[str, Any], p, x, pos, block_q: int = 0):
    """Causal MLA over one sequence, x: (S, D) -> (S, D)."""
    S = x.shape[0]
    r, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    theta = cfg["rope_theta"]
    q = _ein("sd,dhk->shk", x, p["wq"])
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, theta)], -1)
    kv = _ein("sd,dr->sr", x, p["wkv_a"])
    c = rms(kv[:, :r], p["kv_norm"]["scale"], cfg["rms_norm_eps"])
    k_pe = rope(kv[:, r:], pos, theta)
    k_nope = _ein("sr,rhk->shk", c, p["wk_b"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None], k_nope.shape[:2]
                                  + k_pe.shape[-1:])], -1)
    v = _ein("sr,rhk->shk", c, p["wv_b"])
    scale = 1.0 / np.sqrt(q.shape[-1])
    step = block_q or S
    ctx = []
    for lo in range(0, S, step):
        qb = q[lo:lo + step]
        s = _ein("qhk,thk->hqt", qb, k) * scale
        causal = pos[None, lo:lo + step, None] >= pos[None, None, :]
        s = jnp.where(causal, s, -jnp.inf)
        ctx.append(_ein("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), v))
    return _ein("shk,hkd->sd", jnp.concatenate(ctx), p["wo"])


def mlp(p, x):
    return _ein("sf,fd->sd", jax.nn.silu(_ein("sd,df->sf", x, p["w_gate"]))
                * _ein("sd,df->sf", x, p["w_up"]), p["w_down"])


def route(cfg: Dict[str, Any], p, x):
    """Chosen expert ids (S, K) and their gates (S, K)."""
    scores = jax.nn.sigmoid(_ein("sd,de->se", x, p["router"]))
    _, ids = jax.lax.top_k(scores + p["router_bias"], cfg["experts_per_token"])
    gates = jnp.take_along_axis(scores, ids, axis=-1)
    gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return ids, gates * cfg["routed_scaling_factor"]


def moe(cfg: Dict[str, Any], p, x):
    """The held experts' part of the routed sum, plus the shared experts."""
    ids, gates = route(cfg, p, x)
    held = cfg.get("experts_held") or cfg["num_experts"]
    out = mlp(p["shared"], x) if "shared" in p else jnp.zeros_like(x)
    for e in range(held):
        w = jnp.sum(jnp.where(ids == cfg.get("expert_offset", 0) + e, gates,
                              0.0), -1)
        expert = {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}
        out = out + w[:, None] * mlp(expert, x)
    return out


def layer(cfg: Dict[str, Any], p, x, pos, block_q: int = 0):
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, p["attn"], rms(x, p["ln1"]["scale"], eps), pos,
                      block_q)
    h = rms(x, p["ln2"]["scale"], eps)
    return x + (moe(cfg, p["moe"], h) if "moe" in p else mlp(p["mlp"], h))


def layers_of(params) -> Sequence[Any]:
    """The per-layer params, in order: the dense blocks, then the rest."""
    out = []
    for name in ("dense_blocks", "blocks"):
        stack = params.get(name)
        if stack is not None:
            n = jax.tree_util.tree_leaves(stack)[0].shape[0]
            out += [jax.tree_util.tree_map(lambda a, i=i: a[i], stack)
                    for i in range(n)]
    return out


def forward(cfg: Dict[str, Any], params, tokens, *, block_q: int = 0,
            logits_at: Optional[Sequence[int]] = None, jit: bool = False):
    """tokens: (S,) ids -> float32 logits (S, V), or (len(logits_at), V).
    ``jit`` compiles each layer once per kind (dense, MoE) and runs the
    layers one after the other."""
    with jax.default_matmul_precision("highest"):
        step = lambda p, x, pos: layer(cfg, _f32(p), x, pos, block_q)
        if jit:
            step = jax.jit(step)
        tokens = jnp.asarray(tokens)
        pos = jnp.arange(tokens.shape[0])
        emb = params["embed"]["tok"]
        x = jnp.asarray(emb, jnp.float32)[tokens]
        for p in layers_of(params):
            x = step(p, x, pos)
        if logits_at is not None:
            x = x[jnp.asarray(logits_at)]
        x = rms(x, jnp.asarray(params["final_norm"]["scale"], jnp.float32),
                cfg["rms_norm_eps"])
        return _ein("sd,dv->sv", x,
                    jnp.asarray(params["embed"]["lm_head"], jnp.float32))
