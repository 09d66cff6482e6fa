"""Moonlight-16B-A3B (DeepSeek-V3 architecture) against its plain
reference, ``bench/reference/deepseek_v3.py``, at tiny widths on the CPU
with seeded weights: latent attention through the cache, the sigmoid
router, dropless dispatch, a chip's share of the experts, and the names
the device trace reads."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import deepseek_v3 as ref
from repro.models import lm, mla, moe, registry
from repro.runtime import Request, Server

# Logits of the program against the reference, as ||a - b|| / ||b||, at
# float32 compute.  The two sum in other orders (blockwise, cached and
# absorbed against expanded) and the reference multiplies at HIGHEST:
# float32 roundoff through 3 layers measures 2e-6 here.  Rounding the
# inputs of attention to bfloat16 (2^-9 relative) measures 8e-2, of the
# experts 1.3e-3; 1e-4 lies between, with room on both sides.
TOL_F32 = 1e-4
# A MoE layer's output, routed and shared: float32 roundoff of one layer.
TOL_LAYER = 1e-5


def _cfg(**kw):
    base = registry.load_config("moonshot-v1-16b-a3b").smoke()
    return dataclasses.replace(base, **dict(
        dict(num_layers=3, num_experts=8, experts_per_token=2,
             experts_held=4, expert_offset=2), **kw))


def _params(cfg, seed=0):
    """The program's initialisation, with a random correction bias so that
    the bias changes some choices."""
    params = registry.get_model(cfg).init(jax.random.PRNGKey(seed))
    bias = params["blocks"]["moe"]["router_bias"]
    params["blocks"]["moe"]["router_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), bias.shape, bias.dtype)
    return params


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _cached_vs_reference(cfg, params, B=2, S=12, half=8):
    """Prefill ``half`` tokens, decode the rest one at a time through the
    latent cache; the worst relative error of any compared position."""
    api = registry.get_model(cfg)
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    cache = api.init_cache(B, 16)
    logits, cache = api.prefill(params, jnp.asarray(tokens[:, :half]), cache)
    got = [logits[:, -1]]
    for t in range(half, S):
        logits, cache = api.decode_step(
            params, jnp.asarray(tokens[:, t:t + 1]), cache)
        got.append(logits[:, 0])
    got = np.stack([np.asarray(g) for g in got], 1)       # (B, S-half+1, V)
    d = dataclasses.asdict(cfg)
    worst = 0.0
    for b in range(B):
        want = ref.forward(d, params, tokens[b], block_q=4,
                           logits_at=range(half - 1, S))
        for i in range(want.shape[0]):
            worst = max(worst, _rel(got[b, i], want[i]))
    return worst


def _bf16_inputs(fn):
    def lowered(cfg, p, x, *a, **kw):
        out = fn(cfg, p, x.astype(jnp.bfloat16), *a, **kw)
        return (out[0].astype(x.dtype),) + tuple(out[1:])
    return lowered


def _bf16_experts(fn):
    def lowered(p, xt, *a):
        out, keep = fn(p, xt.astype(jnp.bfloat16), *a)
        return out.astype(xt.dtype), keep
    return lowered


@pytest.mark.parametrize("variant", ["f32", "bf16_attention", "bf16_experts"])
def test_prefill_then_decode_matches_the_reference(variant, monkeypatch):
    """At float32 the cached path agrees with the reference's full forward
    within TOL_F32; computing attention or the experts one precision lower
    does not."""
    cfg = _cfg()
    assert cfg.kv_lora_rank and cfg.compute_dtype == "float32"
    if variant == "bf16_attention":
        monkeypatch.setattr(mla, "mla_attention",
                            _bf16_inputs(mla.mla_attention))
    elif variant == "bf16_experts":
        monkeypatch.setattr(moe, "_grouped_experts",
                            _bf16_experts(moe._grouped_experts))
    err = _cached_vs_reference(cfg, _params(cfg))
    if variant == "f32":
        assert err <= TOL_F32, err
    else:
        assert err > TOL_F32, err


def test_the_cache_is_latent():
    cfg = _cfg()
    cache = registry.get_model(cfg).init_cache(3, 16, abstract_only=True)
    assert {k: v.shape for k, v in cache.items()} == {
        "pos": (3,), "c_kv": (3, 3, 16, cfg.kv_lora_rank),
        "k_pe": (3, 3, 16, cfg.qk_rope_head_dim)}


def test_router_choice_and_gates_match_the_reference():
    cfg = _cfg(experts_per_token=3)
    p = _params(cfg)["blocks"]["moe"]
    p = jax.tree_util.tree_map(lambda a: a[0], p)
    x = jax.random.normal(jax.random.PRNGKey(7), (64, cfg.d_model))
    ids, gates, aux = moe.route(cfg, p, x)
    want_ids, want_gates = ref.route(dataclasses.asdict(cfg), p, x)
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want_ids, -1))
    order = np.argsort(ids, -1)
    want_order = np.argsort(want_ids, -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(gates), order, -1),
        np.take_along_axis(np.asarray(want_gates), want_order, -1),
        rtol=1e-6, atol=1e-7)
    # normalised, then scaled; the bias changed some choices but no gate
    np.testing.assert_allclose(np.sum(gates, -1), cfg.routed_scaling_factor,
                               rtol=1e-6)
    scores = jax.nn.sigmoid(x @ p["router"])
    _, unbiased = jax.lax.top_k(scores, cfg.experts_per_token)
    assert (np.sort(unbiased, -1) != np.sort(ids, -1)).any()
    assert float(aux) == 0.0


def test_no_token_is_dropped_when_every_token_picks_one_expert():
    """A decode batch of 16 tokens that all choose held expert 3: the
    dropless layer gives the reference's answer; the capacity dispatch
    (factor 1.25, capacity 8) would have dropped half of them."""
    cfg = _cfg()
    p = jax.tree_util.tree_map(lambda a: a[0], _params(cfg)["blocks"]["moe"])
    p["router_bias"] = jnp.zeros_like(p["router_bias"]).at[3].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(8), (16, 1, cfg.d_model))
    ids, _, _ = moe.route(cfg, p, x.reshape(16, -1))
    assert (ids == 3).any(-1).all()
    want = ref.moe(dataclasses.asdict(cfg), p, x.reshape(16, -1))
    got, _ = moe.apply_moe(cfg, p, x)
    assert _rel(got.reshape(16, -1), want) <= TOL_LAYER
    capped, _ = moe.apply_moe(
        dataclasses.replace(cfg, moe_capacity_factor=1.25), p, x)
    assert _rel(capped.reshape(16, -1), want) > 0.1


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of 2 experts each (ids 0-1, ..., 6-7): their
    outputs, with the shared experts counted once, add up to the uncut
    reference layer, all 8 experts held."""
    whole = _cfg(experts_held=0, expert_offset=0)
    p = jax.tree_util.tree_map(lambda a: a[1], _params(whole)["blocks"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 5, whole.d_model))
    want = ref.moe(dataclasses.asdict(whole), p, x.reshape(10, -1))
    total = 0.0
    for off in range(0, 8, 2):
        share = dataclasses.replace(whole, experts_held=2, expert_offset=off)
        ps = dict(p, **{k: p[k][off:off + 2]
                        for k in ("w_gate", "w_up", "w_down")})
        if off:
            ps.pop("shared")            # what every chip computes, once
        out, _ = moe.apply_moe(share, ps, x)
        total = total + out
    assert _rel(np.asarray(total).reshape(10, -1), want) <= TOL_LAYER


def test_a_decode_step_names_the_new_device_work():
    api = registry.get("moonshot-v1-16b-a3b", smoke=True)
    params = api.init(jax.random.PRNGKey(0))
    text = jax.jit(api.decode_step).lower(
        params, jnp.zeros((2, 1), jnp.int32),
        api.init_cache(2, 16)).compile().as_text()
    for name in ("mla.attend", "moe.route", "moe.experts", "moe.shared"):
        assert name in text, name


def test_the_server_serves_it_through_the_latent_cache():
    """The server's prefill, cache install and batched decode over the
    latent leaves give the tokens of a plain greedy decode."""
    api = registry.get("moonshot-v1-16b-a3b", smoke=True)
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, api.cfg.vocab_size, n).astype(np.int32)
               for n in (7, 3, 5)]
    server = Server(api, params, slots=2, max_seq=32)
    for i, prompt in enumerate(prompts):
        server.submit(Request(rid=i, prompt=prompt, max_new_tokens=4))
    done = {r.rid: r.tokens_out for r in server.run(max_steps=50)}
    assert sorted(done) == [0, 1, 2]
    for i, prompt in enumerate(prompts):
        cache = api.init_cache(1, 32)
        logits, cache = api.prefill(params, jnp.asarray(prompt)[None], cache)
        want = [int(np.argmax(np.asarray(logits[0, -1])))]
        for _ in range(3):
            logits, cache = api.decode_step(
                params, jnp.asarray([[want[-1]]], jnp.int32), cache)
            want.append(int(np.argmax(np.asarray(logits[0, -1]))))
        assert done[i] == want, i


def test_the_registry_config_is_the_published_model():
    cfg = registry.load_config("moonshot-v1-16b-a3b")
    assert (cfg.num_layers, cfg.d_model, cfg.vocab_size, cfg.num_heads) == (
        27, 2048, 163840, 16)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.rope_theta, cfg.rms_norm_eps) == (
        512, 128, 64, 128, 50000, 1e-5)
    assert (cfg.first_k_dense_replace, cfg.dense_d_ff, cfg.d_ff,
            cfg.num_experts, cfg.held_experts, cfg.experts_per_token,
            cfg.n_shared_experts) == (1, 11264, 1408, 64, 64, 6, 2)
    assert (cfg.scoring_func, cfg.routed_scaling_factor,
            cfg.moe_capacity_factor, cfg.tie_embeddings) == (
        "sigmoid", 2.446, 0.0, False)
    tree = lm.abstract(cfg)
    assert tree["dense_blocks"]["mlp"]["w_up"].shape == (1, 2048, 11264)
    assert tree["blocks"]["moe"]["w_up"].shape == (26, 64, 2048, 1408)
    assert tree["blocks"]["moe"]["shared"]["w_up"].shape == (26, 2048, 2816)


def test_the_chip_phase_runs_at_tiny_widths(monkeypatch):
    """``chip_smoke.py --arch moonlight-16b-a3b-s9``'s phase at tiny widths
    on the CPU: staging, prefill, the resume op and decode, against the
    reference within the phase's own bounds."""
    import json
    from pathlib import Path

    import chip_smoke

    for name, value in dict(ML_SLOTS=4, ML_MAX_SEQ=48, ML_PROMPT=40,
                            ML_DECODE=3, ML_FROM=1, ML_TO=3,
                            ML_BLOCK_Q=16).items():
        monkeypatch.setattr(chip_smoke, name, value)
    root = Path(chip_smoke.ROOT)
    cfg = json.loads((root / "bench/configs/moonlight-16b-a3b-s9.json")
                     .read_text())
    cfg.update(d_model=64, num_heads=2, kv_lora_rank=32, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, vocab_size=256, d_ff=32,
               dense_d_ff=96, num_layers=3)
    out = chip_smoke.phase_moonlight(jax.devices(), chip_smoke.CompileClock(),
                                     cfg)
    assert out["f32_max_rel_err"] <= chip_smoke.TOL_ML_F32
    assert out["bf16_median_rel_err"] <= chip_smoke.TOL_ML_BF16_MEDIAN
