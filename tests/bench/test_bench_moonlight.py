"""The Moonlight-16B-A3B cell (``moonlight-16b-a3b-s9.resume-8k``): its
closed forms, its family file against the program's own layout, its
traffic, and ``correct`` of a tiny cell of the same family and op on the
CPU."""
import json
import time

import jax
import numpy as np
import pytest

from bench import costs, harness, weights
from bench.model import model_api
from tiny_cells import REPO, make_tiny_root

CELL = "moonlight-16b-a3b-s9.resume-8k"
TINY_MOE = {"name": "tiny-moe", "num_layers": 3, "num_hidden_layers": 3,
            "d_model": 32, "num_heads": 2, "kv_lora_rank": 16,
            "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
            "d_ff": 16, "dense_d_ff": 24, "num_experts": 8,
            "experts_per_token": 2, "experts_held": 2, "expert_offset": 2,
            "vocab_size": 97}
TINY_TRAFFIC = {"generator": "transfer", "op": "resume", "slots": 4,
                "max_seq": 16, "dp": 1, "snapshots": 5}


def _cfg():
    cfg = json.loads(
        (REPO / "bench/configs/moonlight-16b-a3b-s9.json").read_text())
    return harness.load_family(REPO, cfg["family"]), cfg


def _nbytes(tree):
    return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
               for x in jax.tree_util.tree_leaves(tree))


def test_closed_forms_of_the_moonlight_cell():
    fam, cfg = _cfg()
    d = 2048
    # attention: wq 2048 x 16 x (128 + 64), wkv_a 2048 x (512 + 64),
    # kv_norm 512, wk_b and wv_b 512 x 16 x 128 each, wo 16 x 128 x 2048
    attn = d * 16 * 192 + d * 576 + 512 + 2 * 512 * 16 * 128 + 16 * 128 * d
    assert attn == 13_763_072
    norms = 2 * d
    dense = attn + norms + 3 * d * 11264                  # layer 0
    routed = 8 * 3 * d * 1408                             # experts 8-15
    shared = 3 * d * 2 * 1408                             # one MLP 2816 wide
    moe = attn + norms + d * 64 + 64 + routed + shared    # router + bias
    embed = 2 * 20480 * d + d                             # table, head, norm
    n_params = dense + 8 * moe + embed
    assert (8 * routed, 8 * shared) == (553_648_128, 138_412_032)
    assert n_params == 970_107_904
    assert costs.params_bytes(fam, cfg) == 2 * n_params == 1_940_215_808
    # the latent cache, 9 layers x 16 slots x 8192 positions, bf16:
    # c_kv 512 wide, k_pe 64 wide; pos 16 x int32
    cache = costs.cache_bytes(fam, cfg, 16, 8192)
    assert cache == {"c_kv": 9 * 16 * 8192 * 512 * 2,
                     "k_pe": 9 * 16 * 8192 * 64 * 2, "pos": 16 * 4}
    assert cache == {"c_kv": 1_207_959_552, "k_pe": 150_994_944, "pos": 64}
    # + the slot table, rid and pos, 2 x 16 x int32
    assert costs.serve_state_bytes(fam, cfg, 16, 8192) == (
        1_940_215_808 + 1_358_954_560 + 128) == 3_299_170_496
    # one slot's rows of every cache leaf + its slot-table row:
    # 9 x 8192 x 576 x 2 + 4 + 8
    assert costs.snapshot_bytes(fam, cfg, 16, 8192) == (
        9 * 8192 * 576 * 2 + 4 + 8) == 84_934_668
    # whole, the 27-layer model with all 64 experts and the whole vocabulary
    full = dict(cfg, num_layers=27, experts_held=64, vocab_size=163840)
    assert costs.params_bytes(fam, full) == 2 * (
        dense + 26 * (moe + 56 * 3 * d * 1408) + 2 * 163840 * d + d)


@pytest.mark.parametrize("slots,max_seq", [(16, 8192), (4, 512)])
def test_the_family_layout_is_the_programs(slots, max_seq):
    fam, cfg = _cfg()
    api = model_api(cfg)
    want = jax.tree_util.tree_map(lambda s: (s.shape, str(s.dtype)),
                                  api.abstract())
    got = jax.tree_util.tree_map(lambda s: (tuple(s), cfg["param_dtype"]),
                                 fam.param_shapes(cfg),
                                 is_leaf=lambda x: isinstance(x, tuple))
    assert got == want
    cache = api.init_cache(slots, max_seq, abstract_only=True)
    assert {k: (tuple(v.shape), str(v.dtype)) for k, v in cache.items()} == {
        k: (tuple(s), d) for k, (s, d)
        in fam.cache_shapes(cfg, slots, max_seq).items()}
    assert costs.params_bytes(fam, cfg) == _nbytes(api.abstract())


def test_the_configuration_keeps_the_published_values():
    """Every value of the source's config.json is kept under its own key,
    but for the cuts that ``reduced`` names."""
    _, cfg = _cfg()
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]
    changed = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert changed == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert changed <= set(cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (9, 8, 20480)
    # the program's keys agree with the source's
    assert (cfg["num_layers"], cfg["experts_held"], cfg["d_model"],
            cfg["num_heads"], cfg["d_ff"], cfg["dense_d_ff"],
            cfg["num_experts"], cfg["experts_per_token"]) == (
        9, 8, cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["moe_intermediate_size"], cfg["intermediate_size"], 64,
        cfg["num_experts_per_tok"])


def test_every_op_of_the_traffic_changes_its_slot():
    """5 snapshots into 16 slots in turn: 5 is prime to 16, so a slot's
    next session is never the one it holds."""
    tr = json.loads((REPO / "bench/traffic/resume-8k.json").read_text())
    assert tr == {"generator": "transfer", "op": "resume", "slots": 16,
                  "max_seq": 8192, "dp": 1, "snapshots": 5}
    holds = [-1] * tr["slots"]
    for op in range(10 * tr["slots"] * tr["snapshots"]):
        slot, j = op % tr["slots"], op % tr["snapshots"]
        assert holds[slot] != j
        holds[slot] = j


def test_the_real_cell_resolves():
    cell = harness.find_cell(REPO, CELL)
    assert cell.family.__name__ == "bench_family_moe" and cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["pass_ms", "setup_s"]
    assert len(cell.per_layer) == 12


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    root = make_tiny_root(tmp_path_factory.mktemp("checkout"))
    cfg = dict(_cfg()[1], **TINY_MOE)
    (root / "bench/configs/tiny-moe.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/resume-8k-tiny.json").write_text(
        json.dumps(TINY_TRAFFIC))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-moe", "source": "test",
                            "file": "bench/configs/tiny-moe.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-moe.resume-8k-tiny",
                              "config": "tiny-moe",
                              "traffic": "resume-8k-tiny", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m.get("workloads"):
            m["workloads"].append("tiny-moe.resume-8k-tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_seeded_weights_have_the_programs_layout(moe_root):
    cell = harness.find_cell(moe_root, "tiny-moe.resume-8k-tiny")
    want = jax.tree_util.tree_map(lambda s: s.shape,
                                  model_api(cell.config).abstract())
    params = weights.make_params(cell.family, cell.config, 3)
    assert jax.tree_util.tree_map(lambda a: a.shape, params) == want
    moe = params["blocks"]["moe"]
    assert not np.asarray(moe["router_bias"], np.float32).any()
    assert np.asarray(params["dense_blocks"]["ln1"]["scale"] == 1).all()
    std = float(np.std(np.asarray(moe["w_gate"], np.float32)))
    assert 0.015 < std < 0.025


def _run(root, control=False):
    return harness.run_cell(root, "tiny-moe.resume-8k-tiny", 2 ** 31 + 7,
                            0.3, False, t_start=time.perf_counter(),
                            require_chip=False, control=control,
                            log=lambda m: None)


def test_a_tiny_moe_resume_cell_is_correct(moe_root):
    out = _run(moe_root)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"pass_ms", "setup_s"}


def test_its_control_is_not_correct(moe_root):
    out = _run(moe_root, control=True)
    assert not out["correct"]
    assert out["checks"]["passes_mismatched"]["value"] == out["attempted"]
    assert out["checks"]["leaves_mismatched"]["value"] > 0
