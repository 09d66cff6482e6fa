"""The transfer mixes: the same state, sessions and order of ops for one
seed, other contents for another, and every op within the ServeState's
slots and the mix's snapshots."""
import json

import jax
import numpy as np
import pytest

from bench import harness, weights
from bench.generators import transfer
from tiny_cells import REPO, TINY_TRAFFIC

BIG_SEED = 2 ** 31 + 12345


def _ops(root, cell, seed, n=9):
    c = harness.find_cell(root, cell)
    ctx = harness.Context(root, c, seed, False, jax.devices()[:1],
                          log=lambda m: None)
    run = transfer.setup(ctx)
    for _ in range(n):
        run._op()
    return run


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_resume_repeats_per_seed_and_keeps_to_its_slots(tiny_root, seed):
    a, b = (_ops(tiny_root, "tiny-hybrid.resume", seed) for _ in range(2))
    assert a.expected_slots == b.expected_slots
    same = jax.tree_util.tree_map(np.array_equal, jax.device_get(a.digests),
                                  jax.device_get(b.digests))
    assert all(jax.tree_util.tree_leaves(same))
    tr = TINY_TRAFFIC["resume-tiny"]
    assert all(len(holds) == tr["slots"] and
               all(-1 <= j < tr["snapshots"] for j in holds)
               for holds in a.expected_slots)
    # every slot takes a session once the ops have gone round the slots
    assert all(j >= 0 for j in a.expected_slots[-1])


def test_seeds_stage_other_contents(tiny_root):
    a = _ops(tiny_root, "tiny-ssm.stage", 1, n=1)
    b = _ops(tiny_root, "tiny-ssm.stage", BIG_SEED, n=1)
    digests = jax.device_get((a.expected0, b.expected0))
    differ = jax.tree_util.tree_map(lambda x, y: not np.array_equal(x, y),
                                    *digests)
    # the random leaves (norm scales and D are ones for every seed)
    assert differ["params"]["embed"]["tok"] and differ["params"]["blocks"]["ssm"]["wz"]
    assert all(jax.tree_util.tree_leaves(differ["cache"]))


def test_weights_and_state_repeat_per_seed_and_differ_between_seeds():
    cfg = json.loads((REPO / "bench/configs/mamba2-1.3b.json").read_text())
    cfg.update(num_layers=1, d_model=32, vocab_size=64, ssm_state=8,
               ssm_head_dim=8)
    fam = harness.load_family(REPO, cfg["family"])
    a = weights.make_params(fam, cfg, BIG_SEED)
    b = weights.make_params(fam, cfg, BIG_SEED)
    c = weights.make_params(fam, cfg, BIG_SEED - 2 ** 31)
    same = jax.tree_util.tree_map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree_util.tree_leaves(same))
    assert not bool((a["blocks"]["ssm"]["wz"] == c["blocks"]["ssm"]["wz"]).all())
    A = -np.exp(np.asarray(a["blocks"]["ssm"]["A_log"], np.float32))
    assert (A <= -1 + 1e-2).all() and (A >= -16.1).all()
    s1 = weights.make_random({"x": ((4, 5), np.float32)}, BIG_SEED, 3)
    s2 = weights.make_random({"x": ((4, 5), np.float32)}, BIG_SEED, 3)
    assert np.array_equal(s1["x"], s2["x"])
