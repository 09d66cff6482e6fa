"""The benchmark's byte counts: the closed forms of its cells, and
agreement with the program's own shapes."""
import json

import jax
import numpy as np
import pytest

from bench import costs, harness, peaks, weights
from bench.model import model_api
from tiny_cells import REPO, TINY_HYBRID, TINY_SSM


def _cfg(name):
    cfg = json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text())
    return harness.load_family(REPO, cfg["family"]), cfg


def _nbytes(tree):
    return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
               for x in jax.tree_util.tree_leaves(tree))


def test_closed_forms_of_the_cells():
    fam, mamba = _cfg("mamba2-1.3b")
    # 48 x (2 x 2048 x 4096 + 2 x 2048 x 128 + 2048 x 64 + 4096 x 2048
    #       + 3 x 64 + 4 x 4096 + 2 x 4096 + 2048) + 50288 x 2048 + 2048
    assert costs.params_bytes(fam, mamba) == 2 * 1_343_695_872
    # + state 48 x 8 x 64 x 64 x 128 x 4 + conv 48 x 8 x 3 x 4096 x 2
    # + pos 8 x 4 + slot table 2 x 8 x 4
    assert costs.serve_state_bytes(fam, mamba, 8, 128) == (
        2 * 1_343_695_872 + 805_306_368 + 9_437_184 + 32 + 64)
    fam, zamba = _cfg("zamba2-2.7b-d18")
    # head size 160: q, k, v and o are 2560 x 32 x 160 each
    shared = 4 * 2560 * 32 * 160 + 3 * 2560 * 10240 + 2 * 2560
    per_layer = (2 * 2560 * 5120 + 2 * 2560 * 64 + 2560 * 80 + 5120 * 2560
                 + 3 * 80 + 4 * 5120 + 2 * 5120 + 2560)
    assert costs.params_bytes(fam, zamba) == 2 * (
        18 * per_layer + shared + 32000 * 2560 + 2560) == 1_861_952_960
    kv = 3 * 8 * 2048 * 32 * 160 * 2
    cache = 18 * 8 * 80 * 64 * 64 * 4 + 18 * 8 * 3 * 5120 * 2 + 2 * kv + 32
    assert costs.serve_state_bytes(fam, zamba, 8, 2048) == (
        1_861_952_960 + cache + 64) == 3_061_753_376
    assert costs.snapshot_bytes(fam, zamba, 8, 2048) == cache // 8 + 8 == 149_975_052
    assert costs.params_bytes(fam, dict(zamba, num_layers=54)) == 4_733_860_160


@pytest.mark.parametrize("name,slots,max_seq", [
    ("mamba2-1.3b", 8, 128), ("zamba2-2.7b-d18", 8, 2048),
    ("mamba2-1.3b", 4, 512), ("zamba2-2.7b-d18", 4, 512)])
def test_bytes_match_the_programs_abstract_state(name, slots, max_seq):
    fam, cfg = _cfg(name)
    api = model_api(cfg)
    assert costs.params_bytes(fam, cfg) == _nbytes(api.abstract())
    cache = api.init_cache(slots, max_seq, abstract_only=True)
    assert costs.cache_bytes(fam, cfg, slots, max_seq) == {
        k: _nbytes(v) for k, v in cache.items()}


@pytest.mark.parametrize("tiny", [TINY_SSM, TINY_HYBRID])
def test_seeded_weights_have_the_programs_layout(tiny):
    fam, base = _cfg("mamba2-1.3b" if tiny is TINY_SSM else "zamba2-2.7b-d18")
    cfg = dict(base, **tiny)
    want = jax.tree_util.tree_map(lambda s: s.shape, model_api(cfg).abstract())
    assert jax.tree_util.tree_map(tuple, fam.param_shapes(cfg),
                                  is_leaf=lambda x: isinstance(x, tuple)) == want
    params = weights.make_params(fam, cfg, 3)
    assert jax.tree_util.tree_map(lambda a: a.shape, params) == want


def test_unpack_bytes():
    fam, mamba = _cfg("mamba2-1.3b")
    state = costs.serve_state_bytes(fam, mamba, 8, 128)
    assert costs.unpack_bytes(fam, mamba, 8, 128) == 2 * (state - 64)


def test_peaks_table_raises_for_an_unknown_kind():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
