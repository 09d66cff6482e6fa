"""The harness finds cells, configurations, traffic mixes and per-layer
metrics by name, with no edit to its own files; and it refuses to run
without a chip."""
import json
import os
import shutil
import subprocess
import sys
import time

from bench import harness
from tiny_cells import REPO


def test_the_real_cells_resolve():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.find_cell(REPO, w["name"])
        assert cell.chips == 1 and cell.traffic["generator"] == "transfer"
        assert cell.family.__name__ == "bench_family_" + cell.config["family"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.load_reader(REPO, m["name"]))
            assert m["moves"] in [e["name"] for e in cell.end_to_end]


def test_added_files_and_entries_are_found_by_name(tiny_root):
    """A new configuration, traffic mix, per-layer metric and cell, each
    added as a file plus its BENCHMARK.json entry, run through the
    unchanged harness, and the new metric is read in the traced run."""
    root = tiny_root
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/tiny-ssm.json").read_text())
    (root / "bench/configs/tiny-ssm-b.json").write_text(
        json.dumps(dict(cfg, name="tiny-ssm-b", num_layers=1)))
    spec["configs"].append({"name": "tiny-ssm-b", "source": "test",
                            "file": "bench/configs/tiny-ssm-b.json",
                            "reduced": [], "why": "test"})
    (root / "bench/traffic/stage-two-slots.json").write_text(json.dumps(
        {"generator": "transfer", "op": "stage", "slots": 2, "max_seq": 8,
         "dp": 1}))
    (root / "bench/metrics/passes_in_window.py").write_text(
        "def read(ctx):\n    return ctx.run.counters.get('passes')\n")
    spec["workloads"].append({"name": "tiny-ssm-b.stage-two-slots",
                              "config": "tiny-ssm-b",
                              "traffic": "stage-two-slots", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "passes_in_window", "unit": "passes",
                              "better": "higher", "source": "program_counter",
                              "layer": "test", "moves": "pass_ms",
                              "workloads": ["tiny-ssm-b.stage-two-slots"]})
    for m in spec["end_to_end"]:
        if m["name"] == "pass_ms":
            m["workloads"].append("tiny-ssm-b.stage-two-slots")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.find_cell(root, "tiny-ssm-b.stage-two-slots")
    assert cell.config["num_layers"] == 1 and cell.traffic["slots"] == 2
    assert [m["name"] for m in cell.per_layer] == ["passes_in_window"]
    out = harness.run_cell(root, "tiny-ssm-b.stage-two-slots", 3, 0.3, True,
                           t_start=time.perf_counter(), require_chip=False,
                           log=lambda m: None)
    assert out["correct"]
    assert out["metrics"]["passes_in_window"]["value"] == out["attempted"] > 0
    assert list(out)[-1] == "checks"


# The program's dense decoder (``models/lm.py``: norm, attention, norm,
# gated MLP per layer, a KV cache per layer), as a later change would add
# it: one new file under bench/families/.
DENSE_FAMILY = """
def param_shapes(cfg):
    d, H, KV, F, L = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                      cfg["d_ff"], cfg["num_layers"])
    hd = cfg.get("head_dim") or d // H
    tree = {"embed": {"tok": (cfg["vocab_size"], d)},
            "final_norm": {"scale": (d,)},
            "blocks": {"ln1": {"scale": (L, d)}, "ln2": {"scale": (L, d)},
                       "attn": {"wq": (L, d, H, hd), "wk": (L, d, KV, hd),
                                "wv": (L, d, KV, hd), "wo": (L, H, hd, d)},
                       "mlp": {"w_gate": (L, d, F), "w_up": (L, d, F),
                               "w_down": (L, F, d)}}}
    if not cfg.get("tie_embeddings"):
        tree["embed"]["lm_head"] = (d, cfg["vocab_size"])
    return tree


def cache_shapes(cfg, slots, max_seq):
    hd = cfg.get("head_dim") or cfg["d_model"] // cfg["num_heads"]
    kv = ((cfg["num_layers"], slots, max_seq, cfg["num_kv_heads"], hd),
          cfg["compute_dtype"])
    return {"pos": ((slots,), "int32"), "k": kv, "v": kv}


def init_leaf(path, shape, key, cfg):
    import jax
    import jax.numpy as jnp

    if path.endswith("scale"):
        return jnp.ones(shape, jnp.float32)
    return 0.02 * jax.random.normal(key, shape, jnp.float32)
"""


def test_a_family_added_as_a_file_is_found_by_name(tiny_root):
    """A configuration of a family the benchmark had no file for (the
    program's dense decoder) runs a stage cell once its family file and
    entries are added; the harness and the generator are not edited."""
    root = tiny_root
    (root / "bench/families/dense.py").write_text(DENSE_FAMILY)
    (root / "bench/configs/tiny-dense.json").write_text(json.dumps(
        {"name": "tiny-dense", "family": "dense", "num_layers": 2,
         "d_model": 32, "num_heads": 4, "num_kv_heads": 2, "d_ff": 64,
         "vocab_size": 97, "tie_embeddings": False, "norm": "rmsnorm",
         "param_dtype": "bfloat16", "compute_dtype": "bfloat16"}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-dense", "source": "test",
                            "file": "bench/configs/tiny-dense.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-dense.stage-tiny",
                              "config": "tiny-dense", "traffic": "stage-tiny",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "pass_ms":
            m["workloads"].append("tiny-dense.stage-tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.find_cell(root, "tiny-dense.stage-tiny")
    assert cell.family.__name__ == "bench_family_dense"
    out = harness.run_cell(root, "tiny-dense.stage-tiny", 11, 0.3, False,
                           t_start=time.perf_counter(), require_chip=False,
                           log=lambda m: None)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"pass_ms", "setup_s"}


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mamba2-1.3b.stage",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_result(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(REPO / "src", tmp_path / "src")
    proc = _run(tmp_path)
    assert proc.returncode == 2
    assert "no TPU" in proc.stderr and "cpu" in proc.stderr
    assert "{" not in proc.stdout
