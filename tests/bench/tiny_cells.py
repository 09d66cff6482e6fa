"""Tiny cells for the benchmark's tests, added to a copy of the checkout as
files and entries, the way a later change adds a configuration, a mix or
a cell; and the checkout root on the import path."""
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

TINY_SSM = {"name": "tiny-ssm", "num_layers": 4, "d_model": 64,
            "vocab_size": 257, "ssm_state": 16, "ssm_head_dim": 16,
            "ssm_chunk": 8}
TINY_HYBRID = dict(TINY_SSM, name="tiny-hybrid", family="hybrid",
                   num_layers=4, num_heads=4, num_kv_heads=4, head_dim=32,
                   d_ff=96, attn_every=2)
TINY_TRAFFIC = {
    "stage-tiny": {"generator": "transfer", "op": "stage", "slots": 4,
                   "max_seq": 16, "dp": 1},
    "resume-tiny": {"generator": "transfer", "op": "resume", "slots": 4,
                    "max_seq": 16, "dp": 1, "snapshots": 3},
}
TINY_CELLS = {"tiny-ssm.stage": ("tiny-ssm", "stage-tiny"),
              "tiny-hybrid.resume": ("tiny-hybrid", "resume-tiny")}


def _add_tiny(root: Path) -> None:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    base = {"mamba2-1.3b": TINY_SSM, "zamba2-2.7b-d18": TINY_HYBRID}
    for parent, tiny in base.items():
        cfg = json.loads((root / f"bench/configs/{parent}.json").read_text())
        cfg.update(tiny)
        (root / f"bench/configs/{tiny['name']}.json").write_text(json.dumps(cfg))
        spec["configs"].append({"name": tiny["name"], "source": "test",
                                "file": f"bench/configs/{tiny['name']}.json",
                                "reduced": [], "why": "test"})
    for name, traffic in TINY_TRAFFIC.items():
        (root / f"bench/traffic/{name}.json").write_text(json.dumps(traffic))
    for name, (config, traffic) in TINY_CELLS.items():
        spec["workloads"].append({"name": name, "config": config,
                                  "traffic": traffic, "chips": 1, "why": "test"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if m.get("workloads"):
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))


def make_tiny_root(root: Path) -> Path:
    """A copy of ``BENCHMARK.json`` and ``bench/`` under ``root``, with the
    tiny cells added."""
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _add_tiny(root)
    return root
