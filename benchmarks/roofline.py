"""Roofline analysis from the dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch x shape x mesh) cell:
    compute_s    = HLO_FLOPs_corrected / peak_FLOPs        (per device)
    memory_s     = HLO_bytes_corrected / HBM_bw
    collective_s = collective_bytes_corrected / ICI_bw
with the scan-trip correction from the per-layer probes (see launch/probe.py)
and the peaks of the cell's ``device_kind`` (:data:`PEAKS`).  MODEL_FLOPS is the analytic 6*N_active*D (train) /
2*N_active*D (inference) + attention-context term; the useful-compute ratio
MODEL_FLOPS / (HLO_FLOPs * devices) catches remat/dispatch waste.
"""
from __future__ import annotations

import glob
import json
import os
import sys
from typing import Dict, List, Optional

from repro.configs.shapes import SHAPES
from repro.models import registry

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s ICI per chip over 4 links (one 50 GB/s link is budgeted).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks_for(device_kind: Optional[str]) -> Dict[str, float]:
    """The peak table row of ``device_kind``; a kind not in the table is
    an error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)}); add a row to PEAKS with "
                       f"its source")
    return PEAKS[device_kind]


# ---------------------------------------------------------------------------
# analytic model flops
# ---------------------------------------------------------------------------

def _per_token_matmul_flops(cfg) -> float:
    """Forward matmul flops per token, excluding the attention-context term."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    total = 0.0
    if cfg.family in ("dense", "moe", "vlm", "encdec", "hybrid"):
        attn_proj = 2 * d * hd * (2 * cfg.num_heads + 2 * cfg.num_kv_heads)
        mlp_mats = 3 if cfg.gated_mlp else 2
        if cfg.family == "moe":
            mlp = (cfg.experts_per_token * 2 * 3 * d * cfg.d_ff
                   + 2 * d * cfg.num_experts)
            if cfg.moe_dense_residual:
                mlp += 2 * mlp_mats * d * cfg.d_ff
        else:
            mlp = 2 * mlp_mats * d * cfg.d_ff
        per_attn_layer = attn_proj + mlp
    if cfg.family in ("ssm", "hybrid"):
        di, N, nh = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
        Q = cfg.ssm_chunk
        ssm_proj = 2 * d * (2 * di + 2 * N + nh) + 2 * di * d
        ssd = 4 * di * N + 2 * Q * N + 2 * Q * di
        conv = 2 * cfg.ssm_conv_width * di
        per_ssm_layer = ssm_proj + ssd + conv

    if cfg.family in ("dense", "moe", "vlm"):
        total = cfg.num_layers * per_attn_layer
    elif cfg.family == "ssm":
        total = cfg.num_layers * per_ssm_layer
    elif cfg.family == "hybrid":
        napps = -(-cfg.num_layers // cfg.attn_every)
        total = cfg.num_layers * per_ssm_layer + napps * per_attn_layer
    elif cfg.family == "encdec":
        # decoder layers add cross-attention (k/v/q/o over src handled in ctx)
        total = cfg.num_layers * (attn_proj * 2 + mlp)
    total += 2 * d * cfg.vocab_size          # unembed
    return float(total)


def _attn_ctx_flops(cfg, S_eff: float, tokens: float) -> float:
    """scores + PV: 4 * H * hd * S_eff per token per attention layer."""
    if cfg.family == "ssm":
        return 0.0
    n_attn = cfg.num_layers
    if cfg.family == "hybrid":
        n_attn = -(-cfg.num_layers // cfg.attn_every)
    per_tok = 4 * cfg.num_heads * cfg.resolved_head_dim * S_eff * n_attn
    if cfg.family == "encdec":
        src = S_eff / cfg.src_ratio
        per_tok += 4 * cfg.num_heads * cfg.resolved_head_dim * src * cfg.num_layers
    return float(per_tok * tokens)


def model_flops(cfg, shape) -> float:
    B, S = shape.global_batch, shape.seq_len
    fwd_per_tok = _per_token_matmul_flops(cfg)
    if shape.mode == "train":
        tokens = B * S
        flops = 3 * (fwd_per_tok * tokens + _attn_ctx_flops(cfg, S / 2, tokens))
    elif shape.mode == "prefill":
        tokens = B * S
        flops = fwd_per_tok * tokens + _attn_ctx_flops(cfg, S / 2, tokens)
    else:  # decode: one token per sequence against an S-token cache
        tokens = B
        flops = fwd_per_tok * tokens + _attn_ctx_flops(cfg, S, tokens)
    return float(flops)


# ---------------------------------------------------------------------------
# table builder
# ---------------------------------------------------------------------------

def _advice(dom: str, cell: Dict) -> str:
    arch = cell["arch"]
    if dom == "compute":
        return ("compute-bound: raise MXU utilization (bigger per-chip tiles, "
                "bf16 everywhere, fuse elementwise into matmuls)")
    if dom == "memory":
        return ("HBM-bound: fuse ops / cut activation re-reads (flash kernels,"
                " remat policy, fp8/bf16 cache) to lower bytes per step")
    return ("collective-bound: reshard to cut all-gathers (larger FSDP shards,"
            " overlap collectives with compute, int8-compress gradients)")


def load_cells(art_dir: str) -> List[Dict]:
    cells = []
    for f in sorted(glob.glob(os.path.join(art_dir, "*.json"))):
        with open(f) as fh:
            cells.append(json.load(fh))
    return cells


def analyse(cell: Dict) -> Optional[Dict]:
    if "skipped" in cell or "error" in cell:
        return None
    cfg = registry.load_config(cell["arch"])
    shape = SHAPES[cell["shape"]]
    corr = cell.get("corrected") or {
        "flops": cell["flops"], "bytes": cell["bytes_accessed"],
        "collective_bytes": cell["collectives"]["total_bytes"]}
    n_dev = cell["devices"]
    peaks = peaks_for(cell.get("device_kind"))
    compute_s = corr["flops"] / peaks["flops"]
    memory_s = corr["bytes"] / peaks["hbm_bw"]
    coll_s = corr["collective_bytes"] / peaks["ici_bw"]
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    dom = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    hlo_total = corr["flops"] * n_dev
    return {
        "arch": cell["arch"], "shape": cell["shape"],
        "mesh": cell.get("mesh_name", cell.get("mesh", "?")),
        "devices": n_dev,
        "compute_s": compute_s, "memory_s": memory_s, "collective_s": coll_s,
        "dominant": dom,
        "model_flops": mf,
        "useful_ratio": mf / hlo_total if hlo_total else 0.0,
        "roofline_fraction": compute_s / max(terms.values()) if max(
            terms.values()) > 0 else 0.0,
        "advice": _advice(dom, cell),
    }


def markdown_table(rows: List[Dict]) -> str:
    hdr = ("| arch | shape | mesh | compute s | memory s | collective s | "
           "bottleneck | MODEL_FLOPS | useful | roofline frac |\n"
           "|---|---|---|---|---|---|---|---|---|---|")
    lines = [hdr]
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['compute_s']:.3f} | {r['memory_s']:.3f} "
            f"| {r['collective_s']:.3f} | {r['dominant']} "
            f"| {r['model_flops']:.2e} | {r['useful_ratio']:.2f} "
            f"| {r['roofline_fraction']:.2f} |")
    return "\n".join(lines)


def run(art_dir: str = "artifacts/dryrun", out=sys.stdout) -> List[Dict]:
    rows = [a for a in (analyse(c) for c in load_cells(art_dir)) if a]
    rows.sort(key=lambda r: (r["mesh"], r["arch"], r["shape"]))
    print("arch,shape,mesh,compute_s,memory_s,collective_s,dominant,"
          "model_flops,useful_ratio,roofline_fraction", file=out)
    for r in rows:
        print(f"{r['arch']},{r['shape']},{r['mesh']},{r['compute_s']:.4f},"
              f"{r['memory_s']:.4f},{r['collective_s']:.4f},{r['dominant']},"
              f"{r['model_flops']:.3e},{r['useful_ratio']:.3f},"
              f"{r['roofline_fraction']:.3f}", file=out)
    return rows


if __name__ == "__main__":
    run(sys.argv[1] if len(sys.argv) > 1 else "artifacts/dryrun")
