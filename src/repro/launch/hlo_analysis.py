"""HLO parsing: collective bytes + op census from compiled/lowered text.

cost_analysis() has no collective numbers, so the ICI roofline term comes
from here: we sum the *output* operand sizes of every collective op in the
compiled HLO (post-SPMD-partitioning, so shapes are per-device).
"""
from __future__ import annotations

import re
from typing import Any, Dict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

# e.g.  %ag = bf16[4,128]{1,0} all-gather(%x), ...
# shapes may be tuples with /*index=N*/ comments:
#   %ar = (f32[4]{0}, /*index=1*/f32[8]{0}) all-reduce(%a, %b), ...
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"\s(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")


def _parse_collective(line: str):
    """Return (op, shape_text) for a collective instruction line, else None."""
    eq = line.find("= ")
    if eq < 0:
        return None
    m = _OP_RE.search(line, eq)
    if not m:
        return None
    return m.group(1), m.group(2) or "", line[eq + 1: m.start()]


def _shape_bytes(shape_text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_stats(hlo_text: str) -> Dict[str, Any]:
    """Per-op-kind count + output bytes (per device) from HLO text."""
    stats: Dict[str, Dict[str, float]] = {
        op: {"count": 0, "bytes": 0} for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        parsed = _parse_collective(line.strip())
        if parsed is None:
            continue
        op, suffix, shape_text = parsed
        # skip the -done halves of async pairs (bytes counted at -start)
        if suffix == "-done":
            continue
        stats[op]["count"] += 1
        stats[op]["bytes"] += _shape_bytes(shape_text)
    total_bytes = sum(s["bytes"] for s in stats.values())
    total_count = sum(s["count"] for s in stats.values())
    return {"per_op": stats, "total_bytes": total_bytes,
            "total_count": total_count}


def cost_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a plain dict."""
    return dict(compiled.cost_analysis() or {})


MEMORY_KEYS = ("argument_size_in_bytes", "output_size_in_bytes",
               "temp_size_in_bytes", "generated_code_size_in_bytes",
               "alias_size_in_bytes", "peak_memory_in_bytes")


def memory_dict(mem) -> Dict[str, float]:
    """``compiled.memory_analysis()`` as a plain dict (empty if None)."""
    if mem is None:
        return {}
    return {key: int(getattr(mem, key)) for key in MEMORY_KEYS}


def op_census(hlo_text: str, top: int = 25) -> Dict[str, int]:
    """Instruction census — the PTX-LOC analogue for Tables 3-4."""
    counts: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.search(r"=\s*[^=]*?\s([a-z][a-z0-9-]*)\(", line)
        if m:
            op = m.group(1)
            counts[op] = counts.get(op, 0) + 1
    return dict(sorted(counts.items(), key=lambda kv: -kv[1])[:top])


def hlo_line_count(hlo_text: str) -> int:
    return sum(1 for l in hlo_text.splitlines()
               if "=" in l and not l.strip().startswith(("//", "#")))
