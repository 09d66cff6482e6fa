"""Bytes computed from a configuration's shapes alone.

These are the yardstick's own counts: the ServeState a cell stages, one
slot's session snapshot, and what the arena unpack must read and write.
They read only the configuration file's numbers, through the layout its
family file gives (``bench/families/<family>.py``), never the program.
"""
from __future__ import annotations

import math

BYTES = {"bfloat16": 2, "float32": 4, "int32": 4}


def _leaves(tree):
    for v in tree.values():
        if isinstance(v, dict):
            yield from _leaves(v)
        else:
            yield v


def params_bytes(family, cfg: dict) -> int:
    return (sum(math.prod(s) for s in _leaves(family.param_shapes(cfg)))
            * BYTES[cfg["param_dtype"]])


def cache_bytes(family, cfg: dict, slots: int, max_seq: int) -> dict:
    """Bytes of each cache leaf of a ``slots x max_seq`` ServeState."""
    return {k: math.prod(shape) * BYTES[dtype]
            for k, (shape, dtype) in family.cache_shapes(cfg, slots, max_seq).items()}


def serve_state_bytes(family, cfg: dict, slots: int, max_seq: int) -> int:
    """Params + cache + slot table (``rid`` and ``pos``, int32 each)."""
    return (params_bytes(family, cfg)
            + sum(cache_bytes(family, cfg, slots, max_seq).values())
            + 2 * slots * 4)


def snapshot_bytes(family, cfg: dict, slots: int, max_seq: int) -> int:
    """One slot's session: its row of every cache leaf plus its slot-table
    row."""
    return (sum(cache_bytes(family, cfg, slots, max_seq).values()) // slots
            + 2 * 4)


def unpack_bytes(family, cfg: dict, slots: int, max_seq: int) -> int:
    """The least HBM traffic of the arena unpack of one whole-state pass:
    every params and cache leaf read once out of its bucket and written
    once as a leaf."""
    return 2 * (params_bytes(family, cfg)
                + sum(cache_bytes(family, cfg, slots, max_seq).values()))
