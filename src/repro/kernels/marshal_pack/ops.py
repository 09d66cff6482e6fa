"""jit'd wrappers: arena pack/unpack for pytrees via the gather kernel.

Bridges ``repro.core.arena`` layouts to the tile-map representation: leaves
are padded to TILE elements, the map is built once per layout (host side,
cached), then pack/unpack are single kernel launches.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...core import arena as arena_lib
from ...core import engine as engine_lib

from . import kernel as K
from . import ref

TILE = K.SUBLANE * K.LANE  # 1024 elements


def _pad_len(n: int) -> int:
    return -(-n // TILE) * TILE


def build_tile_maps(shapes, layout: "arena_lib.ArenaLayout" = None
                    ) -> Tuple[np.ndarray, np.ndarray, int]:
    """For a list of leaf shapes: (pack_map, unpack_map, n_tiles).

    Source pool layout: leaves concatenated in declaration order, each
    padded to a TILE multiple.  Packed layout: tiles in ARENA order — when a
    ``layout`` is given, the destination ordering is derived from the real
    arena slot offsets (the requestList), not assumed to be the declaration
    order.  pack_map[i] gives the source tile of packed tile i; unpack_map
    is the inverse permutation.
    """
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    tiles_per = [_pad_len(s) // TILE for s in sizes]
    n_tiles = sum(tiles_per)
    src_start = np.concatenate([[0], np.cumsum(tiles_per)]).astype(np.int64)
    if layout is not None:
        if len(layout.slots) != len(shapes):
            raise ValueError("layout does not match leaf shapes")
        # destination order = arena order: offsets are per-BUCKET cursors,
        # so bucket must lead the key or multi-dtype layouts would
        # interleave colliding offsets across buckets
        order = sorted(range(len(shapes)),
                       key=lambda i: (layout.slots[i].bucket,
                                      layout.slots[i].offset))
    else:
        order = range(len(shapes))
    pack_map = np.concatenate(
        [np.arange(src_start[i], src_start[i] + tiles_per[i])
         for i in order]).astype(np.int32) if n_tiles else \
        np.zeros((0,), np.int32)
    unpack_map = np.argsort(pack_map).astype(np.int32)
    return pack_map, unpack_map, n_tiles


def flatten_to_pool(leaves, dtype) -> jax.Array:
    """Concatenate leaves (padded per-leaf to TILE) into the source pool."""
    parts = []
    for leaf in leaves:
        flat = jnp.ravel(leaf).astype(dtype)
        pad = _pad_len(flat.size) - flat.size
        if pad:
            flat = jnp.pad(flat, (0, pad))
        parts.append(flat)
    return jnp.concatenate(parts) if parts else jnp.zeros((0,), dtype)


def pool_to_leaves(pool: jax.Array, shapes, dtype):
    out = []
    off = 0
    for s in shapes:
        n = int(np.prod(s))
        out.append(pool[off: off + n].reshape(s).astype(dtype))
        off += _pad_len(n)
    return out


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack_pool(pool: jax.Array, tile_map: jax.Array, interpret: bool = False
              ) -> jax.Array:
    """One kernel launch: gather source tiles into the packed arena."""
    mat = pool.reshape(-1, K.LANE)
    out = K.gather_tiles(mat, tile_map, interpret=interpret)
    return out.reshape(-1)


def pack_tree(tree: Any, *, interpret: bool = False) -> Tuple[jax.Array, Any]:
    """Marshal a (single-dtype) pytree into one contiguous buffer.

    The tile map is derived from the arena plan (requestList) for the tree
    at TILE alignment — the kernel packs into the same slot ordering the
    arena engine uses, instead of assuming declaration order."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    dtype = leaves[0].dtype
    shapes = [l.shape for l in leaves]
    layout = engine_lib.cached_plan(tree, align_elems=TILE)
    pack_map, unpack_map, _ = build_tile_maps(shapes, layout=layout)
    pool = flatten_to_pool(leaves, dtype)
    packed = pack_pool(pool, jnp.asarray(pack_map), interpret=interpret)
    meta = {"treedef": treedef, "shapes": shapes, "dtype": dtype,
            "layout": layout, "unpack_map": jnp.asarray(unpack_map)}
    return packed, meta


def unpack_tree(packed: jax.Array, meta, *, interpret: bool = False) -> Any:
    pool = pack_pool(packed, meta["unpack_map"], interpret=interpret)
    leaves = pool_to_leaves(pool, meta["shapes"], meta["dtype"])
    return jax.tree_util.tree_unflatten(meta["treedef"], leaves)
