"""Scenario families — the paper's two workloads plus four new ones.

Ported from the seed ``benchmarks/scenarios.py``:

  * linear (Fig. 3)  — k-deep chain, all three init/use layouts, with the
                       paper's closed-form data sizes (Eq. 1-2 at 4-byte
                       elements) declared as exact expectations.
  * dense (Fig. 4)   — array-of-structs fanout q, one chained leaf used
                       (Eq. 3); payloads are seeded nonzero randoms so the
                       Algorithm-2 line-7 check actually discriminates.

New families (the ROADMAP's "as many scenarios as you can imagine"):

  * ragged       — uneven fanout and uneven payload sizes per branch.
  * mixed_dtype  — f32/i32/bf16 leaves: multiple marshalling buckets.
  * sweep        — deep-narrow chains vs. wide-shallow fanout, the two
                   extremes of the paper's depth axis.
  * model_state  — real model parameter pytrees from ``repro.models`` at
                   smoke scale (llama3.2-1b, mamba2-1.3b), so the matrix
                   covers production-shaped state, not only toy structs.

Every family function takes a size preset (``smoke``/``quick``/``full``)
and returns concrete :class:`Scenario` cells; the per-cell ``*_case``
constructors are exported so sweep benchmarks can build arbitrary grids
from the same single source of truth.
"""
from __future__ import annotations

import functools
from typing import Any, List, Optional

import numpy as np

from repro.core import TransferSpec, TreePath

from .base import Motion, Scenario, register

LINEAR_LAYOUTS = ("allinit-allused", "allinit-LLused", "LLinit-LLused")

_I32 = 4  # header field bytes (np.int32)
_F32 = 4  # payload element bytes (np.float32)


def chain_access_set(tree: Any, *paths: str,
                     header_fields=("nA", "nL")) -> List[str]:
    """The pages a demand-paging dereference of ``paths`` touches: every
    node header along each chain, plus the final leaf."""
    out: List[str] = []
    seen = set()

    def add(p: str) -> None:
        if p not in seen:
            seen.add(p)
            out.append(p)

    for path in paths:
        tp = TreePath.parse(path)
        for i in range(1, tp.depth):
            prefix = TreePath(tp.steps[:i])
            for h in header_fields:
                hp = prefix.child(h)
                if hp.exists(tree):
                    add(str(hp))
        add(str(tp))
    return out


# ---------------------------------------------------------------------------
# linear (paper Fig. 3)
# ---------------------------------------------------------------------------

def linear_tree(k: int, n: int, layout: str) -> Any:
    """Fig. 3: L1 -> ... -> Lk, each level with header + payload A[n].

    layout: allinit-allused | allinit-LLused | LLinit-LLused
    """
    all_init = layout.startswith("allinit")
    tree = None
    for level in range(k, 0, -1):
        init = all_init or level == k
        node = {"nA": np.int32(n), "nL": np.int32(level),
                "pad": np.zeros(4, np.int32),
                "A": np.random.default_rng(level).standard_normal(
                    n if init else 1).astype(np.float32)}
        if tree is not None:
            node["Lnext"] = tree
        tree = node
    return {"L1": tree}


def linear_chain(k: int) -> str:
    return "L1" + ".Lnext" * (k - 1) + ".A"


def linear_used_paths(k: int, layout: str) -> List[str]:
    if layout.endswith("allused"):
        return ["L1" + ".Lnext" * (i - 1) + ".A" for i in range(1, k + 1)]
    return [linear_chain(k)]


def linear_expected(k: int, n: int, layout: str) -> dict:
    """Paper Eq. 1-2 at this repo's field widths (DESIGN.md §6): each level
    carries a 24-byte int32 header (nA + nL + pad[4]) and a float32 payload
    of n (initialized) or 1 (placeholder) elements."""
    header = 6 * _I32  # nA(4) + nL(4) + pad[4](16) = 24 bytes per level
    all_init = layout.startswith("allinit")
    payload_elems = n * k if all_init else n + (k - 1)
    marshal = Motion(header * k + _F32 * payload_elems, 2)  # i32 + f32 buckets
    if layout.endswith("allused"):
        used = Motion(_F32 * n * k, k)
    else:
        used = Motion(_F32 * n, 1)
    return {"marshal": marshal, "uvm": used, "pointerchain": used}


def linear_case(k: int, n: int, layout: str) -> Scenario:
    return Scenario(
        name=f"linear_k{k}_n{n}_{layout}",
        family="linear",
        build=functools.partial(linear_tree, k, n, layout),
        used_paths=tuple(linear_used_paths(k, layout)),
        uvm_access=None,
        expected=linear_expected(k, n, layout),
        params=dict(k=k, n=n, layout=layout))


@register("linear")
def _linear_family(size: str) -> List[Scenario]:
    k, n = {"smoke": (4, 64), "quick": (6, 1000), "full": (6, 1000)}[size]
    return [linear_case(k, n, layout) for layout in LINEAR_LAYOUTS]


# ---------------------------------------------------------------------------
# dense (paper Fig. 4)
# ---------------------------------------------------------------------------

def dense_tree(q: int, n: int, depth: int = 3, seed: int = 0) -> Any:
    """Fig. 4: each level is an ARRAY of q structures; leaves carry A[n].

    Payloads are seeded nonzero randoms — with the seed's ``np.zeros`` fill,
    the Algorithm-2 line-7 check (got == want * SCALE) was vacuously true
    for a scheme that silently dropped data (0 * SCALE == 0).
    """
    rng = np.random.default_rng(seed)

    def build(d):
        node = {"nA": np.int32(n),
                "A": rng.standard_normal(n).astype(np.float32)}
        if d > 0:
            node["nL"] = np.int32(q)
            node["Lnext"] = [build(d - 1) for _ in range(q)]
        return node

    return {"a0": build(depth)}


def dense_chain(q: int, depth: int = 3) -> str:
    return "a0" + "".join(f".Lnext[{q - 1}]" for _ in range(depth)) + ".A"


def dense_uvm_access_set(q: int, depth: int = 3) -> List[str]:
    """UVM faults the pages touched while dereferencing the chain: the
    headers of every node along it, plus the final A array."""
    out = []
    prefix = "a0"
    for _ in range(depth):
        out.append(prefix + ".nA")
        out.append(prefix + ".nL")
        prefix += f".Lnext[{q - 1}]"
    out.append(prefix + ".nA")
    out.append(prefix + ".A")
    return out


def dense_expected(q: int, n: int, depth: int) -> dict:
    """Paper Eq. 3 at this repo's field widths (DESIGN.md §6): interior
    nodes carry 8-byte headers (nA + nL), leaf nodes 4 (nA), every node a
    float32 payload A[n]."""
    interior = sum(q ** i for i in range(depth))
    leaves = q ** depth
    marshal = Motion(interior * (2 * _I32 + _F32 * n)
                     + leaves * (_I32 + _F32 * n), 2)
    uvm = Motion(2 * _I32 * depth + _I32 + _F32 * n, 2 * depth + 2)
    pointerchain = Motion(_F32 * n, 1)
    return {"marshal": marshal, "uvm": uvm, "pointerchain": pointerchain}


def dense_case(q: int, n: int, depth: int = 3) -> Scenario:
    return Scenario(
        name=f"dense_q{q}_n{n}_d{depth}",
        family="dense",
        build=functools.partial(dense_tree, q, n, depth),
        used_paths=(dense_chain(q, depth),),
        uvm_access=tuple(dense_uvm_access_set(q, depth)),
        expected=dense_expected(q, n, depth),
        params=dict(q=q, n=n, depth=depth))


@register("dense")
def _dense_family(size: str) -> List[Scenario]:
    if size == "smoke":
        return [dense_case(2, 64, 2)]
    if size == "quick":
        return [dense_case(4, 1000, 3)]
    return [dense_case(4, 1000, 3), dense_case(8, 1000, 3)]


# ---------------------------------------------------------------------------
# ragged — uneven fanout, uneven payloads
# ---------------------------------------------------------------------------

def ragged_tree(n: int, seed: int = 7) -> Any:
    """Uneven fanout (3/0/1 children at level 1) and per-branch payload
    sizes from n//4 to 3n — no single (q, n) describes it, which is exactly
    what defeats a harness hardcoded to the paper's two regular shapes."""
    rng = np.random.default_rng(seed)

    def node(size: int, kids: Optional[list] = None) -> dict:
        out = {"nA": np.int32(size),
               "A": rng.standard_normal(size).astype(np.float32)}
        if kids:
            out["nL"] = np.int32(len(kids))
            out["kids"] = kids
        return out

    return {"root": node(n, [
        node(2 * n, [node(n // 4, []), node(3 * n, [])]),
        node(n // 2, []),
        node(n, [node(2 * n, [node(n, [])])]),
    ])}


def ragged_case(n: int) -> Scenario:
    used = ("root.kids[2].kids[0].kids[0].A",   # deepest branch
            "root.kids[0].kids[1].A",           # biggest payload
            "root.kids[1].A")                   # shallow small leaf
    # access paths depend only on the structure, so a tiny skeleton avoids
    # building the full-size payloads twice per case construction
    skel = ragged_tree(4)
    return Scenario(
        name=f"ragged_n{n}",
        family="ragged",
        build=functools.partial(ragged_tree, n),
        used_paths=used,
        uvm_access=tuple(chain_access_set(skel, *used)),
        params=dict(n=n))


@register("ragged")
def _ragged_family(size: str) -> List[Scenario]:
    return [ragged_case(32 if size == "smoke" else 512)]


# ---------------------------------------------------------------------------
# mixed_dtype — multiple marshalling buckets
# ---------------------------------------------------------------------------

def mixed_dtype_tree(n: int, seed: int = 11) -> Any:
    """f32 / i32 / bf16 leaves: marshalling needs one bucket (one DMA) per
    dtype, demand paging and pointerchain stay per-leaf/per-chain."""
    rng = np.random.default_rng(seed)
    return {
        "meta": {"count": np.int32(n),
                 "ids": np.arange(2 * n, dtype=np.int32)},
        "f32": {"a": rng.standard_normal(n).astype(np.float32),
                "b": rng.standard_normal(n // 2).astype(np.float32)},
        "bf16": {"w": rng.standard_normal(n).astype("bfloat16")},
    }


def mixed_dtype_case(n: int) -> Scenario:
    used = ("f32.a", "bf16.w")
    return Scenario(
        name=f"mixed_dtype_n{n}",
        family="mixed_dtype",
        build=functools.partial(mixed_dtype_tree, n),
        used_paths=used,
        uvm_access=tuple(["meta.count"] + list(used)),
        params=dict(n=n))


@register("mixed_dtype")
def _mixed_dtype_family(size: str) -> List[Scenario]:
    return [mixed_dtype_case(48 if size == "smoke" else 1024)]


# ---------------------------------------------------------------------------
# sweep — the depth/width extremes
# ---------------------------------------------------------------------------

def deep_narrow_tree(depth: int, n: int, seed: int = 3) -> Any:
    """A depth-k chain of single-child nodes with one payload at the end:
    the paper's k axis pushed far past Fig. 3's range, minimal payload."""
    rng = np.random.default_rng(seed)
    tree: dict = {"nA": np.int32(n),
                  "A": rng.standard_normal(n).astype(np.float32)}
    for level in range(depth - 1, 0, -1):
        tree = {"nA": np.int32(level), "next": tree}
    return {"root": tree}


def deep_narrow_chain(depth: int) -> str:
    return "root" + ".next" * (depth - 1) + ".A"


def wide_shallow_tree(width: int, n: int, seed: int = 5) -> Any:
    """One level, ``width`` siblings: fanout with no nesting — the opposite
    extreme of deep_narrow on the same total-payload budget axis."""
    rng = np.random.default_rng(seed)
    return {"root": {"nL": np.int32(width),
                     "kids": [{"nA": np.int32(n),
                               "A": rng.standard_normal(n).astype(np.float32)}
                              for _ in range(width)]}}


def deep_narrow_case(depth: int, n: int) -> Scenario:
    used = (deep_narrow_chain(depth),)
    skel = deep_narrow_tree(depth, 1)  # access paths: structure-only
    return Scenario(
        name=f"deep_narrow_d{depth}_n{n}",
        family="sweep",
        build=functools.partial(deep_narrow_tree, depth, n),
        used_paths=used,
        uvm_access=tuple(chain_access_set(skel, *used)),
        params=dict(depth=depth, n=n))


def wide_shallow_case(width: int, n: int) -> Scenario:
    used = tuple(f"root.kids[{i}].A" for i in range(width))
    skel = wide_shallow_tree(width, 1)  # access paths: structure-only
    return Scenario(
        name=f"wide_shallow_w{width}_n{n}",
        family="sweep",
        build=functools.partial(wide_shallow_tree, width, n),
        used_paths=used,
        uvm_access=tuple(chain_access_set(skel, *used)),
        params=dict(width=width, n=n))


@register("sweep")
def _sweep_family(size: str) -> List[Scenario]:
    if size == "smoke":
        return [deep_narrow_case(6, 16), wide_shallow_case(8, 16)]
    return [deep_narrow_case(24, 64), wide_shallow_case(64, 256)]


# ---------------------------------------------------------------------------
# model_state — real parameter pytrees at smoke scale
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _model_params(arch_id: str):
    """Host-resident (numpy) parameter tree of the arch's smoke config.

    Cached per process and treated as read-only: schemes never mutate host
    leaves, and the deterministic PRNGKey keeps expectations exact.
    """
    import jax

    from repro.models import registry as model_registry

    api = model_registry.get(arch_id, smoke=True)
    params = api.init(jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(np.asarray, params)


def model_state_case(arch_id: str) -> Scenario:
    slug = arch_id.replace("-", "_").replace(".", "_")
    return Scenario(
        name=f"model_state_{slug}",
        family="model_state",
        build=functools.partial(_model_params, arch_id),
        # interior chains: declare() expands them to every leaf below —
        # the paper's selective deep copy over struct-valued fields.
        used_paths=("embed", "final_norm"),
        uvm_access=None,
        params=dict(arch=arch_id))


@register("model_state")
def _model_state_family(size: str) -> List[Scenario]:
    archs = ["llama3.2-1b"] if size == "smoke" \
        else ["llama3.2-1b", "mamba2-1.3b"]
    return [model_state_case(a) for a in archs]


# ---------------------------------------------------------------------------
# sharded — per-device arenas over the whole host mesh
# ---------------------------------------------------------------------------

def data_sharding():
    """A 1-D "data" mesh over every available device, leaves split on dim 0
    — built lazily so importing the registry never touches jax devices."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from ..jaxenv import make_mesh

    mesh = make_mesh((jax.device_count(),), ("data",))
    return NamedSharding(mesh, PartitionSpec("data"))


def sharded_tree(n: int, k: int, seed: int = 13) -> Any:
    """Two f32 payloads + one i32 id table, all 1-D with sizes divisible by
    the mesh size ``k`` so every transfer granule splits evenly per device."""
    rng = np.random.default_rng(seed)
    return {
        "w": rng.standard_normal(n).astype(np.float32),
        "v": rng.standard_normal(3 * n).astype(np.float32),
        "ids": np.arange(4 * k, dtype=np.int32),
    }


def sharded_expected(n: int, k: int) -> dict:
    """Closed-form per-device Motion on a k-device mesh: marshal pads each
    dtype bucket to a multiple of k and ships one contiguous sub-range per
    (bucket, device); per-leaf schemes split each granule k ways.  A
    per-device delta transfer's COLD pass ships everything, so its closed
    form equals marshal's (its steady state is the sharded_delta family)."""
    f32_elems = n + 3 * n                 # already divisible by k (n = 16k·…)
    i32_elems = 4 * k
    marshal_bytes = _F32 * f32_elems + _I32 * i32_elems
    used_bytes = _F32 * (n + 3 * n)       # w + v
    if k == 1:
        return {"marshal": Motion(marshal_bytes, 2),
                "marshal_delta": Motion(marshal_bytes, 2),
                "uvm": Motion(used_bytes, 2),
                "pointerchain": Motion(used_bytes, 2)}
    per_leaf = Motion(used_bytes, 2 * k, used_bytes // k, 2)
    marshal = Motion(marshal_bytes, 2 * k, marshal_bytes // k, 2)
    return {"marshal": marshal, "marshal_delta": marshal,
            "uvm": per_leaf, "pointerchain": per_leaf}


def sharded_case(n: int, k: int) -> Scenario:
    used = ("w", "v")
    return Scenario(
        name=f"sharded_n{n}_dev{k}",
        family="sharded",
        build=functools.partial(sharded_tree, n, k),
        used_paths=used,
        uvm_access=used,
        expected=sharded_expected(n, k),
        sharding=data_sharding,
        num_shards=k,
        params=dict(n=n, devices=k))


@register("sharded")
def _sharded_family(size: str) -> List[Scenario]:
    import jax

    k = jax.device_count()
    n = (16 if size == "smoke" else 256) * k
    return [sharded_case(n, k)]


# ---------------------------------------------------------------------------
# sharded_delta — per-device incremental transfers (marshal+delta@dp{k})
# ---------------------------------------------------------------------------

def sharded_delta_tree(n: int, k: int, seed: int = 19) -> Any:
    """The per-device delta steady state: two hot f32 leaves that mutate
    every pass, a cold f32 leaf that never does, and a frozen i32 id
    table.  Dict keys flatten alphabetically, so the f32 bucket is laid
    out ``cold[2n] | hot.a[n] | hot.b[n]`` — with sizes divisible by the
    mesh size ``k``, mutating the hot leaves dirties exactly the TRAILING
    ``ceil(k/2)`` shards of the f32 bucket, the closed form a
    ``marshal+delta@dp{k}`` transfer must reproduce per device."""
    rng = np.random.default_rng(seed)
    return {
        "hot": {"a": rng.standard_normal(n).astype(np.float32),
                "b": rng.standard_normal(n).astype(np.float32)},
        "cold": rng.standard_normal(2 * n).astype(np.float32),
        "ids": np.arange(4 * k, dtype=np.int32),
    }


def sharded_delta_expected(n: int, k: int) -> dict:
    """Cold-pass closed forms (Algorithm-2 differential): the f32 bucket is
    4n elements (hot.a + hot.b + cold), the i32 bucket 4k — both divisible
    by k, so marshal ships one contiguous sub-range per (bucket, device)."""
    marshal_bytes = _F32 * 4 * n + _I32 * 4 * k
    used_bytes = _F32 * (n + 2 * n)       # hot.a + cold
    if k == 1:
        return {"marshal": Motion(marshal_bytes, 2),
                "marshal_delta": Motion(marshal_bytes, 2),
                "uvm": Motion(used_bytes, 2),
                "pointerchain": Motion(used_bytes, 2)}
    per_leaf = Motion(used_bytes, 2 * k, used_bytes // k, 2)
    marshal = Motion(marshal_bytes, 2 * k, marshal_bytes // k, 2)
    return {"marshal": marshal, "marshal_delta": marshal,
            "uvm": per_leaf, "pointerchain": per_leaf}


def sharded_delta_steady_expected(n: int, k: int) -> Motion:
    """Closed-form per-device Motion of ONE steady pass after mutating
    hot.a and hot.b: the mutated region is elements [2n, 4n) of the
    4n-element f32 bucket (cold packs first — see the tree docstring),
    whose per-device shard is 4n/k elements — so exactly the shards
    overlapping that tail region ship (``ceil(k/2)`` of them, one DMA
    each, a full shard of bytes), every other (bucket, device) shard is
    skipped, and the non-uniform split is declared per shard."""
    if k == 1:
        return Motion(_F32 * 4 * n, 1)    # the whole f32 bucket, one DMA
    step = (4 * n) // k                   # f32 shard elements per device
    first_dirty = (2 * n) // step         # hot region starts at element 2n
    by_shard = tuple((step * _F32, 1) if s >= first_dirty else (0, 0)
                     for s in range(k))
    dirty = k - first_dirty               # == ceil(k/2)
    return Motion(dirty * step * _F32, dirty, by_shard=by_shard)


def sharded_delta_case(n: int, k: int) -> Scenario:
    used = ("hot.a", "cold")
    return Scenario(
        name=f"sharded_delta_n{n}_dev{k}",
        family="sharded_delta",
        build=functools.partial(sharded_delta_tree, n, k),
        used_paths=used,
        uvm_access=used,
        expected=sharded_delta_expected(n, k),
        sharding=data_sharding,
        num_shards=k,
        steady_expected=sharded_delta_steady_expected(n, k),
        steady_spec=TransferSpec("marshal", delta=True, sharding=k),
        params=dict(n=n, devices=k, mutate_paths=("hot.a", "hot.b")))


@register("sharded_delta")
def _sharded_delta_family(size: str) -> List[Scenario]:
    import jax

    k = jax.device_count()
    n = (4 if size == "smoke" else 64) * k
    return [sharded_delta_case(n, k)]


# ---------------------------------------------------------------------------
# mixed_policy — path-scoped policy trees over model-shaped state
# ---------------------------------------------------------------------------

def mixed_policy_tree(n: int, seed: int = 23) -> Any:
    """What real model state actually is (ISSUE 5): persistent sharded
    params, hot optimizer state, and marshal/metadata odds and ends — three
    regions a single whole-tree spec cannot serve.  All f32 payload sizes
    are multiples of ``n`` (the family passes ``n = base * devices``), so
    the params region splits evenly over any mesh the policy names."""
    rng = np.random.default_rng(seed)
    return {
        "params": {"w": rng.standard_normal(2 * n).astype(np.float32),
                   "b": rng.standard_normal(n).astype(np.float32)},
        "opt": {"m": rng.standard_normal(n).astype(np.float32),
                "v": rng.standard_normal(n).astype(np.float32),
                "t": np.int32(0)},
        "meta": {"ids": np.arange(2 * n, dtype=np.int32),
                 "scale": rng.standard_normal(n).astype(np.float32)},
    }


def mixed_policy_case(n: int, k: int) -> Scenario:
    """Closed-form per-region Motion for the declared policy
    ``params/**=marshal@dp{k}; opt/**=marshal+delta; **=pointerchain``:

    * params region — one f32 bucket of 3n elements (w + b), marshalled:
      12n bytes in 1 DMA (per device: 12n/k bytes, 1 DMA each on a k-mesh).
    * opt region — f32 bucket (m + v, 8n bytes) + i32 bucket (t, 4 bytes):
      cold 8n+4 bytes in 2 DMAs; steady after mutating ``opt.m`` the f32
      bucket ships whole (8n, 1) and the i32 bucket is skipped exactly.
    * default region (meta) — pointerchain: one DMA per leaf, every pass:
      ids (8n) + scale (4n) = 12n bytes in 2 DMAs.
    """
    pol = f"params/**=marshal@dp{k}; opt/**=marshal+delta; **=pointerchain"
    params_cold = Motion(12 * n, 1) if k == 1 else \
        Motion(12 * n, k, 12 * n // k, 1)
    meta = Motion(12 * n, 2)
    return Scenario(
        name=f"mixed_policy_n{n}_dev{k}",
        family="mixed_policy",
        build=functools.partial(mixed_policy_tree, n),
        used_paths=("params.w", "opt.m", "meta.scale"),
        uvm_access=None,
        declared_policy=pol,
        region_expected={"params/**": params_cold,
                         "opt/**": Motion(8 * n + 4, 2),
                         "**": meta},
        steady_region_expected={"params/**": params_cold,
                                "opt/**": Motion(8 * n, 1),
                                "**": meta},
        params=dict(n=n, devices=k, mutate_paths=("opt.m",)))


@register("mixed_policy")
def _mixed_policy_family(size: str) -> List[Scenario]:
    import jax

    k = jax.device_count()
    n = (8 if size == "smoke" else 128) * k
    return [mixed_policy_case(n, k)]


# ---------------------------------------------------------------------------
# elastic — the restore-onto-a-changed-mesh state shape (ISSUE 7)
# ---------------------------------------------------------------------------

def elastic_tree(n: int, seed: int = 29) -> Any:
    """The train-state shape an elastic restart restores: dp-sharded params,
    delta optimizer state, and a marshalled step counter — the same three
    regions ``runtime.train.state_transfer_policy`` names, sized so the
    params f32 bucket (3n elements) splits evenly over any mesh the family
    passes (``n = base * devices``)."""
    rng = np.random.default_rng(seed)
    return {
        "params": {"w": rng.standard_normal(2 * n).astype(np.float32),
                   "b": rng.standard_normal(n).astype(np.float32)},
        "opt": {"mu": rng.standard_normal(2 * n).astype(np.float32),
                "nu": rng.standard_normal(n).astype(np.float32),
                "t": np.int32(0)},
        "step": np.int32(0),
    }


def elastic_case(n: int, k: int) -> Scenario:
    """Closed-form per-region Motion for the restore policy
    ``params/**=marshal@dp{k}; opt/**=marshal+delta; **=marshal`` (the
    state policy's shape minus its 128-alignment, which would pad the
    closed forms away at family sizes):

    * params region — one f32 bucket of 3n elements (w + b): 12n bytes in
      1 DMA (per device 12n/k bytes, 1 DMA each on a k-mesh) — the bytes
      an n→m restore re-ships per surviving device.
    * opt region — f32 bucket (mu + nu, 12n bytes) + i32 bucket (t, 4):
      cold 12n+4 bytes in 2 DMAs; steady after mutating ``opt.mu`` the f32
      bucket ships whole (12n, 1), the i32 bucket is skipped exactly.
    * default region (step) — 4 bytes, 1 DMA, every pass.
    """
    pol = f"params/**=marshal@dp{k}; opt/**=marshal+delta; **=marshal"
    params_cold = Motion(12 * n, 1) if k == 1 else \
        Motion(12 * n, k, 12 * n // k, 1)
    return Scenario(
        name=f"elastic_n{n}_dev{k}",
        family="elastic",
        build=functools.partial(elastic_tree, n),
        used_paths=("params.w", "opt.mu"),
        uvm_access=None,
        declared_policy=pol,
        region_expected={"params/**": params_cold,
                         "opt/**": Motion(12 * n + 4, 2),
                         "**": Motion(4, 1)},
        steady_region_expected={"params/**": params_cold,
                                "opt/**": Motion(12 * n, 1),
                                "**": Motion(4, 1)},
        params=dict(n=n, devices=k, mutate_paths=("opt.mu",)))


@register("elastic")
def _elastic_family(size: str) -> List[Scenario]:
    import jax

    k = jax.device_count()
    n = (8 if size == "smoke" else 128) * k
    return [elastic_case(n, k)]


# ---------------------------------------------------------------------------
# steady_reuse — the delta transfer steady state
# ---------------------------------------------------------------------------

def steady_reuse_tree(n: int, seed: int = 17) -> Any:
    """Production-shaped steady state: a hot f32 part that changes every
    step, frozen bf16 weights and an i32 id table that never do.  Each dtype
    is its own marshalling bucket, so a delta transfer's dirty set is
    exactly the hot bucket."""
    rng = np.random.default_rng(seed)
    return {
        "hot": {"a": rng.standard_normal(n).astype(np.float32),
                "b": rng.standard_normal(n // 2).astype(np.float32)},
        "frozen": {"w": rng.standard_normal(4 * n).astype("bfloat16")},
        "meta": {"ids": np.arange(2 * n, dtype=np.int32)},
    }


def steady_reuse_case(n: int) -> Scenario:
    used = ("hot.a", "frozen.w")
    f32_bucket = _F32 * (n + n // 2)      # hot.a + hot.b share the f32 bucket
    return Scenario(
        name=f"steady_reuse_n{n}",
        family="steady_reuse",
        build=functools.partial(steady_reuse_tree, n),
        used_paths=used,
        uvm_access=tuple(["meta.ids"] + list(used)),
        # steady state: mutating hot.a dirties ONLY the f32 bucket — one DMA
        # carrying that bucket's bytes, everything else proven clean.
        steady_expected=Motion(f32_bucket, 1),
        steady_spec=TransferSpec("marshal", delta=True),
        params=dict(n=n, mutate_path="hot.a"))


@register("steady_reuse")
def _steady_reuse_family(size: str) -> List[Scenario]:
    return [steady_reuse_case(64 if size == "smoke" else 2048)]
