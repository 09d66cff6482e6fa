"""Arena transfer engine — sessions, persistent layouts, versioned staging.

The paper's Algorithm 1 separates *planning* (determineTotalBytes + the
requestList) from *data motion* (serve allocations, one batched DMA).  The
seed code re-ran the plan and re-packed with ``np.concatenate`` on every
``to_device``; this module makes the plan a reusable, cached artifact
(LLAMA's layout-as-metadata, arXiv 2106.04284) and makes the *staging
contents* a versioned artifact too, so steady-state repeat transfers can
skip buckets — and, per-device, individual bucket *shards* — whose bytes
have not changed (delta transfers):

  * :class:`TransferSession` — owns everything that outlives one scheme
    executor: the LRU-bounded ``ArenaLayout``/``ArenaEntry`` caches keyed
    by (treedef, leaf signature, alignment, shards), the
    :class:`DeltaState` registry (retained device buckets), and the
    ledgers it has issued.  Schemes built by
    ``TransferScheme.from_spec(spec, session)`` are thin executors over a
    session; the module-level functions below delegate to the default
    session, so existing call sites keep working.
  * :class:`ArenaEntry`   — per-layout persistent state:
      - TWO host staging buffers per dtype bucket (double buffering): a
        rewrite rotates to the other buffer and waits only that buffer's
        fence, so packing call N+1 can overlap the in-flight DMA of call N;
      - per-bucket monotone **version counters**: ``pack_host`` compares
        each leaf's bytes against the staged copy and bumps a bucket's
        version only when bytes actually changed (``trust_identity=True``
        additionally skips the compare when the identical leaf *object* was
        packed last time — callers that mutate leaves in place must then
        call :meth:`ArenaEntry.mark_dirty` / :meth:`ArenaEntry.bump_version`).
        The compare (:func:`_bytes_equal`) runs in machine words, a chunk of
        ``COMPARE_CHUNK`` words at a time into one small bool buffer that
        the call reuses for every leaf, and stops at the first chunk that
        differs; ``PackRecord.compare_unread_bytes`` and ``compare_exits``
        count what such early exits left unread;
      - per-(bucket, shard) version counters (``shard_versions``) for
        sharded layouts: a changed slot bumps exactly the shards whose
        element ranges it overlaps, so a per-device delta transfer
        re-ships only the shards whose bytes moved;
      - jit-compiled fused unpack / device-pack / repack.
  * :func:`pack_traced` / :func:`unpack_traced` — the same fused transforms
                            as free functions, safe to call under an outer
                            ``jit``/``shard_map`` trace (the gradient-arena
                            path in ``runtime/train.py``).

Aliasing invariant: ``jax.device_put`` may zero-copy ALIAS a suitably
aligned numpy buffer (observed on the XLA CPU client), so a staging buffer
may be read by device values long after the put returns.  Every consumer
must either synchronize before staging is rewritten (the blocking
``MarshalScheme`` path) or register the consuming arrays as a **fence** on
the buffer (:meth:`ArenaEntry.add_fence`); ``pack_host`` waits a buffer's
fence before rewriting it.  Retained per-shard device arrays additionally
rely on range disjointness: a shard's byte range in a staging buffer is
rewritten only when a slot overlapping it changed, which bumps that
shard's version — and a bumped shard is re-shipped (its retained array
replaced) before any gather of the same call.  See DESIGN.md §4/§7/§8.
"""
from __future__ import annotations

import collections
import dataclasses
import time
import weakref
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import arena as arena_lib
from .arena import ArenaLayout
# the staging race sanitizer (repro.analysis.sanitizer is a leaf module:
# stdlib + numpy, no core imports).  Every hook below guards on
# `_sanitizer._ACTIVE is not None` — one module-global read when disabled,
# the same fast-path shape as faults.trip.
from ..analysis import sanitizer as _sanitizer

Buffers = arena_lib.Buffers

# default cache caps for new sessions: layouts are tiny but long-running
# serve/train loops can still visit an unbounded stream of shapes; entries
# additionally pin full-size host staging buffers plus three compiled
# executables.  Both are bounded per session.
LAYOUT_CACHE_MAX = 512
ENTRY_CACHE_MAX = 64


def num_shards_of(sharding: Any) -> int:
    """Shard count of a sharding target: an int, a NamedSharding (mesh
    size), or None (1).  One derivation for the whole tree — this is the
    spec layer's rule (``spec._shard_count``), re-exposed with the
    engine's TypeError contract."""
    from .spec import UnsupportedSpecError, _shard_count

    try:
        return _shard_count(sharding)
    except UnsupportedSpecError as e:
        raise TypeError(str(e)) from None


def _leaf_signature(leaves) -> Tuple:
    sig = []
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            sig.append((tuple(leaf.shape), np.dtype(leaf.dtype).str))
        else:
            arr = np.asarray(leaf)
            sig.append((tuple(arr.shape), arr.dtype.str))
    return tuple(sig)


def _layout_key(tree: Any, align_elems: int,
                num_shards: int = 1) -> Tuple[Any, Tuple, int, int]:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (treedef, _leaf_signature(leaves), align_elems, num_shards)


class DeltaState:
    """What a delta executor has already SHIPPED: per entry, the retained
    device buffer (or per-shard buffers) of every bucket, keyed by shipped
    version, plus the memoized fully-clean unpack.  Owned by a
    :class:`TransferSession` so its device memory has a lifecycle
    (``session.clear()`` drops it); held per executor by default, shared
    across executors of one spec via ``session.delta_state(spec)``."""

    def __init__(self):
        # entry -> {bucket: (shipped version, retained device buffer)}, or
        # for sharded layouts {bucket: [(version, buffer)] per shard}
        self.retained: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
        # entry -> (versions snapshot, unpacked device tree): a repeat pass
        # with ZERO dirty buckets/shards returns the memoized (immutable)
        # tree — no DMA, no gather dispatch, pure fingerprint walk.
        self.last_unpack: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()

    def clear(self) -> None:
        self.retained.clear()
        self.last_unpack.clear()


class TransferSession:
    """Owns every artifact that outlives one transfer call: cached layouts
    and entries (LRU-bounded), the delta states holding retained device
    buckets, and the ledgers issued to schemes.  The module-level default
    session (:func:`get_session`) is what the delegating free functions and
    spec-less scheme construction use; an isolated session gives a workload
    its own caches and retained-state lifecycle."""

    def __init__(self, layout_max: int = None, entry_max: int = None,
                 sanitize: bool = False):
        if sanitize:
            # the shadow machine is process-wide (entries/schemes have no
            # back-pointer to their session); the kwarg is the ergonomic
            # opt-in next to REPRO_SANITIZE=1 (DESIGN.md §13.3)
            _sanitizer.enable()
        self.layout_max = LAYOUT_CACHE_MAX if layout_max is None else int(layout_max)
        self.entry_max = ENTRY_CACHE_MAX if entry_max is None else int(entry_max)
        self._layouts: "collections.OrderedDict[Tuple, ArenaLayout]" = \
            collections.OrderedDict()
        self._entries: "collections.OrderedDict[Tuple, ArenaEntry]" = \
            collections.OrderedDict()
        self._stats = {"hits": 0, "misses": 0,
                       "layout_evictions": 0, "entry_evictions": 0}
        # spec -> shared DeltaState; plus every private state ever issued
        # (weak: dropped with its executor), so clear() can release all
        # retained device memory this session caused to be held.
        self._spec_states: Dict[Any, DeltaState] = {}
        self._delta_states: "weakref.WeakSet[DeltaState]" = weakref.WeakSet()
        self._ledgers: List["weakref.ref"] = []
        # compiled TransferPrograms (weak: dropped with their owner);
        # clear() must walk them too — a program's region executors hold
        # strong entry refs that would otherwise keep staging buffers,
        # fences and retained device buckets alive past the cache flush.
        self._programs: "weakref.WeakSet" = weakref.WeakSet()

    # -- plans & entries -----------------------------------------------------
    def cached_plan(self, tree: Any, align_elems: int = 1,
                    sharding: Any = None) -> ArenaLayout:
        """``arena.plan`` behind the persistent layout cache.

        Works on concrete trees AND on tracer trees (inside jit/shard_map):
        the key only reads shapes/dtypes, never values.  ``sharding`` (an
        int shard count or a NamedSharding) pads every bucket to a
        per-device multiple and becomes part of the cache key.
        """
        k = num_shards_of(sharding)
        return self._plan_for_key(_layout_key(tree, align_elems, k), tree,
                                  align_elems, k)

    def plan(self, tree: Any, spec: Any) -> ArenaLayout:
        """`cached_plan` keyed by a :class:`~repro.core.spec.TransferSpec`:
        the spec's align/sharding axes ARE the plan parameters."""
        return self.cached_plan(tree, spec.align_elems, spec.sharding)

    def _plan_for_key(self, key: Tuple, tree: Any, align_elems: int,
                      num_shards: int) -> ArenaLayout:
        layout = self._layouts.get(key)
        if layout is None:
            self._stats["misses"] += 1
            layout = arena_lib.plan(tree, align_elems,
                                    shard_multiple=num_shards)
            self._layouts[key] = layout
            self._trim()
        else:
            self._stats["hits"] += 1
            self._layouts.move_to_end(key)
        return layout

    def get_entry(self, tree: Any, align_elems: int = 1,
                  sharding: Any = None) -> "ArenaEntry":
        """The engine's front door: cached ``ArenaEntry`` for this tree's
        shape.  LRU-bounded at ``entry_max``: evicted entries stay usable
        for any scheme still holding them, they just stop being shared."""
        k = num_shards_of(sharding)
        key = _layout_key(tree, align_elems, k)
        entry = self._entries.get(key)
        if entry is None:
            entry = ArenaEntry(self._plan_for_key(key, tree, align_elems, k))
            self._entries[key] = entry
            self._trim()
        else:
            self._stats["hits"] += 1
            self._entries.move_to_end(key)
        return entry

    def entry_for(self, tree: Any, spec: Any) -> "ArenaEntry":
        return self.get_entry(tree, spec.align_elems, spec.sharding)

    def _trim(self) -> None:
        while len(self._layouts) > self.layout_max:
            self._layouts.popitem(last=False)
            self._stats["layout_evictions"] += 1
        while len(self._entries) > self.entry_max:
            self._entries.popitem(last=False)
            self._stats["entry_evictions"] += 1

    def set_cache_limits(self, layout_max: Optional[int] = None,
                         entry_max: Optional[int] = None) -> None:
        """Configure the cache caps (e.g. per deployment memory budget)."""
        if layout_max is not None:
            self.layout_max = int(layout_max)
        if entry_max is not None:
            self.entry_max = int(entry_max)
        self._trim()

    def cache_stats(self) -> Dict[str, int]:
        out = dict(self._stats)
        out["layout_size"] = len(self._layouts)
        out["entry_size"] = len(self._entries)
        out["programs"] = len(self._programs)
        # every device bucket (or bucket shard) a delta state of this
        # session still retains — MUST report 0 after clear()
        retained = 0
        for state in list(self._delta_states):
            for per_entry in state.retained.values():
                for val in per_entry.values():
                    retained += sum(1 for x in val if x is not None) \
                        if isinstance(val, list) else 1
        out["retained_device_buckets"] = retained
        return out

    # -- delta state ---------------------------------------------------------
    def delta_state(self, spec: Any = None) -> DeltaState:
        """Retained-device-state container for a delta executor.  With a
        ``spec`` key the state is SHARED by every executor of that spec in
        this session (the session owns one steady state per policy);
        without one the caller gets a private state (a fresh executor's
        first pass is always a full cold transfer) whose lifecycle the
        session still tracks."""
        if spec is not None:
            state = self._spec_states.get(spec)
            if state is None:
                state = self._spec_states[spec] = DeltaState()
                self._delta_states.add(state)
            return state
        state = DeltaState()
        self._delta_states.add(state)
        return state

    # -- ledgers -------------------------------------------------------------
    def make_ledger(self):
        """A fresh ledger whose lifecycle the session tracks (merge all
        live ones with :meth:`merged_ledger`)."""
        from .schemes import TransferLedger

        ledger = TransferLedger()
        self._ledgers.append(weakref.ref(ledger))
        self._ledgers = [r for r in self._ledgers if r() is not None]
        return ledger

    def merged_ledger(self):
        """One ledger summing every live ledger this session issued — the
        session-wide data-motion picture."""
        from .schemes import TransferLedger

        out = TransferLedger()
        out.merge(*[led for r in self._ledgers
                    if (led := r()) is not None])
        return out

    # -- compiled programs ---------------------------------------------------
    def compile(self, tree: Any, policy: Any) -> Any:
        """Compile a path-scoped :class:`~repro.core.policy.TransferPolicy`
        against ``tree``'s treedef into a
        :class:`~repro.core.policy.TransferProgram`: the treedef partitioned
        into regions (every leaf covered exactly once), one executor per
        region over THIS session's caches, all regions' buckets enqueued
        before one sync per pass.  The session tracks the program so
        :meth:`clear` releases its retained device state too."""
        from .policy import compile_program

        program = compile_program(tree, policy, self)
        self._programs.add(program)
        return program

    # -- lifecycle -----------------------------------------------------------
    def clear(self) -> None:
        """Drop cached layouts/entries, every retained device bucket —
        including the per-region delta states and entry references of
        compiled programs — and the stats counters.  Live schemes and
        programs keep working (cold)."""
        self._layouts.clear()
        self._entries.clear()
        self._spec_states.clear()
        for program in list(self._programs):
            program.clear()
        for state in list(self._delta_states):
            state.clear()
        for k in self._stats:
            self._stats[k] = 0


_DEFAULT_SESSION = TransferSession()


def get_session() -> TransferSession:
    """The process-default session (what spec-less construction uses)."""
    return _DEFAULT_SESSION


# -- module-level delegates (the pre-session API; unchanged signatures) ------

def cached_plan(tree: Any, align_elems: int = 1,
                sharding: Any = None) -> ArenaLayout:
    return _DEFAULT_SESSION.cached_plan(tree, align_elems, sharding)


def get_entry(tree: Any, align_elems: int = 1,
              sharding: Any = None) -> "ArenaEntry":
    return _DEFAULT_SESSION.get_entry(tree, align_elems, sharding)


def set_cache_limits(layout_max: Optional[int] = None,
                     entry_max: Optional[int] = None) -> None:
    _DEFAULT_SESSION.set_cache_limits(layout_max, entry_max)


def cache_stats() -> Dict[str, int]:
    return _DEFAULT_SESSION.cache_stats()


def clear_cache() -> None:
    _DEFAULT_SESSION.clear()


# ---------------------------------------------------------------------------
# fused transforms (trace-safe free functions)
# ---------------------------------------------------------------------------

def unpack_leaves(buffers: Buffers, layout: ArenaLayout) -> List[Any]:
    """Slice every leaf out of its bucket.  All offsets are static, so under
    jit this lowers to one fused gather region — no per-leaf dispatch."""
    leaves = []
    for slot in layout.slots:
        buf = buffers[slot.bucket]
        flat = jax.lax.slice_in_dim(buf, slot.offset, slot.offset + slot.size)
        leaves.append(jnp.reshape(flat, slot.shape))
    return leaves


def unpack_traced(buffers: Buffers, layout: ArenaLayout) -> Any:
    return jax.tree_util.tree_unflatten(layout.treedef,
                                        unpack_leaves(buffers, layout))


def _scatter_leaves(buffers: Buffers, leaves, layout: ArenaLayout) -> Buffers:
    out = dict(buffers)
    for leaf, slot in zip(leaves, layout.slots):
        flat = jnp.reshape(jnp.asarray(leaf, dtype=slot.dtype), (-1,))
        out[slot.bucket] = jax.lax.dynamic_update_slice_in_dim(
            out[slot.bucket], flat, slot.offset, 0)
    return out


def pack_traced(tree: Any, layout: ArenaLayout) -> Buffers:
    """Scatter leaves into fresh zero buckets.  Static offsets: one fused
    scatter region under jit (the device-side direction of Alg. 1)."""
    leaves = jax.tree_util.tree_leaves(tree)
    if len(leaves) != layout.num_leaves:
        raise ValueError("tree does not match arena layout")
    zeros = {b: jnp.zeros((n,), np.dtype(b))
             for b, n in layout.bucket_sizes.items()}
    return _scatter_leaves(zeros, leaves, layout)


def repack_traced(buffers: Buffers, layout: ArenaLayout, tree: Any) -> Buffers:
    """Fused ``arena.repack_into``: scatter a tree's leaves back over an
    existing arena (the gradient-arena update path)."""
    leaves = jax.tree_util.tree_leaves(tree)
    if len(leaves) != layout.num_leaves:
        raise ValueError("tree does not match arena layout")
    return _scatter_leaves(buffers, leaves, layout)


# ---------------------------------------------------------------------------
# ArenaEntry — persistent per-layout state
# ---------------------------------------------------------------------------

# per-buffer fences are trimmed to this depth: older fence groups are
# force-waited so a long clean streak cannot pin unbounded device values.
FENCE_DEPTH = 8

# words per step of a leaf's byte compare: the bool buffer that takes one
# step's results is this long (128 KiB), and a mismatch ends the compare
# after the step it lies in.
COMPARE_CHUNK = 1 << 17
_WORDS = tuple(map(np.dtype, ("u8", "u4", "u2", "u1")))


def _bytes_equal(a: np.ndarray, b: np.ndarray,
                 scratch: np.ndarray) -> Tuple[bool, int]:
    """Whether two C-contiguous 1-D arrays of the same byte length hold
    the same bytes, and how many bytes of each were read to tell.

    The bytes are compared as the widest unsigned words that both start
    addresses are aligned to (a tail shorter than a word in the next
    narrower ones, down to single bytes), ``len(scratch)`` words at a time
    into ``scratch``; the first step that finds a difference ends the
    compare.  Bitwise, not by value: NaNs with equal payloads compare
    equal, +0.0 and -0.0 do not.  ``scratch`` is a bool buffer the caller
    reuses, so no leaf-sized temporary is made."""
    a, b = a.view(np.uint8), b.view(np.uint8)
    n = a.nbytes
    align = a.ctypes.data | b.ctypes.data
    step = len(scratch)
    pos = 0
    for word in _WORDS:
        w = word.itemsize
        if align % w:
            continue
        end = pos + (n - pos) // w * w
        x, y = a[pos:end].view(word), b[pos:end].view(word)
        for i in range(0, x.size, step):
            j = min(i + step, x.size)
            if not np.equal(x[i:j], y[i:j], out=scratch[:j - i]).all():
                return False, pos + j * w
        pos = end
    return True, n


@dataclasses.dataclass
class PackRecord:
    """What one ``pack_host`` call did: the bytes it compared against
    staging, copied into staging and skipped by identity, the seconds it
    waited on fences before rotating buffers, and how many compares a
    mismatch ended early and the bytes of them left unread (part of
    ``compared_bytes``).  A scheme books it into its
    :class:`~repro.core.schemes.TransferLedger` once per call."""

    compared_bytes: int = 0
    staged_bytes: int = 0
    identity_skipped_bytes: int = 0
    fence_wait_s: float = 0.0
    compare_unread_bytes: int = 0
    compare_exits: int = 0


def _drop_ready(fence: List[List[Any]]) -> None:
    """Forget fence groups whose values are all ready (or deleted):
    nothing they guard is in flight any more, and holding them keeps the
    device copies of earlier passes alive.  On one TPU v5e the steady pass
    of mamba2-1.3b's ServeState ran out of memory while the cold pass's
    fences still held its 2.9 GB params bucket and leaves."""
    fence[:] = [grp for grp in fence
                if not all(v.is_deleted() or v.is_ready() for v in grp)]


class ArenaEntry:
    """Everything reusable about one (treedef, signature, alignment, shards)
    point: the layout, double-buffered host staging per bucket with content
    version counters (bucket- and shard-granular) and per-buffer fences,
    and the compiled fused transforms.  Created once, then every call is
    pure data motion."""

    def __init__(self, layout: ArenaLayout):
        self.layout = layout
        # double-buffered, zero-initialised staging: alignment gaps stay
        # zero forever; writes only ever touch live leaf extents, and a
        # rewrite rotates to the buffer whose DMA cannot still be in flight
        # (after waiting its fence).
        self._bufs: Dict[str, List[np.ndarray]] = {
            b: [np.zeros(int(n), np.dtype(b)), np.zeros(int(n), np.dtype(b))]
            for b, n in layout.bucket_sizes.items()}
        self._active: Dict[str, int] = {b: 0 for b in self._bufs}
        self._fences: Dict[str, List[List[Any]]] = {
            b: [[], []] for b in self._bufs}
        # staging content versions: versions[b] bumps exactly when bucket
        # b's staged bytes change (or bump_version forces it) — monotone.
        self.versions: Dict[str, int] = {b: 0 for b in self._bufs}
        # per-(bucket, shard) versions for sharded layouts: shard s of
        # bucket b bumps exactly when a changed slot overlaps its element
        # range — the per-device half of the dirty tracking.
        k = max(1, layout.shard_multiple)
        self.shard_versions: Dict[str, List[int]] = {
            b: [0] * k for b in self._bufs}
        self._slot_vers: List[int] = [0] * layout.num_leaves
        self._bucket_slots: Dict[str, List[int]] = {b: [] for b in self._bufs}
        for i, slot in enumerate(layout.slots):
            if slot.size:
                self._bucket_slots[slot.bucket].append(i)
        self._buf_slot_vers: Dict[str, List[List[int]]] = {
            b: [[-1] * len(idx), [-1] * len(idx)]
            for b, idx in self._bucket_slots.items()}
        self._last_leaf: List[Any] = [None] * layout.num_leaves
        self._recheck: set = set()          # buckets whose identity skip is off
        self.pack_host_calls = 0
        self.last_pack = PackRecord()       # what the newest pack_host did

        def _unpack(buffers):
            return tuple(unpack_leaves(buffers, layout))

        def _pack_device(leaves):
            zeros = {b: jnp.zeros((n,), np.dtype(b))
                     for b, n in layout.bucket_sizes.items()}
            return _scatter_leaves(zeros, leaves, layout)

        def _repack(buffers, leaves):
            return _scatter_leaves(buffers, leaves, layout)

        # one compiled gather/scatter region each; compiled on first use,
        # steady-state is a single dispatch.
        self.unpack_leaves_jit = jax.jit(_unpack)
        self.pack_device_jit = jax.jit(_pack_device)
        self.repack_jit = jax.jit(_repack)

    # -- staging views -------------------------------------------------------
    @property
    def staging(self) -> Buffers:
        """The ACTIVE buffer per bucket (the one holding the newest bytes)."""
        return {b: bufs[self._active[b]] for b, bufs in self._bufs.items()}

    def shard_views(self, num_shards: Optional[int] = None
                    ) -> Dict[str, List[np.ndarray]]:
        """Zero-copy per-device views of every active bucket buffer."""
        ranges = arena_lib.shard_ranges(self.layout, num_shards)
        stg = self.staging
        return {b: [stg[b][lo:hi] for lo, hi in rs]
                for b, rs in ranges.items()}

    # -- dirty tracking ------------------------------------------------------
    def mark_dirty(self, *buckets: str) -> None:
        """Disable the identity fast path for these buckets (all if none
        given) until the next ``pack_host``: leaves are re-compared against
        staging, so in-place host mutations are detected."""
        self._recheck.update(buckets or self._bufs)

    def bump_version(self, *buckets: str) -> None:
        """Unconditionally advance bucket (and shard) versions (all buckets
        if none given), forcing the next delta transfer to re-ship them
        even if the staged bytes are unchanged."""
        for b in (buckets or list(self._bufs)):
            self.versions[b] += 1
            self.shard_versions[b] = [v + 1 for v in self.shard_versions[b]]

    def _bump_shards(self, bucket: str, pending_slots: Sequence[int]) -> None:
        """Bump the shard versions a set of changed slots overlaps."""
        shards = self.shard_versions[bucket]
        k = len(shards)
        if k == 1:
            shards[0] += 1
            return
        n = self.layout.bucket_sizes[bucket]
        step = n // k
        touched = set()
        for i in pending_slots:
            slot = self.layout.slots[i]
            lo = slot.offset // step
            hi = (slot.offset + slot.size - 1) // step
            touched.update(range(lo, min(hi, k - 1) + 1))
        for s in touched:
            shards[s] += 1

    # -- fences --------------------------------------------------------------
    def add_fence(self, bucket: str, values: Sequence[Any]) -> None:
        """Register device values that (may) read the bucket's active buffer.
        ``pack_host`` waits them before rewriting that buffer."""
        fence = self._fences[bucket][self._active[bucket]]
        _drop_ready(fence)
        fence.append(list(values))
        while len(fence) > FENCE_DEPTH:
            jax.block_until_ready(fence.pop(0))
        if _sanitizer._ACTIVE is not None:
            _sanitizer._ACTIVE.on_add_fence(self, bucket, self._active[bucket],
                                            len(fence), FENCE_DEPTH)

    def _wait_fence(self, bucket: str, buf_idx: int) -> None:
        fence = self._fences[bucket][buf_idx]
        if fence:
            with jax.profiler.TraceAnnotation(
                    "ArenaEntry.pack_host.fence_wait", dtype=bucket):
                t0 = time.perf_counter()
                jax.block_until_ready([v for grp in fence for v in grp])
                self.last_pack.fence_wait_s += time.perf_counter() - t0
            fence.clear()
        if _sanitizer._ACTIVE is not None:
            _sanitizer._ACTIVE.on_fence_wait(self, bucket, buf_idx)

    def _drop_ready_fences(self) -> None:
        for fences in self._fences.values():
            for fence in fences:
                _drop_ready(fence)

    # -- host side ----------------------------------------------------------
    def pack_host(self, tree: Any, *, trust_identity: bool = False) -> Buffers:
        """Marshal into the persistent staging buffers and update version
        counters.  Per leaf: skip when the staged bytes already match (a
        word-wise compare in chunks into one bool buffer reused across the
        call, ended by the first chunk that differs: :func:`_bytes_equal`);
        with ``trust_identity`` also skip the compare when the identical
        leaf object was packed last time (in-place mutators must
        ``mark_dirty``).  Buckets that change rotate to their spare buffer
        (waiting only that buffer's fence) and bump their version; the
        shards a changed slot overlaps bump their shard versions.  What the
        call compared, copied, skipped and waited is left in ``last_pack``.
        """
        leaves = jax.tree_util.tree_leaves(tree)
        if len(leaves) != self.layout.num_leaves:
            raise ValueError("tree does not match arena layout")
        self._drop_ready_fences()
        record = self.last_pack = PackRecord()
        scratch = np.empty(COMPARE_CHUNK, np.bool_)
        pending: Dict[int, np.ndarray] = {}
        with jax.profiler.TraceAnnotation(
                "ArenaEntry.pack_host.compare") as span:
            for i, (leaf, slot) in enumerate(zip(leaves, self.layout.slots)):
                if slot.size == 0:
                    continue
                recheck = slot.bucket in self._recheck
                if (trust_identity and not recheck
                        and self._last_leaf[i] is leaf):
                    record.identity_skipped_bytes += \
                        slot.size * np.dtype(slot.bucket).itemsize
                    if _sanitizer._ACTIVE is not None:
                        # shadow memcmp: catches in-place mutation without
                        # mark_dirty (DC306), exactly the check this fast
                        # path elides
                        _sanitizer._ACTIVE.on_identity_skip(self, slot, leaf)
                    continue
                arr = np.asarray(leaf, dtype=slot.dtype).reshape(-1)
                # the byte compare is the fingerprint: it costs at most one
                # read pass over the leaf (a changed leaf stops at the chunk
                # of its first difference) but is what lets shared entries
                # keep exact versions (and lets unchanged repeat packs skip
                # the write entirely).  A slot that was never packed is
                # always dirty — no point comparing against the
                # zero-initialised staging.
                if self._last_leaf[i] is not None:
                    record.compared_bytes += arr.nbytes
                    act = self._bufs[slot.bucket][self._active[slot.bucket]]
                    staged = act[slot.offset:slot.offset + slot.size]
                    # compare raw bytes, not values: NaN != NaN under value
                    # comparison, which would make any NaN-bearing bucket
                    # permanently dirty and silently defeat the delta path.
                    equal, read = _bytes_equal(
                        staged, np.ascontiguousarray(arr), scratch)
                    if equal:
                        self._last_leaf[i] = leaf
                        continue
                    record.compare_unread_bytes += arr.nbytes - read
                    record.compare_exits += 1
                self._slot_vers[i] += 1
                pending[i] = arr
                self._last_leaf[i] = leaf
            span.set_metadata(bytes=record.compared_bytes,
                              unread_bytes=record.compare_unread_bytes)
        dirty = {self.layout.slots[i].bucket for i in pending}
        for b in dirty:
            tgt = 1 - self._active[b]
            self._wait_fence(b, tgt)
            if _sanitizer._ACTIVE is not None:
                _sanitizer._ACTIVE.on_staging_write(self, b, tgt)
            buf = self._bufs[b][tgt]
            held = self._buf_slot_vers[b][tgt]
            with jax.profiler.TraceAnnotation(
                    "ArenaEntry.pack_host.copy", dtype=b) as span:
                staged_bytes = 0
                for lj, si in enumerate(self._bucket_slots[b]):
                    if held[lj] < self._slot_vers[si]:
                        slot = self.layout.slots[si]
                        arr = pending.get(si)
                        if arr is None:
                            arr = np.asarray(leaves[si],
                                             dtype=slot.dtype).reshape(-1)
                        buf[slot.offset:slot.offset + slot.size] = arr
                        staged_bytes += arr.nbytes
                        held[lj] = self._slot_vers[si]
                span.set_metadata(bytes=staged_bytes)
            record.staged_bytes += staged_bytes
            self._active[b] = tgt
            if _sanitizer._ACTIVE is not None:
                _sanitizer._ACTIVE.on_rotate(self, b, tgt)
            self.versions[b] += 1
            self._bump_shards(b, [i for i in pending
                                  if self.layout.slots[i].bucket == b])
        self._recheck.clear()
        self.pack_host_calls += 1
        return self.staging

    # -- device side --------------------------------------------------------
    def unpack(self, buffers: Buffers) -> Any:
        """Fused acc_attach: one compiled gather, then unflatten."""
        leaves = self.unpack_leaves_jit(dict(buffers))
        return jax.tree_util.tree_unflatten(self.layout.treedef, list(leaves))

    def pack_device(self, tree: Any) -> Buffers:
        leaves = tuple(jax.tree_util.tree_leaves(tree))
        if len(leaves) != self.layout.num_leaves:
            raise ValueError("tree does not match arena layout")
        return self.pack_device_jit(leaves)

    def repack(self, buffers: Buffers, tree: Any) -> Buffers:
        leaves = tuple(jax.tree_util.tree_leaves(tree))
        return self.repack_jit(dict(buffers), leaves)
