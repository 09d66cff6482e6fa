"""Transfer schemes — thin executors of a :class:`TransferSpec`.

  * :class:`UVMScheme`          — demand-paged analogue: leaf-granular,
                                  on-access transfers at arbitrary times.
  * :class:`MarshalScheme`      — Algorithm 1: pack into contiguous arenas,
                                  one DMA per dtype bucket, attach views.
  * :class:`PointerChainScheme` — declared chains only (selective deep copy).

A scheme is constructed from a spec via :func:`transfer_scheme` /
:meth:`TransferScheme.from_spec`; the spec's axes (delta, sharding,
staging, alignment, placement) compose orthogonally and are validated by
the capability matrix in :mod:`repro.core.spec`.  Persistent state —
cached layouts/entries, retained delta buckets, ledger lifecycle — lives
in a :class:`~repro.core.engine.TransferSession`.  The legacy constructors
(``SCHEMES`` / :func:`make_scheme` / the old keyword signatures) remain as
deprecation shims that build the equivalent spec and warn.

Every scheme records its traffic in a :class:`TransferLedger` so tests and
benchmarks can assert the paper's data-motion claims structurally (bytes
moved, DMA count) in addition to timing them.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import arena as arena_lib
from . import engine as engine_lib
from ..analysis import sanitizer as _sanitizer
from .chainref import ChainRef, declare, extract, insert
from .spec import TransferSpec, UnsupportedSpecError
from .treepath import TreePath, leaf_items


def _nbytes(x: Any) -> int:
    arr = np.asarray(x) if not hasattr(x, "nbytes") else x
    return int(arr.nbytes)


@dataclasses.dataclass
class TransferLedger:
    """Counts H2D/D2H traffic: the paper's implicit metric made explicit.

    ``wall_s`` is total CALLER-VISIBLE transfer time, split into
    ``enqueue_s`` (issuing the async copies), ``sync_s`` (time the caller
    thread spent blocked in a barrier / fence wait) and ``finish_s``
    (post-barrier bookkeeping: retained-state updates, gather dispatch) so
    batching overlap is measurable: a fully serialized path has enqueue ≈ 0
    and sync ≈ wall, and the identity ``wall_s == enqueue_s + sync_s +
    finish_s`` holds exactly by construction.

    ``overlap_s`` is the async executor's fourth attribution: time a
    barrier spent OFF the caller's thread (the background sync of a
    :class:`~repro.core.policy.ProgramFuture`).  It is deliberately NOT
    part of ``wall_s`` — counting the same barrier both where it ran
    (background) and where the caller waited for it (``sync_s`` inside
    ``result()``) would double-count under overlap and make the wall
    splits sum past the measured wall.

    Delta accounting (invariant 4 stays exact): ``h2d_bytes``/``h2d_calls``
    record only bytes that actually moved; ``skipped_bytes`` records bytes a
    delta transfer proved unchanged and did NOT move, so per pass
    ``h2d_bytes + skipped_bytes`` equals the full-marshal motion.
    ``delta_calls`` counts transfer passes that reused at least one clean
    bucket (or bucket shard).  ``*_by_device`` split the same exact totals
    per target device — including ``skipped_bytes_by_device``, so the
    per-device equality ``h2d + skipped == full sharded motion`` holds on
    EVERY device of a sharded delta transfer; an unsharded path records
    everything under its one device.

    Host-side staging work, booked from each ``ArenaEntry.pack_host`` call's
    :class:`~repro.core.engine.PackRecord`: ``compared_bytes`` (submitted to
    the compare against staging), ``compare_unread_bytes`` (the part of
    those a mismatch left unread) and ``compare_exits`` (compares a
    mismatch ended), ``staged_bytes`` (memcpy'd into staging) and
    ``identity_skipped_bytes`` (skipped by ``trust_identity``).  Per pack,
    compared + identity-skipped + never-packed-before bytes equal the
    region's non-empty leaf bytes.  The pack's fence wait goes to ``sync_s``.
    """

    h2d_bytes: int = 0
    d2h_bytes: int = 0
    h2d_calls: int = 0   # DMA batches issued host->device
    d2h_calls: int = 0
    wall_s: float = 0.0
    enqueue_s: float = 0.0
    sync_s: float = 0.0
    overlap_s: float = 0.0   # barrier time spent off the caller's thread
    finish_s: float = 0.0    # post-barrier bookkeeping on the caller's thread
    skipped_bytes: int = 0   # delta: bytes proven unchanged, not re-shipped
    delta_calls: int = 0     # transfer passes that skipped >=1 clean bucket
    compared_bytes: int = 0  # pack_host: submitted to the staging compare
    compare_unread_bytes: int = 0  # ... of which a mismatch left unread
    compare_exits: int = 0   # pack_host: compares a mismatch ended early
    staged_bytes: int = 0    # pack_host: memcpy'd into staging
    identity_skipped_bytes: int = 0   # pack_host: skipped by trust_identity
    h2d_bytes_by_device: Dict[str, int] = dataclasses.field(default_factory=dict)
    h2d_calls_by_device: Dict[str, int] = dataclasses.field(default_factory=dict)
    skipped_bytes_by_device: Dict[str, int] = dataclasses.field(default_factory=dict)

    @staticmethod
    def _device_key(device: Any) -> str:
        return str(getattr(device, "id", device))

    def record_h2d(self, nbytes: int, device: Optional[Any] = None) -> None:
        self.h2d_bytes += int(nbytes)
        self.h2d_calls += 1
        if device is not None:
            key = self._device_key(device)
            self.h2d_bytes_by_device[key] = \
                self.h2d_bytes_by_device.get(key, 0) + int(nbytes)
            self.h2d_calls_by_device[key] = \
                self.h2d_calls_by_device.get(key, 0) + 1

    def record_skip(self, nbytes: int, device: Optional[Any] = None) -> None:
        self.skipped_bytes += int(nbytes)
        if device is not None:
            key = self._device_key(device)
            self.skipped_bytes_by_device[key] = \
                self.skipped_bytes_by_device.get(key, 0) + int(nbytes)

    def record_pack(self, record: engine_lib.PackRecord) -> None:
        """Book one ``pack_host`` call: its byte counts, and its fence wait
        as time the caller was blocked (``sync_s``)."""
        self.compared_bytes += record.compared_bytes
        self.compare_unread_bytes += record.compare_unread_bytes
        self.compare_exits += record.compare_exits
        self.staged_bytes += record.staged_bytes
        self.identity_skipped_bytes += record.identity_skipped_bytes
        if record.fence_wait_s:
            self.record_wall(0.0, record.fence_wait_s)

    def record_d2h(self, nbytes: int) -> None:
        self.d2h_bytes += int(nbytes)
        self.d2h_calls += 1

    def record_wall(self, enqueue_s: float, sync_s: float) -> None:
        self.enqueue_s += enqueue_s
        self.sync_s += sync_s
        self.wall_s += enqueue_s + sync_s

    def record_overlap(self, overlap_s: float) -> None:
        """Barrier time that ran on a background thread — attributed, but
        NOT added to ``wall_s`` (the caller never waited for it here)."""
        self.overlap_s += overlap_s

    def record_finish(self, finish_s: float) -> None:
        self.finish_s += finish_s
        self.wall_s += finish_s

    def per_device(self) -> Dict[str, Tuple[int, int]]:
        """{device id: (h2d_bytes, h2d_calls)} for sharded assertions."""
        return {d: (self.h2d_bytes_by_device[d],
                    self.h2d_calls_by_device.get(d, 0))
                for d in self.h2d_bytes_by_device}

    def as_dict(self) -> Dict[str, Any]:
        """Every field as plain data (maps copied) — THE row format for
        benchmark persistence and cross-ledger comparison; adding a ledger
        field automatically adds the column everywhere this is used."""
        return dataclasses.asdict(self)

    def merge(self, *others: "TransferLedger") -> "TransferLedger":
        """Accumulate other ledgers into this one (exact counters add; the
        per-device maps union-add).  Returns self, so
        ``TransferLedger().merge(a, b)`` is the non-destructive sum."""
        for o in others:
            self.h2d_bytes += o.h2d_bytes
            self.d2h_bytes += o.d2h_bytes
            self.h2d_calls += o.h2d_calls
            self.d2h_calls += o.d2h_calls
            self.skipped_bytes += o.skipped_bytes
            self.delta_calls += o.delta_calls
            self.compared_bytes += o.compared_bytes
            self.compare_unread_bytes += o.compare_unread_bytes
            self.compare_exits += o.compare_exits
            self.staged_bytes += o.staged_bytes
            self.identity_skipped_bytes += o.identity_skipped_bytes
            self.record_wall(o.enqueue_s, o.sync_s)
            self.record_overlap(o.overlap_s)
            self.record_finish(o.finish_s)
            for field in ("h2d_bytes_by_device", "h2d_calls_by_device",
                          "skipped_bytes_by_device"):
                mine = getattr(self, field)
                for k, v in getattr(o, field).items():
                    mine[k] = mine.get(k, 0) + v
        return self

    def reset(self) -> None:
        self.h2d_bytes = self.d2h_bytes = 0
        self.h2d_calls = self.d2h_calls = 0
        self.wall_s = self.enqueue_s = self.sync_s = 0.0
        self.overlap_s = self.finish_s = 0.0
        self.skipped_bytes = self.delta_calls = 0
        self.compared_bytes = self.staged_bytes = 0
        self.compare_unread_bytes = self.compare_exits = 0
        self.identity_skipped_bytes = 0
        self.h2d_bytes_by_device.clear()
        self.h2d_calls_by_device.clear()
        self.skipped_bytes_by_device.clear()


def _legacy_spec(kind: str, device: Any = None, align_elems: int = 1,
                 delta: bool = False, sharding: Any = None) -> TransferSpec:
    """The old keyword surface, expressed as a spec."""
    dev_index = None
    if device is not None:
        dev_index = device if isinstance(device, int) \
            else jax.devices().index(device)
    return TransferSpec(kind=kind, delta=delta, sharding=sharding,
                        align_elems=align_elems, device=dev_index)


def _warn_legacy(what: str) -> None:
    warnings.warn(
        f"deprecated: {what} — construct a TransferSpec (or spec string) and "
        "use transfer_scheme()/TransferScheme.from_spec() instead",
        DeprecationWarning, stacklevel=3)


def _default_dp_sharding(k: int):
    """A 1-D "data" NamedSharding over the first ``k`` devices — what an
    int sharding axis (``@dp{k}``) executes on.  A mesh larger than the
    visible device set is an :class:`UnsupportedSpecError`, not a raw jax
    ValueError: after an elastic mesh change this is the recoverable
    "stale policy" signal (re-derive via ``TransferPolicy.reshard``)."""
    from jax.sharding import NamedSharding, PartitionSpec

    visible = jax.device_count()
    if k > visible:
        raise UnsupportedSpecError(
            f"sharded spec names a dp{k} mesh, but only {visible} device(s) "
            f"are visible — the policy is stale for this (surviving) mesh; "
            f"re-derive it for {visible} device(s)")
    from ..jaxenv import make_mesh

    mesh = make_mesh((k,), ("data",))
    return NamedSharding(mesh, PartitionSpec("data"))


class TransferScheme:
    """Protocol: move a nested state tree host<->device under a policy.

    Thin executor over a (spec, session) pair: the spec describes the
    policy, the session owns the reusable state.  A ``sharding`` axis (a
    ``NamedSharding``, or an int executed on the default 1-D data mesh)
    makes the scheme place data across every device of the sharding's mesh
    instead of on one device; the ledger then additionally records exact
    per-device bytes/DMA counts.
    """

    kind: str = "marshal"
    name: str = "base"
    # what the SECOND positional argument meant before the spec redesign
    # (TransferScheme/UVM/PointerChain took (device, sharding)); MarshalScheme
    # overrides with "align_elems".  Lets old positional call sites hit the
    # deprecation shim instead of binding into `session`.
    _second_legacy_kw: str = "sharding"

    def __init__(self, spec: Union[TransferSpec, str, None] = None,
                 session: Optional[engine_lib.TransferSession] = None,
                 **legacy: Any):
        if session is not None and not isinstance(
                session, engine_lib.TransferSession):
            legacy = dict(legacy, **{self._second_legacy_kw: session})
            session = None
        if legacy or not isinstance(spec, (TransferSpec, str, type(None))):
            # the pre-spec keyword surface (device=, sharding=, ...):
            # accepted, warned, and routed through a TransferSpec
            _warn_legacy(f"{type(self).__name__}({'device=..., ' if spec is not None else ''}"
                         f"{', '.join(f'{k}=...' for k in legacy)}) keyword construction")
            if spec is not None:
                legacy = dict(legacy, device=spec)
            spec = _legacy_spec(self.kind, **legacy)
        spec = TransferSpec.parse(spec) if spec is not None \
            else TransferSpec(kind=self.kind)
        if spec.kind != self.kind:
            raise UnsupportedSpecError(
                f"{type(self).__name__} executes kind={self.kind!r} specs, "
                f"got {spec}")
        self.spec = spec
        self.session = session if session is not None \
            else engine_lib.get_session()
        sharding = spec.sharding
        if isinstance(sharding, int):
            sharding = None if sharding == 1 and spec.device is None \
                else _default_dp_sharding(sharding)
        self.sharding = sharding
        devices = jax.devices()
        if spec.device is not None and spec.device >= len(devices):
            raise UnsupportedSpecError(
                f"spec {spec} names device index {spec.device}, but only "
                f"{len(devices)} devices are visible")
        self.device = devices[spec.device or 0]
        self.target = self.sharding if self.sharding is not None else self.device
        self.ledger = self.session.make_ledger()
        self.name = spec.name

    @classmethod
    def from_spec(cls, spec: Union[TransferSpec, str],
                  session: Optional[engine_lib.TransferSession] = None,
                  **kw: Any) -> "TransferScheme":
        """THE front door: executor for ``spec`` (string or dataclass),
        dispatched on its kind.  ``session`` defaults to the process
        session; ``shared_state=True`` (delta specs) makes executors of the
        same spec share the session's retained device state."""
        spec = TransferSpec.parse(spec)
        return _EXECUTORS[spec.kind](spec, session, **kw)

    def _shard_devices(self) -> list:
        return list(self.sharding.mesh.devices.flat)

    def _record_sharded_put(self, x: Any) -> None:
        """One sharded device_put = one DMA per device; each device receives
        its shard (replicated specs receive the full leaf per device)."""
        shard_shape = self.sharding.shard_shape(np.shape(x))
        itemsize = np.dtype(getattr(x, "dtype", np.asarray(x).dtype)).itemsize
        nb = int(np.prod(shard_shape, dtype=np.int64)) * itemsize \
            if shard_shape else itemsize
        for d in self._shard_devices():
            self.ledger.record_h2d(nb, device=d)

    # to_device returns a *device tree* whose accessed leaves live on device.
    def to_device(self, tree: Any, paths: Optional[Sequence[Union[str, TreePath]]] = None) -> Any:
        raise NotImplementedError

    def begin_pass(self, tree: Any,
                   paths: Optional[Sequence[Union[str, TreePath]]] = None
                   ) -> Tuple[List[Any], Callable[[], Any]]:
        """Enqueue this scheme's H2D copies for ``tree`` WITHOUT a sync.

        Returns ``(pending, finish)``: ``pending`` are the in-flight device
        values the caller must include in its own (single) barrier, and
        ``finish()`` — called after that barrier — completes the ledger /
        retained-state bookkeeping and returns the device tree.  This is the
        two-phase half of ``to_device`` that lets a compiled
        :class:`~repro.core.policy.TransferProgram` enqueue EVERY region's
        buckets before one ``jax.block_until_ready`` (staging safety comes
        from the per-buffer fence discipline, not the barrier).
        """
        raise NotImplementedError

    def from_device(self, device_tree: Any, host_tree: Any,
                    paths: Optional[Sequence[Union[str, TreePath]]] = None) -> Any:
        raise NotImplementedError

    def stage(self, tree: Any, used_paths: Sequence[Union[str, TreePath]],
              uvm_access: Optional[Sequence[Union[str, TreePath]]] = None,
              declare_refs: bool = True) -> tuple:
        """Algorithm-2 transfer step under this scheme's policy.

        Returns ``(device_tree, refs)`` where ``refs`` are the ChainRefs of
        the kernel's declared leaves in ``device_tree``.  The scenario
        driver (``repro.scenarios.driver``) is scheme-agnostic because each
        scheme owns its staging policy here instead of being a branch of an
        if/elif ladder in the harness.  The default covers eager whole-tree
        movers (marshalling); ``uvm_access`` is ignored by schemes without
        an on-access concept.  Transfer-only callers (steady-state timing
        loops) pass ``declare_refs=False`` to keep the chain-resolution
        walk out of the measured region; schemes that must declare to move
        (pointerchain) return their refs regardless.
        """
        dev = self.to_device(tree)
        return dev, (declare(tree, *used_paths) if declare_refs else ())

    def _put(self, x: Any) -> Any:
        return self._put_batch([x])[0]

    def _put_batch(self, xs: Sequence[Any], sync: bool = True) -> list:
        """Enqueue every H2D copy, then synchronize ONCE.

        One ledger DMA record per buffer per target device (same data
        motion as issuing them serially), but the copies overlap: wall time
        splits into the cheap enqueue phase and a single sync barrier.
        ``sync=False`` skips the barrier — the pipelined delta path fences
        the staging buffers instead (DESIGN.md §7).
        """
        if not xs:
            return []
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("TransferScheme.device_put"):
            ys = [jax.device_put(x, self.target) for x in xs]
        t1 = time.perf_counter()
        if sync:
            if _sanitizer._ACTIVE is not None:
                _sanitizer._ACTIVE.on_sync(f"{type(self).__name__}._put_batch")
            jax.block_until_ready(ys)
        t2 = time.perf_counter()
        self.ledger.record_wall(t1 - t0, t2 - t1)
        for x in xs:
            if self.sharding is not None:
                self._record_sharded_put(x)
            else:
                self.ledger.record_h2d(_nbytes(x), device=self.device)
        return ys

    def _get(self, x: Any) -> Any:
        return self._get_batch([x])[0]

    def _get_batch(self, xs: Sequence[Any]) -> list:
        """Enqueue every D2H copy (async where the array supports it), then
        materialize all of them behind one barrier."""
        if not xs:
            return []
        t0 = time.perf_counter()
        for x in xs:
            if hasattr(x, "copy_to_host_async"):
                x.copy_to_host_async()
        t1 = time.perf_counter()
        ys = [np.asarray(jax.device_get(x)) for x in xs]
        t2 = time.perf_counter()
        self.ledger.record_wall(t1 - t0, t2 - t1)
        for y in ys:
            self.ledger.record_d2h(_nbytes(y))
        return ys


# ---------------------------------------------------------------------------
# UVM — demand paging, simulated at leaf granularity
# ---------------------------------------------------------------------------

class LazyLeaf:
    """A leaf that is faulted to the device on first access (a page fault)."""

    __slots__ = ("_host", "_dev", "_scheme")

    def __init__(self, host_value: Any, scheme: "UVMScheme"):
        self._host = host_value
        self._dev: Optional[Any] = None
        self._scheme = scheme

    def get(self) -> Any:
        if self._dev is None:
            self._dev = self._scheme._put(self._host)
        return self._dev


class UVMScheme(TransferScheme):
    """Closest TPU analogue of CUDA UVM (see DESIGN.md §2.1).

    Every leaf is its own transfer granule, issued lazily at first access —
    zero developer effort, arbitrary transfer times, no batching.  TPUs have
    no page-faulting unified memory, so the *behavioural* contract is
    simulated: ``to_device`` wraps leaves in :class:`LazyLeaf`;
    ``materialize`` (a kernel touching the tree) triggers the faults.
    """

    kind = "uvm"
    name = "uvm"

    def to_device(self, tree, paths=None):
        return jax.tree_util.tree_map(lambda leaf: LazyLeaf(leaf, self), tree)

    def _fault_batch(self, subtree: Any) -> None:
        """Service every pending fault in ``subtree`` as ONE enqueue + sync.

        Each leaf stays its own transfer granule (one ledger DMA per fault,
        the UVM contract), but a single access burst no longer serializes."""
        pending, seen = [], set()
        for l in jax.tree_util.tree_leaves(
                subtree, is_leaf=lambda l: isinstance(l, LazyLeaf)):
            if isinstance(l, LazyLeaf) and l._dev is None and id(l) not in seen:
                seen.add(id(l))
                pending.append(l)
        if pending:
            for leaf, dev in zip(pending, self._put_batch(
                    [l._host for l in pending])):
                leaf._dev = dev

    def materialize(self, lazy_tree: Any,
                    paths: Optional[Sequence[Union[str, TreePath]]] = None) -> Any:
        """Touch leaves (all, or the chains a kernel dereferences)."""
        if paths is None:
            self._fault_batch(lazy_tree)
            return jax.tree_util.tree_map(
                lambda l: l.get() if isinstance(l, LazyLeaf) else l, lazy_tree,
                is_leaf=lambda l: isinstance(l, LazyLeaf))
        nodes = [(tp, tp.resolve(lazy_tree))
                 for tp in map(TreePath.parse, paths)]
        self._fault_batch([node for _, node in nodes])
        out = lazy_tree
        for tp, node in nodes:
            node = jax.tree_util.tree_map(
                lambda l: l.get() if isinstance(l, LazyLeaf) else l, node,
                is_leaf=lambda l: isinstance(l, LazyLeaf))
            out = tp.set(out, node)
        return out

    def stage(self, tree, used_paths, uvm_access=None, declare_refs=True):
        # demand paging: wrap lazily, then the access walk (the declared
        # access set, or the kernel's own chains) triggers the faults.
        dev = self.to_device(tree)
        dev = self.materialize(dev, paths=list(uvm_access or used_paths))
        return dev, (declare(tree, *used_paths) if declare_refs else ())

    def begin_pass(self, tree, paths=None):
        # demand paging transfers at ACCESS time, not program-pass time:
        # zero enqueues here, faults (and their ledger records) happen when
        # the lazy leaves are first dereferenced.
        return [], lambda: self.to_device(tree)

    def from_device(self, device_tree, host_tree, paths=None):
        # demand paging back: every device leaf is its own granule, but the
        # fetch burst is enqueued together and synchronized once.
        leaves, treedef = jax.tree_util.tree_flatten(
            device_tree, is_leaf=lambda l: isinstance(l, LazyLeaf))
        fetch_idx, fetch_vals = [], []
        for i, l in enumerate(leaves):
            if isinstance(l, LazyLeaf):
                if l._dev is not None:
                    fetch_idx.append(i)
                    fetch_vals.append(l._dev)
                else:
                    leaves[i] = l._host
            elif isinstance(l, jax.Array):
                fetch_idx.append(i)
                fetch_vals.append(l)
        for i, y in zip(fetch_idx, self._get_batch(fetch_vals)):
            leaves[i] = y
        return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Marshalling — Algorithm 1
# ---------------------------------------------------------------------------

class MarshalScheme(TransferScheme):
    """Algorithm 1 on the persistent arena engine.

    First call for a given tree shape: plan + compile (cache miss).  Every
    later call is pure data motion: in-place staging writes, one enqueued
    DMA per dtype bucket synchronized once, one fused-gather attach.

    The spec axes compose over the shared engine:

    * default               — one device, every bucket shipped, blocking
                              sync before staging may be rewritten (§4.3).
    * ``staging=db``        — same full motion, but non-blocking: staging
                              safety comes from the per-buffer fences, so
                              the next ``pack_host`` overlaps this call's
                              DMA (the §7 pipeline without the delta skip).
    * ``delta``             — steady-state incremental transfers: the
                              executor's :class:`~repro.core.engine.DeltaState`
                              retains the device copy of every bucket and
                              re-ships only buckets whose staging version
                              moved; clean buckets are ``skipped_bytes``.
    * ``sharding``          — per-device arenas: every bucket is padded to
                              a per-device multiple and split into equal
                              contiguous shards; ALL (bucket x device)
                              transfers are enqueued before one sync, then
                              each bucket is assembled into one global
                              sharded array.
    * ``delta + sharding``  — per-(bucket, device) incremental transfers:
                              a dirty bucket re-ships ONLY the shards whose
                              bytes moved (``ArenaEntry.shard_versions``);
                              clean shards are skipped per device, keeping
                              ``h2d + skipped == full sharded motion`` exact
                              on every device of the mesh.
    """

    kind = "marshal"
    name = "marshal"
    _second_legacy_kw = "align_elems"   # MarshalScheme(device, align_elems, …)

    def __init__(self, spec=None, session=None, shared_state: bool = False,
                 **legacy):
        super().__init__(spec, session, **legacy)
        self.align_elems = self.spec.align_elems
        self.delta = self.spec.delta
        self.staging = self.spec.staging
        self.layout: Optional[arena_lib.ArenaLayout] = None
        self._entry: Optional[engine_lib.ArenaEntry] = None
        # retained delta state lives in the SESSION (its device memory has
        # a lifecycle); per executor by default, per spec when shared.
        self._delta_state = self.session.delta_state(
            self.spec if shared_state else None)

    def _entry_for(self, tree) -> engine_lib.ArenaEntry:
        entry = self.session.get_entry(tree, self.align_elems,
                                       sharding=self.sharding)
        self._entry = entry
        self.layout = entry.layout
        return entry

    def mark_dirty(self, tree, *paths: Union[str, TreePath]) -> None:
        """Delta API for callers that mutate host leaves IN PLACE: flag the
        buckets under ``paths`` (all buckets if none) so the next
        ``to_device`` re-compares and re-ships them."""
        entry = self._entry_for(tree)
        if not paths:
            entry.mark_dirty()
            return
        slots = entry.layout.slots
        buckets = {slots[r.flat_index].bucket for r in declare(tree, *paths)}
        entry.mark_dirty(*buckets)

    def to_device(self, tree, paths=None):
        # 1) determineTotalBytes + requestList (cached); 2) pack into the
        # persistent staging arena; 3) ONE enqueued transfer per dtype
        # bucket (per device when sharded, only dirty buckets/shards when
        # delta); 4) attach = fused gather over device buffers.
        if self.delta and self.sharding is not None:
            return self._to_device_delta_sharded(tree)
        if self.sharding is not None:
            return self._to_device_sharded(tree)
        if self.delta:
            return self._to_device_delta(tree)
        if self.staging == "double_buffered":
            return self._to_device_pipelined(tree)
        entry, buffers = self._pack(tree)
        names = list(buffers)
        dev = self._put_batch([buffers[b] for b in names])
        out = entry.unpack(dict(zip(names, dev)))
        # jax.device_put may zero-copy ALIAS a suitably aligned numpy buffer
        # (observed on the XLA CPU client), and staging is rewritten by the
        # next pack_host.  Synchronizing the fused unpack here guarantees no
        # live device value still reads staging when we return.
        return jax.block_until_ready(out)

    def _pack(self, tree, trust_identity: bool = False):
        """``pack_host`` on this tree's entry, booked into the ledger."""
        entry = self._entry_for(tree)
        buffers = entry.pack_host(tree, trust_identity=trust_identity)
        self.ledger.record_pack(entry.last_pack)
        return entry, buffers

    # -- sanitizer hooks (DESIGN.md §13.3) -----------------------------------
    @staticmethod
    def _san_enqueued(entry, buffers, names) -> None:
        """Report each enqueued bucket to the staging sanitizer.  ``buffers``
        maps bucket -> the exact host array handed to device_put (use an
        empty map for sharded paths, which enqueue per-shard views)."""
        san = _sanitizer._ACTIVE
        if san is not None:
            for b in names:
                san.on_enqueue(entry, b, buffers.get(b))

    @staticmethod
    def _san_drained(entry, names) -> None:
        san = _sanitizer._ACTIVE
        if san is not None:
            for b in names:
                san.on_drain(entry, b)

    # -- double-buffered full transfers (the §7 pipeline, no delta skip) -----
    def _begin_pipelined(self, tree):
        entry, buffers = self._pack(tree)
        names = list(buffers)
        dev = self._put_batch([buffers[b] for b in names], sync=False)
        self._san_enqueued(entry, buffers, names)

        def finish():
            self._san_drained(entry, names)
            out_leaves = entry.unpack_leaves_jit(dict(zip(names, dev)))
            out = jax.tree_util.tree_unflatten(entry.layout.treedef,
                                               list(out_leaves))
            for b, arr in zip(names, dev):
                entry.add_fence(b, [arr])
            for b in names:
                entry.add_fence(b, [out_leaves[i]
                                    for i in entry._bucket_slots[b]])
            return out

        return list(dev), finish

    def _to_device_pipelined(self, tree):
        _, finish = self._begin_pipelined(tree)
        return finish()

    # -- delta: dirty-bucket incremental transfers ---------------------------
    def _begin_delta(self, tree):
        entry, buffers = self._pack(tree, trust_identity=True)
        retained = self._delta_state.retained.setdefault(entry, {})
        names = list(buffers)
        bucket_bytes = entry.layout.bucket_bytes()
        dirty = [b for b in names
                 if retained.get(b, (None, None))[0] != entry.versions[b]]
        clean = [b for b in names if b not in dirty]
        if not dirty:
            memo = self._delta_state.last_unpack.get(entry)
            if memo is not None and memo[0] == entry.versions:
                def finish_memo():
                    # fully clean repeat: the previously attached device
                    # tree is immutable and still bit-identical.
                    for b in clean:
                        self.ledger.record_skip(bucket_bytes[b],
                                                device=self.device)
                    self.ledger.delta_calls += 1
                    return memo[1]

                return [], finish_memo
        dev = self._put_batch([buffers[b] for b in dirty], sync=False)
        self._san_enqueued(entry, buffers, dirty)

        def finish():
            self._san_drained(entry, dirty)
            for b, arr in zip(dirty, dev):
                retained[b] = (entry.versions[b], arr)
            for b in clean:
                self.ledger.record_skip(bucket_bytes[b], device=self.device)
            if clean:
                self.ledger.delta_calls += 1
            out_leaves = entry.unpack_leaves_jit(
                {b: retained[b][1] for b in names})
            out = jax.tree_util.tree_unflatten(entry.layout.treedef,
                                               list(out_leaves))
            # every retained device buffer aliases its bucket's ACTIVE
            # staging buffer (a bucket only rotates when dirty, which
            # replaces the retained copy), so fence each active buffer with
            # the values that read it: the new DMA plus this call's gather
            # outputs of THAT bucket's slots (each leaf slices only its own
            # bucket — fencing the whole tree on every bucket would pin
            # FENCE_DEPTH generations of the full device state).
            for b, arr in zip(dirty, dev):
                entry.add_fence(b, [arr])
            for b in names:
                entry.add_fence(b, [out_leaves[i]
                                    for i in entry._bucket_slots[b]])
            self._delta_state.last_unpack[entry] = (dict(entry.versions), out)
            return out

        return list(dev), finish

    def _to_device_delta(self, tree):
        _, finish = self._begin_delta(tree)
        return finish()

    def begin_pass(self, tree, paths=None):
        """Enqueue-only half of :meth:`to_device` (see the base docstring).

        All four mode combinations stage through the per-buffer fence
        discipline, so the caller's single barrier is a latency choice, not
        a correctness requirement."""
        if self.delta and self.sharding is not None:
            return self._begin_delta_sharded(tree)
        if self.sharding is not None:
            return self._begin_sharded(tree)
        if self.delta:
            return self._begin_delta(tree)
        return self._begin_pipelined(tree)

    # -- sharded: per-device arenas ------------------------------------------
    def _bucket_sharding(self):
        mesh = self.sharding.mesh
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))

    def _shard_device_order(self) -> list:
        """Devices in shard order: device ``i`` of this list owns the i-th
        contiguous sub-range of every bucket (the even 1-D split gives every
        bucket the same order)."""
        bsh = self._bucket_sharding()
        k = engine_lib.num_shards_of(self.sharding)
        items = [((0 if sl.start is None else int(sl.start)), d)
                 for d, (sl,) in bsh.devices_indices_map((k,)).items()]
        return [d for _, d in sorted(items, key=lambda t: t[0])]

    def _enqueue_sharded(self, buffers: "engine_lib.Buffers") -> Dict[str, list]:
        """Enqueue every (bucket, device) shard without synchronizing;
        returns the per-bucket shard plan, enqueue time recorded."""
        bsh = self._bucket_sharding()
        plan: Dict[str, list] = {}
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("TransferScheme.device_put"):
            for b, buf in buffers.items():
                n = int(buf.shape[0])
                shards = []
                for dev, idx in bsh.devices_indices_map((n,)).items():
                    sl = idx[0]
                    lo = 0 if sl.start is None else int(sl.start)
                    hi = n if sl.stop is None else int(sl.stop)
                    shards.append((lo, hi, dev,
                                   jax.device_put(buf[lo:hi], dev)))
                shards.sort(key=lambda s: s[0])
                plan[b] = shards
        self.ledger.record_wall(time.perf_counter() - t0, 0.0)
        return plan

    def _assemble_sharded(self, buffers: "engine_lib.Buffers",
                          plan: Dict[str, list]) -> Dict[str, Any]:
        """Ledger bookkeeping + global-array assembly of an enqueued plan."""
        bsh = self._bucket_sharding()
        out: Dict[str, Any] = {}
        for b, shards in plan.items():
            itemsize = np.dtype(b).itemsize
            for lo, hi, dev, _ in shards:
                self.ledger.record_h2d((hi - lo) * itemsize, device=dev)
            out[b] = jax.make_array_from_single_device_arrays(
                (int(buffers[b].shape[0]),), bsh, [s[3] for s in shards])
        return out

    def _begin_sharded(self, tree):
        entry, buffers = self._pack(tree)
        plan = self._enqueue_sharded(buffers)
        pending = [s[3] for ss in plan.values() for s in ss]
        self._san_enqueued(entry, {}, list(buffers))

        def finish():
            self._san_drained(entry, list(buffers))
            dev_bufs = self._assemble_sharded(buffers, plan)
            names = list(buffers)
            out_leaves = entry.unpack_leaves_jit(dev_bufs)
            out = jax.tree_util.tree_unflatten(entry.layout.treedef,
                                               list(out_leaves))
            # shard views alias staging: fence each bucket with its global
            # array (which holds the per-shard arrays) + its gather outputs
            for b in names:
                entry.add_fence(b, [dev_bufs[b]])
                entry.add_fence(b, [out_leaves[i]
                                    for i in entry._bucket_slots[b]])
            return out

        return pending, finish

    def _to_device_sharded(self, tree):
        entry, buffers = self._pack(tree)
        dev_bufs = self._put_sharded(buffers)
        out = entry.unpack(dev_bufs)
        # same sync-before-rewrite discipline as the single-device path:
        # shard views alias staging until the fused gather has consumed them
        return jax.block_until_ready(out)

    def _put_sharded(self, buffers: "engine_lib.Buffers") -> Dict[str, Any]:
        """Enqueue every (bucket, device) shard, ONE sync, then assemble
        each bucket into a global array sharded over the whole mesh."""
        plan = self._enqueue_sharded(buffers)
        t0 = time.perf_counter()
        if _sanitizer._ACTIVE is not None:
            _sanitizer._ACTIVE.on_sync("MarshalScheme._put_sharded")
        jax.block_until_ready([s[3] for ss in plan.values() for s in ss])
        self.ledger.record_wall(0.0, time.perf_counter() - t0)
        return self._assemble_sharded(buffers, plan)

    # -- delta x sharding: per-(bucket, device) incremental transfers --------
    def _begin_delta_sharded(self, tree):
        """The composed axes: pack versions per shard, re-ship ONLY the
        (bucket, device) shards whose bytes moved, book every clean shard
        as skipped bytes ON ITS DEVICE, and assemble each bucket from the
        retained + fresh per-shard arrays.  Non-blocking like the unsharded
        delta path: staging safety is the per-buffer fence discipline plus
        range disjointness (a clean shard's byte range is never rewritten
        while its retained array is live — see engine.py)."""
        entry, buffers = self._pack(tree, trust_identity=True)
        retained = self._delta_state.retained.setdefault(entry, {})
        names = list(buffers)
        order = self._shard_device_order()
        k = len(order)
        ranges = arena_lib.shard_ranges(entry.layout, k)
        ships: List[tuple] = []   # (bucket, shard, lo, hi, device)
        skips: List[tuple] = []   # (bucket, shard, nbytes, device)
        for b in names:
            held = retained.setdefault(b, [None] * k)
            itemsize = np.dtype(b).itemsize
            for s, ((lo, hi), dev) in enumerate(zip(ranges[b], order)):
                ver = entry.shard_versions[b][s]
                if held[s] is None or held[s][0] != ver:
                    ships.append((b, s, lo, hi, dev))
                else:
                    skips.append((b, s, (hi - lo) * itemsize, dev))
        if not ships:
            memo = self._delta_state.last_unpack.get(entry)
            if memo is not None and memo[0] == entry.shard_versions:
                def finish_memo():
                    # fully clean repeat: zero DMA, zero dispatch — every
                    # shard of every bucket is booked as skipped on its
                    # device.
                    for b, s, nbytes, dev in skips:
                        self.ledger.record_skip(nbytes, device=dev)
                    self.ledger.delta_calls += 1
                    return memo[1]

                return [], finish_memo
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("TransferScheme.device_put"):
            new = [(b, s, dev, jax.device_put(buffers[b][lo:hi], dev))
                   for b, s, lo, hi, dev in ships]
        self.ledger.record_wall(time.perf_counter() - t0, 0.0)
        shipped_buckets = sorted({s[0] for s in ships})
        self._san_enqueued(entry, {}, shipped_buckets)

        def finish():
            self._san_drained(entry, shipped_buckets)
            for (b, s, lo, hi, dev), (_, _, _, arr) in zip(ships, new):
                retained[b][s] = (entry.shard_versions[b][s], arr)
                self.ledger.record_h2d((hi - lo) * np.dtype(b).itemsize,
                                       device=dev)
            for b, s, nbytes, dev in skips:
                self.ledger.record_skip(nbytes, device=dev)
            if skips:
                self.ledger.delta_calls += 1
            bsh = self._bucket_sharding()
            assembled = {
                b: jax.make_array_from_single_device_arrays(
                    (int(entry.layout.bucket_sizes[b]),), bsh,
                    [retained[b][s][1] for s in range(k)])
                for b in names}
            out_leaves = entry.unpack_leaves_jit(assembled)
            out = jax.tree_util.tree_unflatten(entry.layout.treedef,
                                               list(out_leaves))
            for b, s, dev, arr in new:
                entry.add_fence(b, [arr])
            for b in names:
                entry.add_fence(b, [out_leaves[i]
                                    for i in entry._bucket_slots[b]])
            self._delta_state.last_unpack[entry] = (
                {b: list(v) for b, v in entry.shard_versions.items()}, out)
            return out

        return [arr for _, _, _, arr in new], finish

    def _to_device_delta_sharded(self, tree):
        _, finish = self._begin_delta_sharded(tree)
        return finish()

    def from_device(self, device_tree, host_tree, paths=None):
        # demarshal: fused scatter repack on device, batched D2H per bucket
        entry = self._entry if self._entry is not None \
            else self._entry_for(device_tree)
        buffers = entry.pack_device(device_tree)
        names = list(buffers)
        host = self._get_batch([buffers[b] for b in names])
        return arena_lib.unpack(dict(zip(names, host)), entry.layout)


# ---------------------------------------------------------------------------
# pointerchain — selective deep copy of declared chains
# ---------------------------------------------------------------------------

class PointerChainScheme(TransferScheme):
    kind = "pointerchain"
    name = "pointerchain"

    def __init__(self, spec=None, session=None, **legacy):
        super().__init__(spec, session, **legacy)
        self.refs: tuple[ChainRef, ...] = ()

    def to_device(self, tree, paths=None):
        """Extract effective leaves for the declared chains; move ONLY them.

        Returns the tree with declared leaves resident on device and all
        interior/undeclared state left on the host — the kernel is handed
        the extracted leaves, never the containers (paper §3).
        """
        if paths is None:
            paths = [str(p) for p, _ in leaf_items(tree)]
        self.refs = declare(tree, *paths)
        leaves = extract(tree, self.refs)
        # one enqueue per declared chain, ONE sync for the whole declare set
        dev_leaves = self._put_batch(leaves)
        return insert(tree, self.refs, dev_leaves)

    def stage(self, tree, used_paths, uvm_access=None, declare_refs=True):
        # selective deep copy: ONLY the declared chains move; the refs were
        # resolved by to_device's declare (a required part of the transfer,
        # so they are returned even for transfer-only callers) and index
        # the same treedef.
        dev = self.to_device(tree, paths=list(used_paths))
        return dev, self.refs

    def begin_pass(self, tree, paths=None):
        # one enqueue per declared chain (every leaf when the region has no
        # chain selection), no sync — the caller's barrier covers them
        if paths is None:
            paths = [str(p) for p, _ in leaf_items(tree)]
        self.refs = declare(tree, *paths)
        leaves = extract(tree, self.refs)
        dev_leaves = self._put_batch(leaves, sync=False)
        return list(dev_leaves), \
            lambda: insert(tree, self.refs, dev_leaves)

    def extract_leaves(self, tree: Any) -> list[Any]:
        return extract(tree, self.refs)

    def from_device(self, device_tree, host_tree, paths=None):
        leaves = extract(device_tree, self.refs)
        host_leaves = self._get_batch(leaves)
        return insert(host_tree, self.refs, host_leaves)


_EXECUTORS: Dict[str, Callable[..., TransferScheme]] = {
    "uvm": UVMScheme,
    "marshal": MarshalScheme,
    "pointerchain": PointerChainScheme,
}


def transfer_scheme(spec: Union[TransferSpec, str],
                    session: Optional[engine_lib.TransferSession] = None,
                    **kw: Any) -> TransferScheme:
    """Executor for ``spec`` — module-level alias of
    :meth:`TransferScheme.from_spec`."""
    return TransferScheme.from_spec(spec, session, **kw)


# ---------------------------------------------------------------------------
# deprecation shims — the pre-spec registry surface
# ---------------------------------------------------------------------------

def _legacy_factory(name: str, **kw) -> TransferScheme:
    _warn_legacy(f"the scheme registry ({name!r})")
    delta = bool(kw.pop("delta", False)) or name == "marshal_delta"
    kind = "marshal" if name == "marshal_delta" else name
    spec = _legacy_spec(kind, delta=delta, **kw)
    return TransferScheme.from_spec(spec)


def _named_factory(name: str) -> Callable[..., TransferScheme]:
    def factory(**kw) -> TransferScheme:
        return _legacy_factory(name, **kw)
    factory.__name__ = f"make_{name}"
    return factory


SCHEMES: dict[str, Callable[..., TransferScheme]] = {
    name: _named_factory(name)
    for name in ("uvm", "marshal", "marshal_delta", "pointerchain")
}


def make_scheme(name: str, **kw) -> TransferScheme:
    """Deprecated: ``transfer_scheme(spec)`` is the composable front door
    (every registry name parses as a spec string, e.g. ``"marshal_delta"``
    == ``"marshal+delta"``)."""
    if name not in SCHEMES:
        raise KeyError(f"unknown transfer scheme {name!r}; options: {sorted(SCHEMES)}")
    return _legacy_factory(name, **kw)
