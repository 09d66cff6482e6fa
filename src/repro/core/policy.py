"""Path-scoped transfer policies — per-subtree specs compiled into ONE program.

The paper's ``pointerchain`` directive names *specific pointer chains* and
treats each region of the nested structure differently; a single
:class:`~repro.core.spec.TransferSpec` applied to the whole tree is exactly
what the directive model forbids.  Following the directive-based porting
surveyed in ESCAPE D2.2 and LLAMA's separation of memory layout from access
expression, a **policy tree** maps tree-path regions to specs:

  * :class:`PolicyRule`     — a frozen (path pattern, TransferSpec) pair.
  * :class:`TransferPolicy` — an ordered rule set with a required default
    (``**``) rule; the most specific matching pattern wins per leaf.
  * :class:`TransferProgram`— the compiled artifact
    (``TransferSession.compile(tree, policy)``): the treedef partitioned
    into regions (every leaf covered exactly once), one thin scheme
    executor per region reusing the session's cached layouts/entries, and
    a ``to_device`` pass that enqueues ALL regions' buckets before ONE
    sync.

Pattern grammar (extends the spec grammar of DESIGN.md §8.1)::

    policy  := rule (';' rule)*
    rule    := pattern '=' spec
    pattern := '**' | part ('/' part)* ('/**')?
    part    := name index* | '[' INT ']' | '*'

``*`` matches exactly one path step, a trailing ``**`` matches any
remaining suffix (including none), and ``kids[2]`` is the two steps
``kids`` then ``[2]`` — the same tokens a :class:`TreePath` prints.  E.g.::

    params/**=marshal@dp8; opt/**=marshal+delta; **=pointerchain

``str``/``parse`` round-trip exactly; a bare spec string (no ``=``) parses
as the one-rule policy ``**=<spec>``.  The capability matrix is validated
ONCE at construction: every per-rule spec goes through
``TransferSpec.parse`` and policy-level conflicts (duplicate patterns,
missing default rule, sharded rules that disagree on the mesh size —
overlapping shard axes) raise :class:`UnsupportedPolicyError`.

Matching (most-specific wins): among the rules whose pattern matches a
leaf path, pick the longest fixed prefix, then the most literal (non-``*``)
steps, then an exact pattern over a ``**`` one; remaining ties go to
declaration order.  Partitioning depends only on the treedef's paths, so
treedef-equal trees always partition identically.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax

from ..analysis import sanitizer as _sanitizer
from .spec import TransferSpec, UnsupportedSpecError
from .treepath import TreePath, leaf_paths, _parse as _parse_steps


class UnsupportedPolicyError(UnsupportedSpecError):
    """The canonical error for any invalid policy: unparseable rule text,
    a rule spec off the capability matrix, or a policy-level conflict
    (duplicate patterns, missing ``**`` default, overlapping shard axes)."""


class TransferTimeout(TimeoutError):
    """A bounded wait on an asynchronous program pass expired before the
    background barrier completed.

    Raised by :meth:`ProgramFuture.result` when given a ``timeout``.  The
    pass is left **un-materialized** — no finish bookkeeping ran, ledgers
    and retained state are untouched — so ``result()`` may simply be
    retried.  Latency-bounded callers (the serving prefill path) treat
    this as the typed transient-fault signal for retry-with-backoff
    instead of blocking a request forever behind a hung DMA."""

    def __init__(self, waited_s: float, detail: str = ""):
        msg = (f"async program pass still pending after {waited_s:.3f}s"
               + (f" ({detail})" if detail else ""))
        super().__init__(msg)
        self.waited_s = waited_s


# ---------------------------------------------------------------------------
# patterns
# ---------------------------------------------------------------------------

def _pattern_parse(pattern: str) -> Tuple[Tuple[Any, ...], bool]:
    """``pattern`` -> (fixed steps, has trailing globstar).  Steps are the
    TreePath step types (str | int) plus the literal single-step wildcard
    ``"*"``."""
    text = pattern.strip()
    if not text:
        raise UnsupportedPolicyError("empty path pattern")
    parts = text.split("/")
    globstar = parts[-1] == "**"
    if globstar:
        parts = parts[:-1]
    steps: List[Any] = []
    for part in parts:
        if part == "**":
            raise UnsupportedPolicyError(
                f"cannot parse pattern {pattern!r}: '**' is only allowed as "
                "the trailing part")
        if part == "*":
            steps.append("*")
            continue
        if not part:
            raise UnsupportedPolicyError(
                f"cannot parse pattern {pattern!r}: empty step")
        try:
            steps.extend(_parse_steps(part))
        except ValueError as e:
            raise UnsupportedPolicyError(
                f"cannot parse pattern {pattern!r}: {e}") from None
    if not steps and not globstar:
        raise UnsupportedPolicyError(
            f"cannot parse pattern {pattern!r}: no steps")
    return tuple(steps), globstar


def _pattern_str(steps: Tuple[Any, ...], globstar: bool) -> str:
    """Canonical string form: int steps print attached (``kids[2]``), the
    inverse of :func:`_pattern_parse`."""
    out: List[str] = []
    for step in steps:
        if isinstance(step, int):
            if out:
                out[-1] += f"[{step}]"
            else:
                out.append(f"[{step}]")
        else:
            out.append(step)
    if globstar:
        out.append("**")
    return "/".join(out)


@dataclasses.dataclass(frozen=True)
class PolicyRule:
    """One (path pattern -> TransferSpec) point of a policy tree.  Frozen
    and hashable; the pattern is canonicalized so equal rules compare equal
    regardless of spelling (``"opt/m"`` == ``"opt/m"``; specs normalize via
    ``TransferSpec.parse``)."""

    pattern: str
    spec: TransferSpec

    def __post_init__(self):
        steps, globstar = _pattern_parse(self.pattern)
        object.__setattr__(self, "pattern", _pattern_str(steps, globstar))
        object.__setattr__(self, "spec", TransferSpec.parse(self.spec))
        # parsed once here; eq/hash stay on the declared (canonical) fields.
        # partition_tree matches every (leaf, rule) pair, so per-call
        # re-parsing would dominate policy resolution on big state trees.
        object.__setattr__(self, "_steps", steps)
        object.__setattr__(self, "_globstar", globstar)
        object.__setattr__(
            self, "_specificity",
            (len(steps), sum(1 for s in steps if s != "*"),
             0 if globstar else 1))

    # -- matching ------------------------------------------------------------
    def _parts(self) -> Tuple[Tuple[Any, ...], bool]:
        return self._steps, self._globstar

    def _match_steps(self, got: Tuple[Any, ...]) -> bool:
        steps = self._steps
        if (len(got) < len(steps)) if self._globstar \
                else (len(got) != len(steps)):
            return False
        return all(p == "*" or p == s for p, s in zip(steps, got))

    def matches(self, path: Union[str, TreePath]) -> bool:
        return self._match_steps(TreePath.parse(path).steps)

    def specificity(self) -> Tuple[int, int, int]:
        """(fixed prefix length, literal steps, exactness) — compared
        lexicographically, larger wins; declaration order breaks ties."""
        return self._specificity

    def __str__(self) -> str:
        return f"{self.pattern}={self.spec}"


@dataclasses.dataclass(frozen=True)
class TransferPolicy:
    """An ordered rule set over tree-path regions.  Validated once at
    construction; hashable, so a policy is a cache key like a spec."""

    rules: Tuple[PolicyRule, ...]

    def __post_init__(self):
        object.__setattr__(self, "rules", tuple(self.rules))
        if not self.rules:
            raise UnsupportedPolicyError("a policy needs at least one rule")
        seen: Dict[str, PolicyRule] = {}
        for rule in self.rules:
            if not isinstance(rule, PolicyRule):
                raise UnsupportedPolicyError(
                    f"rules must be PolicyRule instances, got {rule!r}")
            if rule.pattern in seen:
                raise UnsupportedPolicyError(
                    f"duplicate pattern {rule.pattern!r} in policy")
            seen[rule.pattern] = rule
        if "**" not in seen:
            raise UnsupportedPolicyError(
                "a policy requires a default rule ('**=<spec>') so every "
                "leaf is covered")
        shard_sizes = {r.spec.num_shards for r in self.rules
                       if r.spec.num_shards > 1}
        if len(shard_sizes) > 1:
            raise UnsupportedPolicyError(
                f"overlapping shard axes: sharded rules must agree on the "
                f"mesh size, got {sorted(shard_sizes)}")

    # -- construction --------------------------------------------------------
    @classmethod
    def of(cls, spec: Union[str, TransferSpec]) -> "TransferPolicy":
        """The one-rule policy a whole-tree spec becomes (``**=<spec>``)."""
        return cls((PolicyRule("**", TransferSpec.parse(spec)),))

    @classmethod
    def parse(cls, text: "str | TransferPolicy | TransferSpec"
              ) -> "TransferPolicy":
        """Inverse of ``str``: ``parse(str(policy)) == policy``.  A policy /
        spec instance passes through (specs become one-rule policies); a
        bare spec string (no ``=``) parses as ``**=<spec>``."""
        if isinstance(text, cls):
            return text
        if isinstance(text, TransferSpec):
            return cls.of(text)
        if not isinstance(text, str):
            raise UnsupportedPolicyError(
                f"expected a policy string or TransferPolicy, got {text!r}")
        if "=" not in text:
            return cls.of(TransferSpec.parse(text.strip()))
        rules = []
        for chunk in text.split(";"):
            chunk = chunk.strip()
            if not chunk:
                continue
            pattern, eq, spec = chunk.partition("=")
            if not eq or not pattern.strip() or not spec.strip():
                raise UnsupportedPolicyError(
                    f"cannot parse policy rule {chunk!r}: want "
                    "'<pattern>=<spec>'")
            rules.append(PolicyRule(pattern.strip(), spec.strip()))
        return cls(tuple(rules))

    def __str__(self) -> str:
        return "; ".join(str(r) for r in self.rules)

    # -- resolution ----------------------------------------------------------
    def match(self, path: Union[str, TreePath]) -> PolicyRule:
        """The winning rule for one leaf path (most specific; see module
        docstring).  Total, thanks to the required default rule."""
        got = TreePath.parse(path).steps      # parsed once, not per rule
        best: Optional[PolicyRule] = None
        best_score: Tuple[int, int, int] = (-1, -1, -1)
        for rule in self.rules:
            if rule._match_steps(got):
                score = rule.specificity()
                if score > best_score:
                    best, best_score = rule, score
        assert best is not None  # '**' always matches
        return best

    @property
    def num_shards(self) -> int:
        """The policy's (single, validated) sharded-mesh size, 1 if none."""
        return max((r.spec.num_shards for r in self.rules), default=1)

    def reshard(self, k: int) -> "TransferPolicy":
        """Re-derive this policy for a mesh of ``k`` devices: every sharded
        rule's mesh size becomes ``k`` (``k == 1`` drops the sharding axis
        entirely), unsharded rules pass through untouched.  This is the
        elastic-restart move — a policy compiled for the pre-failure mesh
        is re-derived for the surviving one, keeping every other axis
        (kind, delta, alignment, staging) of every rule intact."""
        if int(k) < 1:
            raise UnsupportedPolicyError(
                f"cannot reshard a policy onto {k} devices")
        k = int(k)
        rules = tuple(
            PolicyRule(r.pattern, r.spec.replace(sharding=None if k == 1
                                                 else k))
            if r.spec.num_shards > 1 else r
            for r in self.rules)
        return TransferPolicy(rules)

    def with_rule(self, pattern: str,
                  spec: Union[str, TransferSpec]) -> "TransferPolicy":
        """This policy with ``pattern``'s spec replaced (the pattern must
        already be a rule — a policy's region structure is part of its
        identity; the autotuner varies specs, never patterns)."""
        spec = TransferSpec.parse(spec)
        if pattern not in {r.pattern for r in self.rules}:
            raise UnsupportedPolicyError(
                f"pattern {pattern!r} is not a rule of this policy")
        return TransferPolicy(tuple(
            PolicyRule(r.pattern, spec) if r.pattern == pattern else r
            for r in self.rules))

    def neighbors(self, mesh_size: int = 1) -> Tuple["TransferPolicy", ...]:
        """Every policy differing from this one in exactly ONE rule's spec,
        over the bounded candidate grid (:func:`candidate_specs`) — the
        local-search moves of the cost-guided autotuner."""
        out: List[TransferPolicy] = []
        for rule in self.rules:
            for spec in candidate_specs(mesh_size):
                if spec != rule.spec:
                    out.append(self.with_rule(rule.pattern, spec))
        return tuple(out)


# ---------------------------------------------------------------------------
# the bounded candidate grid (autotuner / DC111 search space)
# ---------------------------------------------------------------------------

def candidate_specs(mesh_size: int = 1) -> Tuple[TransferSpec, ...]:
    """The bounded per-region spec grid the cost-guided search enumerates:
    tight-packed marshal × {plain, delta} × {unsharded, @dp<mesh>} plus
    unsharded pointerchain.

    Deliberately excluded: ``uvm`` (demand paging defers the motion to
    access time — zero pass-time bytes would trivially "win" while changing
    access semantics), device pins (placement is a correctness decision,
    not a cost one) and ``align>1`` (the grid is the tight-packing
    frontier; alignment only ever adds padding bytes).
    """
    mesh_size = int(mesh_size)
    out = [TransferSpec("marshal"),
           TransferSpec("marshal", delta=True),
           TransferSpec("pointerchain")]
    if mesh_size > 1:
        out.append(TransferSpec("marshal", sharding=mesh_size))
        out.append(TransferSpec("marshal", delta=True, sharding=mesh_size))
    return tuple(out)


def enumerate_policies(patterns: Tuple[str, ...], mesh_size: int = 1,
                       specs: Optional[Tuple[TransferSpec, ...]] = None
                       ) -> List[TransferPolicy]:
    """The full bounded grid over a FIXED region structure: every assignment
    of candidate specs to the given rule patterns (which must include the
    required ``**`` default).  ``len(specs) ** len(patterns)`` policies —
    the autotuner prunes this statically before any device touches data."""
    import itertools

    specs = candidate_specs(mesh_size) if specs is None else tuple(specs)
    out: List[TransferPolicy] = []
    for combo in itertools.product(specs, repeat=len(patterns)):
        out.append(TransferPolicy(tuple(
            PolicyRule(p, s) for p, s in zip(patterns, combo))))
    return out


# ---------------------------------------------------------------------------
# region partitioning
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Region:
    """One policy region of a concrete treedef: the winning rule plus the
    flat leaf indices (and their paths) it covers."""

    rule: PolicyRule
    indices: Tuple[int, ...]
    paths: Tuple[str, ...]

    @property
    def key(self) -> str:
        return self.rule.pattern

    @property
    def spec(self) -> TransferSpec:
        return self.rule.spec


def partition_tree(tree: Any, policy: Union[str, TransferPolicy]
                   ) -> "collections.OrderedDict[str, Region]":
    """Partition a tree's leaves into policy regions, in rule declaration
    order (empty regions omitted).  Every leaf lands in exactly one region
    — matching is total and single-winner — and the result depends only on
    the treedef's paths, so treedef-equal trees partition identically."""
    policy = TransferPolicy.parse(policy)
    paths = leaf_paths(tree)
    by_rule: Dict[str, List[int]] = {r.pattern: [] for r in policy.rules}
    for i, path in enumerate(paths):
        by_rule[policy.match(path).pattern].append(i)
    out: "collections.OrderedDict[str, Region]" = collections.OrderedDict()
    for rule in policy.rules:
        idx = by_rule[rule.pattern]
        if idx:
            out[rule.pattern] = Region(
                rule, tuple(idx), tuple(str(paths[i]) for i in idx))
    return out


# ---------------------------------------------------------------------------
# the compiled program
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ProgramStats:
    """One ``to_device`` pass of a program: how many H2D copies each region
    enqueued, and that the whole pass synchronized exactly once.

    The pipelined executor splits the barrier's attribution: ``sync_s`` is
    what the CALLER waited (inside ``ProgramFuture.result()``), ``overlap_s``
    is how long the barrier actually ran on the background thread — their
    difference is the sync wall the pipeline moved off the critical path.
    ``finish_s`` is the post-barrier bookkeeping (retained-state updates,
    fused-gather dispatch), always on the caller's thread."""

    enqueues: Dict[str, int]
    syncs: int
    sync_s: float
    overlap_s: float = 0.0
    finish_s: float = 0.0

    @property
    def enqueue_total(self) -> int:
        return sum(self.enqueues.values())

    @property
    def offloaded_s(self) -> float:
        """Sync wall the async executor kept off the caller's thread."""
        return max(0.0, self.overlap_s - self.sync_s)


class ProgramFuture:
    """One in-flight asynchronous program pass.

    Created by :meth:`TransferProgram.to_device_async` AFTER every region's
    pack+enqueue ran on the caller's thread; the single
    ``jax.block_until_ready`` over all regions' in-flight copies runs on a
    background thread (``overlap_s``), so the caller's compute overlaps the
    DMA.  :meth:`result` materializes the pass: it waits the barrier (the
    residual wait is ``sync_s`` — zero when compute fully covered the DMA),
    runs every region's ``finish()`` bookkeeping (``finish_s``) and returns
    the staged device tree.  Ledger deltas and retained-state updates are
    booked at finish, exactly as in the blocking executor, so the one-sync
    and per-device complement invariants hold bit-for-bit.

    Lifecycle: a program keeps at most ONE un-materialized future (the
    bounded pipeline of DESIGN.md §10.2) — beginning any new pass first
    materializes the in-flight one, which is what makes a later
    ``pack_host`` rotation always find the fences its spare buffer needs.
    ``result()`` is idempotent and thread-safe; the staged tree is memoized.
    """

    def __init__(self, program: "TransferProgram", leaves: List[Any],
                 pending: List[Any], finishes: List[Tuple["Region", Any]],
                 enqueues: Dict[str, int]):
        self._program = program
        self._leaves = leaves
        self._pending = pending
        self._finishes = finishes
        self._enqueues = enqueues
        self._synced = threading.Event()
        self._overlap_s = 0.0
        self._error: Optional[BaseException] = None
        self._lock = threading.Lock()
        self._materialized = False
        self._result: Any = None

        def _sync():
            t0 = time.perf_counter()
            try:
                san = _sanitizer._ACTIVE
                if san is not None:
                    san.on_sync("ProgramFuture")
                jax.block_until_ready(self._pending)
            except BaseException as e:  # surfaced at result()
                self._error = e
            finally:
                self._overlap_s = time.perf_counter() - t0
                self._synced.set()

        self._thread = threading.Thread(
            target=_sync, name="transfer-program-sync", daemon=True)
        self._thread.start()

    def done(self) -> bool:
        """True once the background barrier has completed (the pass is not
        yet materialized — ``result()`` still runs the finish stage)."""
        return self._synced.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the background barrier completes, at most ``timeout``
        seconds (forever if ``None``).  Returns ``True`` when the barrier is
        done, ``False`` on expiry — never raises, never materializes; the
        cheap watchdog probe :meth:`result`'s bounded wait builds on."""
        return self._synced.wait(timeout)

    def result(self, timeout: Optional[float] = None) -> Any:
        """Materialize the pass: residual barrier wait, per-region finish
        bookkeeping, and the staged device tree (memoized).

        With ``timeout`` (seconds), the residual barrier wait is bounded:
        on expiry a typed :class:`TransferTimeout` is raised and the pass
        stays un-materialized (no finish bookkeeping ran, ledgers are
        untouched), so a later ``result()`` — with or without a timeout —
        retries the wait instead of finding corrupted state.  PR 6's async
        executor had no watchdog; a hung background barrier blocked the
        caller forever.  Note the memoized fast path never times out: once
        any call materialized the pass, every later call returns the tree."""
        with self._lock:
            if self._materialized:
                return self._result
            t0 = time.perf_counter()
            if not self._synced.wait(timeout):
                waited = time.perf_counter() - t0
                raise TransferTimeout(
                    waited, detail="pass not materialized; result() may be "
                    "retried once the barrier completes")
            sync_s = time.perf_counter() - t0
            if self._error is not None:
                raise self._error
            t1 = time.perf_counter()
            out = self._program._finish(self._leaves, self._finishes)
            finish_s = time.perf_counter() - t1
            self._program.last_stats = ProgramStats(
                self._enqueues, 1, sync_s, self._overlap_s, finish_s)
            if _sanitizer._ACTIVE is not None:
                _sanitizer._ACTIVE.on_pass_stats(self._program.last_stats)
            self._result = out
            self._materialized = True
            if self._program._inflight is self:
                self._program._inflight = None
            # drop the staging references; the memoized tree is what lives
            self._leaves = self._pending = self._finishes = None
            return out


class TransferProgram:
    """A policy compiled against one treedef: per-region scheme executors
    over a shared session, executed as ONE transfer pass.

    ``to_device`` stages every region through its executor's ``begin_pass``
    (enqueue-only), issues a single ``jax.block_until_ready`` over all
    in-flight copies, then finishes each region's bookkeeping — so a
    program pass has exactly one sync no matter how many regions/buckets
    it ships.  Ledgers stay per region (``ledgers``/``region_ledger``);
    :meth:`merged_ledger` sums them, and the delta invariant
    ``h2d_bytes_by_device[d] + skipped_bytes_by_device[d] == full bytes[d]``
    survives the merge because each region's accounting is per-device
    exact.
    """

    def __init__(self, session: Any, policy: TransferPolicy, treedef: Any,
                 regions: "collections.OrderedDict[str, Region]"):
        from .schemes import transfer_scheme

        self.session = session
        self.policy = policy
        self.treedef = treedef
        self.regions = regions
        # one thin executor per region over the shared session; delta state
        # stays PRIVATE to this program (a fresh program's first pass is
        # always a full cold transfer, like a fresh executor's), but the
        # session still tracks it so session.clear() releases it.
        self._schemes = collections.OrderedDict()
        for key, region in regions.items():
            try:
                self._schemes[key] = transfer_scheme(region.spec, session)
            except UnsupportedPolicyError:
                raise
            except UnsupportedSpecError as e:
                # name the rule: a caller recovering from a stale mesh
                # (policy.reshard) needs to know WHICH rule cannot execute
                raise UnsupportedPolicyError(
                    f"rule {region.rule} cannot execute on this host: {e}"
                ) from e
        self.last_stats: Optional[ProgramStats] = None
        # the bounded pipeline: at most one un-materialized async pass;
        # beginning any new pass (or touching program state) drains it
        self._inflight: Optional[ProgramFuture] = None

    # -- views ---------------------------------------------------------------
    def scheme(self, key: str):
        return self._schemes[key]

    @property
    def ledgers(self) -> Dict[str, Any]:
        """Region-keyed ledgers (pattern -> TransferLedger)."""
        return {k: s.ledger for k, s in self._schemes.items()}

    def region_ledger(self, key: str):
        return self._schemes[key].ledger

    def merged_ledger(self):
        """One ledger summing every region's (plus this program's barrier
        attribution: caller sync, background overlap, finish bookkeeping) —
        the whole-pass data-motion picture."""
        from .schemes import TransferLedger

        out = TransferLedger().merge(*[s.ledger
                                       for s in self._schemes.values()])
        if self.last_stats is not None:
            out.record_wall(0.0, self.last_stats.sync_s)
            out.record_overlap(self.last_stats.overlap_s)
            out.record_finish(self.last_stats.finish_s)
        return out

    def region_of(self, path: Union[str, TreePath]) -> str:
        return self.policy.match(path).pattern

    def reset_ledgers(self) -> None:
        self.drain()
        for s in self._schemes.values():
            s.ledger.reset()

    # -- execution -----------------------------------------------------------
    def _flatten(self, tree: Any) -> List[Any]:
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if treedef != self.treedef:
            raise ValueError(
                f"tree does not match the compiled treedef: got {treedef}, "
                f"compiled for {self.treedef}")
        return leaves

    def drain(self) -> Optional[Any]:
        """Materialize the in-flight async pass, if any (returns its tree).

        Every entry point that stages or mutates program state calls this
        first: the depth-1 pipeline guarantees a pass's finish bookkeeping —
        including the fences its DMA sources register on their staging
        buffers — has run before any later pack can rotate onto them
        (write-after-enqueue safety, DESIGN.md §10.2)."""
        fut, self._inflight = self._inflight, None
        return fut.result() if fut is not None else None

    def _begin(self, tree: Any) -> Tuple[List[Any], List[Any],
                                         List[Tuple[Region, Any]],
                                         Dict[str, int]]:
        """The begin stage of one pass: every region packs + enqueues (no
        sync) in declaration order — region N+1's pack overlaps region N's
        already-in-flight DMA."""
        self.drain()
        leaves = self._flatten(tree)
        pending_all: List[Any] = []
        finishes: List[Tuple[Region, Any]] = []
        enqueues: Dict[str, int] = {}
        # the enqueue half: the sanitizer (when active) flags any blocking
        # barrier issued inside it (DC304 — the one-sync-per-pass contract)
        with jax.profiler.TraceAnnotation("TransferProgram.begin"), \
                _sanitizer.enqueue_half():
            for key, region in self.regions.items():
                sub = [leaves[i] for i in region.indices]
                pending, finish = self._schemes[key].begin_pass(sub)
                enqueues[key] = len(pending)
                pending_all.extend(pending)
                finishes.append((region, finish))
        return leaves, pending_all, finishes, enqueues

    def _finish(self, leaves: List[Any],
                finishes: List[Tuple[Region, Any]]) -> Any:
        """The finish stage: per-region bookkeeping (ledgers, retained
        buckets, staging fences) + tree assembly, after the barrier."""
        out = list(leaves)
        with jax.profiler.TraceAnnotation("TransferProgram.finish"):
            for region, finish in finishes:
                for i, leaf in zip(region.indices,
                                   jax.tree_util.tree_leaves(
                                       finish(), is_leaf=_is_opaque_leaf)):
                    out[i] = leaf
            return jax.tree_util.tree_unflatten(self.treedef, out)

    def to_device(self, tree: Any) -> Any:
        """One blocking program pass: enqueue all regions' buckets, ONE
        sync, finish.

        Each region moves its leaves under its own spec (delta regions ship
        only dirty buckets/shards; uvm regions wrap lazily and fault later,
        contributing zero enqueues here)."""
        with jax.profiler.TraceAnnotation("TransferProgram.to_device"):
            leaves, pending_all, finishes, enqueues = self._begin(tree)
            t0 = time.perf_counter()
            if _sanitizer._ACTIVE is not None:
                _sanitizer._ACTIVE.on_sync("TransferProgram.to_device")
            with jax.profiler.TraceAnnotation("TransferProgram.barrier"):
                jax.block_until_ready(pending_all)
            t1 = time.perf_counter()
            out = self._finish(leaves, finishes)
            t2 = time.perf_counter()
            self.last_stats = ProgramStats(enqueues, 1, t1 - t0,
                                           finish_s=t2 - t1)
            if _sanitizer._ACTIVE is not None:
                _sanitizer._ACTIVE.on_pass_stats(self.last_stats)
            return out

    def to_device_async(self, tree: Any) -> ProgramFuture:
        """The pipelined pass: pack + enqueue every region NOW (on the
        caller's thread, overlapping any prior in-flight DMA), move the
        single sync to a background thread, and return a
        :class:`ProgramFuture` whose ``result()`` materializes the tree.

        Identical data motion and ledger accounting to :meth:`to_device` —
        verified pass-for-pass by the differential harness — but the
        caller's compute between ``to_device_async`` and ``result()``
        overlaps the DMA: the barrier the blocking executor charges to
        ``sync_s`` runs as ``overlap_s`` off the critical path."""
        leaves, pending_all, finishes, enqueues = self._begin(tree)
        fut = ProgramFuture(self, leaves, pending_all, finishes, enqueues)
        self._inflight = fut
        return fut

    def from_device(self, device_tree: Any, host_tree: Any) -> Any:
        """D2H per region under each region's spec (demarshal / selective
        fetch / demand fetch)."""
        self.drain()
        dev_leaves = self._flatten(device_tree)
        host_leaves = self._flatten(host_tree)
        out = list(host_leaves)
        for key, region in self.regions.items():
            sub_dev = [dev_leaves[i] for i in region.indices]
            sub_host = [host_leaves[i] for i in region.indices]
            back = self._schemes[key].from_device(sub_dev, sub_host)
            for i, leaf in zip(region.indices, back):
                out[i] = leaf
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def mark_dirty(self, tree: Any, *paths: Union[str, TreePath]) -> None:
        """Delta API for in-place host mutators: flag the buckets under
        ``paths`` (all delta regions' buckets if none given) in every delta
        region holding leaves below them — an interior path's leaves may
        span several regions.  Drains any in-flight pass first: a mutation
        racing an enqueued-but-unsynced copy must fence, not corrupt."""
        self.drain()
        leaves = self._flatten(tree)
        roots = [str(TreePath.parse(p)) for p in paths]
        for key, region in self.regions.items():
            scheme = self._schemes[key]
            if not getattr(scheme, "delta", False):
                continue
            sub = [leaves[i] for i in region.indices]
            if not roots:
                scheme.mark_dirty(sub)
                continue
            local = [f"[{j}]" for j, gp in enumerate(region.paths)
                     if any(gp == r or gp.startswith(r + ".")
                            or gp.startswith(r + "[") for r in roots)]
            if local:
                scheme.mark_dirty(sub, *local)

    # -- lifecycle -----------------------------------------------------------
    def clear(self) -> None:
        """Release everything this program retains on device: per-region
        delta state (retained buckets + memoized unpacks), entry references
        (staging buffers + their fences), and the region ledgers' counters.
        The program stays usable — the next pass is cold."""
        self.drain()
        for scheme in self._schemes.values():
            state = getattr(scheme, "_delta_state", None)
            if state is not None:
                state.clear()
            if hasattr(scheme, "_entry"):
                scheme._entry = None
                scheme.layout = None
            scheme.ledger.reset()
        self.last_stats = None


def _is_opaque_leaf(x: Any) -> bool:
    """Treat scheme-produced wrapper leaves (UVM LazyLeaf) as leaves when
    re-flattening a region's finished output."""
    from .schemes import LazyLeaf

    return isinstance(x, LazyLeaf)


def compile_program(tree: Any, policy: Union[str, TransferPolicy],
                    session: Any = None) -> TransferProgram:
    """Compile ``policy`` against ``tree``'s treedef (the functional door;
    ``TransferSession.compile`` is the session method).  Warms the session's
    layout/entry caches for every marshalling region so repeat passes are
    pure data motion."""
    from . import engine as engine_lib

    session = session if session is not None else engine_lib.get_session()
    policy = TransferPolicy.parse(policy)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    regions = partition_tree(tree, policy)
    program = TransferProgram(session, policy, treedef, regions)
    for key, region in regions.items():
        if region.spec.kind == "marshal":
            sub = [leaves[i] for i in region.indices]
            session.get_entry(sub, region.spec.align_elems,
                              sharding=program._schemes[key].sharding)
    return program
