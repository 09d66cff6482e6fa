"""Resilient policy-driven serving: continuous batching over a
TransferProgram-backed ServeState.

The ServeState (params + KV/SSM caches + slot table) is a deep nested tree
that must move under a latency budget; it is now wired through the transfer
machinery instead of living wherever ``jax.jit`` happened to put it:

  * :func:`serve_transfer_policy` — the ``mixed_policy`` shape applied to
    serving: params in the 128-aligned (dp-shardable) persistent arena,
    the KV cache as a delta region, slot metadata as pointer chains.  The
    whole state stages through ONE compiled
    :class:`~repro.core.TransferProgram` pass at install/swap time.
  * batched prefill through the arena path: a refill batch's prompts,
    lengths and slot ids pack into one program pass
    (``to_device_async`` + bounded ``result(timeout=)``) instead of
    per-request host scatter, and the per-sequence caches install into the
    slot cache with ONE fused scatter instead of a ``.at[].set`` per key
    per request.  Prefill *compute* stays per-sequence-exact (no padding
    reaches the model), so tokens are bit-identical to the naive path.
  * a request lifecycle (``runtime/admission.py``): bounded admission with
    backpressure (``submit`` -> ACCEPTED/SHED), per-request deadlines with
    typed :class:`~repro.runtime.admission.RequestTimeout`, retry with
    exponential backoff for transient transfer faults, and graceful
    degradation — a stale-mesh policy resharding to what actually exists
    (counted in :class:`~repro.runtime.admission.ServeStats`, never
    silently) instead of killing the server.

Fault points (``runtime/faults.py``): ``serve.prefill_pack``,
``serve.decode_step``, ``serve.slot_refill``, ``serve.policy_swap``.
Under any of them every submitted request terminates in exactly one state
(completed / shed / timed-out / failed-with-typed-error) — enforced
structurally by the lifecycle tracker, not merely asserted in tests.

Slots: fixed batch of B sequences; finished slots are refilled from the
admission queue each tick (per-slot positions are (B,) vectors; the decode
step scatters each slot's KV at its own position).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import engine as engine_lib
from ..core.policy import TransferPolicy, TransferTimeout
from ..core.spec import UnsupportedSpecError
from ..models.registry import ModelApi
from . import faults as faults_lib
from .admission import (ACCEPTED, ACTIVE, COMPLETED, FAILED, QUEUED, SHED,
                        TIMED_OUT, AdmissionQueue, Backoff, LifecycleTracker,
                        RequestTimeout, ServeStats)
from .train import replicate_state

# errors worth retrying: an injected kill or a hung async barrier — NOT
# genuine model/shape errors, which propagate on the first attempt
TRANSIENT_FAULTS = (faults_lib.InjectedFault, TransferTimeout)


def serve_transfer_policy(dp_size: int = 1) -> TransferPolicy:
    """The ServeState placement policy — `mixed_policy` applied to serving:
    params in the 128-aligned (dp-sharded) persistent arena, the KV/SSM
    cache as a delta region (after install only touched buckets re-ship),
    slot metadata (and anything else) as declared pointer chains."""
    return TransferPolicy.parse(
        f"params/**=marshal+align128@dp{int(dp_size)}; "
        "cache/**=marshal+delta; **=pointerchain")


def serve_state(api: ModelApi, params, slots: int,
                max_seq: int) -> Dict[str, Any]:
    """The host-side ServeState tree a server stages: the params, the
    ``slots x max_seq`` KV/SSM cache, and the slot table."""
    return {
        "params": jax.device_get(params),
        "cache": jax.device_get(api.init_cache(slots, max_seq)),
        "slots": {"rid": np.full((slots,), -1, np.int32),
                  "pos": np.zeros((slots,), np.int32)},
    }


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (P,) int32
    max_new_tokens: int = 16
    eos_id: int = -1              # -1: never
    tokens_out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # lifecycle (admission.py): deadline is relative to submit time
    deadline_s: Optional[float] = None
    state: str = QUEUED
    error: Optional[BaseException] = None
    submitted_at: float = 0.0


class Server:
    """Continuous-batching server with admission control and a
    TransferProgram-backed ServeState.

    ``submit`` answers ``ACCEPTED`` or ``SHED`` (bounded queue +
    watermark); ``tick`` runs one scheduler round (expire deadlines,
    refill free slots through the batched arena prefill, one batched
    decode step); ``run`` loops ticks and returns the authoritative
    terminal-state request list from the lifecycle tracker.  ``stats``
    is the degradation ledger; ``swap_policy`` re-stages the live state
    under a new transfer policy without dropping requests."""

    def __init__(self, api: ModelApi, params, *, slots: int, max_seq: int,
                 policy: Optional[Any] = None, session=None,
                 max_queue: int = 1024, shed_watermark: Optional[int] = None,
                 max_retries: int = 3, backoff_base_s: float = 1e-4,
                 transfer_timeout_s: float = 30.0,
                 clock=time.monotonic):
        self.api = api
        self.slots = slots
        self.max_seq = max_seq
        self.session = session if session is not None \
            else engine_lib.get_session()
        self.transfer_timeout_s = transfer_timeout_s
        self._clock = clock
        self.stats = ServeStats()
        self.tracker = LifecycleTracker()
        self._queue = AdmissionQueue(capacity=max_queue,
                                     shed_watermark=shed_watermark)
        self._backoff = Backoff(max_retries=max_retries, base_s=backoff_base_s)
        self.active: List[Optional[Request]] = [None] * slots

        # host-side ServeState mirror: the tree the program compiles
        # against and the snapshot a policy swap re-stages from
        self._host_state = serve_state(api, params, slots, max_seq)

        self._decode = jax.jit(api.decode_step)
        # ONE cached prefill jit (traced per distinct prompt length), not a
        # fresh jax.jit per request like the old per-slot scatter path
        self._prefill = jax.jit(api.prefill)
        self._install_cache = jax.jit(self._install_batch)
        # prompt-pack programs, keyed by (batch, padded length) bucket
        self._pack_programs: Dict[Tuple[int, int], Any] = {}

        self.policy: Optional[TransferPolicy] = None
        self.program = None
        self.params = None
        self.cache = None
        requested = serve_transfer_policy() if policy is None \
            else TransferPolicy.parse(policy)
        self._install_policy(requested)

    # -- admission -----------------------------------------------------------
    @property
    def queue(self) -> List[Request]:
        """Queued (admitted, not yet slotted) requests, in order."""
        return self._queue.snapshot()

    def submit(self, req: Request) -> str:
        """Admit or shed.  Shed requests terminate immediately (state
        ``shed``) — backpressure is a typed answer, not a dropped rid."""
        self.stats.submitted += 1
        req.submitted_at = self._clock()
        self.tracker.submit(req)
        verdict = self._queue.submit(req)
        if verdict == SHED:
            self.tracker.terminate(req, SHED)
            self.stats.shed += 1
        else:
            self.stats.accepted += 1
        self.stats.queue_high_water = self._queue.high_water
        return verdict

    # -- policy install / swap ----------------------------------------------
    def _stage_state(self, policy: TransferPolicy):
        """One compiled program pass moving the whole ServeState, then one
        consistent compute placement (see ``replicate_state``)."""
        faults_lib.trip(faults_lib.SERVE_POLICY_SWAP)
        program = self.session.compile(self._host_state, policy)
        dev = program.to_device(self._host_state)
        dev = replicate_state(dev, policy.num_shards)
        return program, dev

    def _install_policy(self, requested: TransferPolicy) -> None:
        """Stage ServeState under ``requested``, walking the degradation
        ladder on stale-mesh failure: requested -> reshard(live mesh) ->
        replicated.  Every rung below the top is counted and described in
        ``stats`` — the server degrades loudly, it does not die."""
        k = jax.device_count()
        ladder = [requested]
        if requested.num_shards > 1 and requested.num_shards != k:
            ladder.append(requested.reshard(max(1, k)))
        if ladder[-1].num_shards > 1:
            ladder.append(ladder[-1].reshard(1))
        last_err: Optional[BaseException] = None
        for rung, pol in enumerate(ladder):
            try:
                program, dev = self._backoff.call(
                    lambda p=pol: self._stage_state(p),
                    transient=TRANSIENT_FAULTS,
                    on_retry=lambda e, a: self.stats.record_retry(
                        "serve.policy_swap"))
            except UnsupportedSpecError as e:
                last_err = e
                continue
            if rung > 0:
                self.stats.policy_fallbacks += 1
                self.stats.degradations.append(
                    f"{requested} -> {pol} ({last_err})")
            self.policy = pol
            self.program = program
            self.params = dev["params"]
            self.cache = dev["cache"]
            return
        raise last_err  # no rung could stage: not a stale-mesh failure

    def swap_policy(self, policy: Any) -> TransferPolicy:
        """Re-stage the LIVE ServeState under a new transfer policy without
        dropping requests: snapshot device state D2H under the current
        program's per-region specs, then install the new policy (the
        degradation ladder applies — a stale mesh reshards, loudly)."""
        requested = TransferPolicy.parse(policy)
        if self.program is not None:
            dev_tree = {"params": self.params, "cache": self.cache,
                        "slots": self._host_state["slots"]}
            self._host_state = self.program.from_device(dev_tree,
                                                        self._host_state)
        self._install_policy(requested)
        return self.policy

    # -- slot refill (batched arena prefill) ---------------------------------
    def _pack_program(self, tree: Dict[str, np.ndarray]):
        key = (tree["tokens"].shape[0], tree["tokens"].shape[1])
        program = self._pack_programs.get(key)
        if program is None:
            program = self.session.compile(tree, TransferPolicy.of("marshal"))
            self._pack_programs[key] = program
        return program

    def _install_batch(self, cache, batch_cache, slot_ids):
        """ONE fused scatter installing a refill batch's per-sequence
        caches into the slot cache (replaces per-request per-key
        ``.at[].set``)."""
        out = {}
        for key, val in cache.items():
            upd = batch_cache[key]
            if key == "pos":
                out[key] = val.at[slot_ids].set(upd)
            elif val.ndim >= 2 and val.shape[1] == self.slots:
                # (L, B, ...) layout
                out[key] = val.at[:, slot_ids].set(upd)
            else:
                # (B, ...) layout (enc_out)
                out[key] = val.at[slot_ids].set(upd)
        return out

    def _prefill_pack(self, slot_ids: Sequence[int],
                      reqs: Sequence[Request]) -> List[int]:
        """Stage one refill batch through the arena path and prefill it.

        Prompts pad into a power-of-2 length bucket (bounding the number of
        distinct pack programs) and ship — tokens + lengths + slot ids — as
        ONE async program pass with a bounded wait.  Compute then runs per
        sequence at its EXACT length (padding never reaches the model, so
        tokens stay bit-identical to unbatched prefill), and the resulting
        caches install with one fused scatter.  Nothing here mutates server
        state until the final cache swap — an unwound fault retries from a
        clean slate."""
        n = len(reqs)
        cap = 8
        while cap < max(len(r.prompt) for r in reqs):
            cap *= 2
        tokens = np.zeros((n, cap), np.int32)
        for j, req in enumerate(reqs):
            tokens[j, :len(req.prompt)] = req.prompt
        pack = {"tokens": tokens,
                "lens": np.asarray([len(r.prompt) for r in reqs], np.int32),
                "slots": np.asarray(slot_ids, np.int32)}
        program = self._pack_program(pack)
        faults_lib.trip(faults_lib.SERVE_PREFILL_PACK)
        future = program.to_device_async(pack)
        # computed with the staged state, so placed like it (a no-op on
        # one device; replicated over the params mesh under @dp{k})
        dev = replicate_state(future.result(timeout=self.transfer_timeout_s),
                              self.policy.num_shards)

        firsts: List[int] = []
        caches: List[Dict[str, jax.Array]] = []
        for j, req in enumerate(reqs):
            P = len(req.prompt)
            cache1 = self.api.init_cache(1, self.max_seq)
            logits, cache1 = self._prefill(
                self.params, dev["tokens"][j:j + 1, :P], cache1)
            firsts.append(int(np.argmax(np.asarray(logits[0, -1]))))
            caches.append(cache1)
        batch_cache = {}
        for key, val in self.cache.items():
            if key == "pos":
                batch_cache[key] = jnp.concatenate([c["pos"] for c in caches])
            elif val.ndim >= 2 and val.shape[1] == self.slots:
                batch_cache[key] = jnp.concatenate(
                    [c[key] for c in caches], axis=1)
            else:
                batch_cache[key] = jnp.concatenate(
                    [c[key] for c in caches], axis=0)
        self.cache = self._install_cache(self.cache, batch_cache,
                                         dev["slots"])
        return firsts

    def _refill(self, slot_ids: Sequence[int],
                reqs: Sequence[Request]) -> List[int]:
        faults_lib.trip(faults_lib.SERVE_SLOT_REFILL)
        return self._prefill_pack(slot_ids, reqs)

    def _fill_slots(self) -> None:
        free = [i for i in range(self.slots) if self.active[i] is None]
        if not free or not len(self._queue):
            return
        # peek, don't pop: the queue only commits after the transfer does
        batch = self._queue.peek(len(free))
        slot_ids = free[:len(batch)]
        try:
            firsts = self._backoff.call(
                lambda: self._refill(slot_ids, batch),
                transient=TRANSIENT_FAULTS,
                on_retry=lambda e, a: self.stats.record_retry(
                    e.point if isinstance(e, faults_lib.InjectedFault)
                    else "transfer.timeout"))
        except TRANSIENT_FAULTS as e:
            # retries exhausted: the implicated requests fail TYPED and the
            # server keeps serving; nothing was installed, so the slots and
            # the rest of the queue are untouched
            for req in self._queue.pop(len(batch)):
                self.tracker.terminate(req, FAILED, error=e)
                self.stats.failed += 1
            return
        self._queue.pop(len(batch))
        self.stats.prefill_batches += 1
        self.stats.prefill_requests += len(batch)
        for slot, req, first in zip(slot_ids, batch, firsts):
            req.tokens_out.append(first)
            req.state = ACTIVE
            self.active[slot] = req
            self._host_state["slots"]["rid"][slot] = req.rid
            self._host_state["slots"]["pos"][slot] = len(req.prompt)
            self.stats.tokens_generated += 1

    # -- decode --------------------------------------------------------------
    def _finish_active(self, slot: int, state: str,
                       error: Optional[BaseException] = None) -> None:
        req = self.active[slot]
        self.active[slot] = None
        self._host_state["slots"]["rid"][slot] = -1
        self._host_state["slots"]["pos"][slot] = 0
        self.tracker.terminate(req, state, error=error)

    def _expire(self, now: float) -> None:
        """Deadline pass, queued AND active: expiry is a typed terminal
        state, never a silent drop."""
        for req in self._queue.expire(now):
            self.tracker.terminate(
                req, TIMED_OUT,
                error=RequestTimeout(req.rid, req.deadline_s, "queued"))
            self.stats.timed_out += 1
        for i, req in enumerate(self.active):
            if (req is not None and req.deadline_s is not None
                    and now > req.submitted_at + req.deadline_s):
                self._finish_active(
                    i, TIMED_OUT,
                    error=RequestTimeout(req.rid, req.deadline_s, "active"))
                self.stats.timed_out += 1

    def step(self) -> None:
        """One batched decode step over all active slots."""
        tokens = np.zeros((self.slots, 1), np.int32)
        for i, req in enumerate(self.active):
            if req is not None and req.tokens_out:
                tokens[i, 0] = req.tokens_out[-1]

        def dispatch():
            faults_lib.trip(faults_lib.SERVE_DECODE_STEP)
            logits, cache = self._decode(self.params, jnp.asarray(tokens),
                                         self.cache)
            return np.asarray(jnp.argmax(logits[:, -1], axis=-1)), cache

        try:
            # no state is assigned until dispatch succeeds, so a retried
            # decode recomputes from the same cache — idempotent
            next_tokens, self.cache = self._backoff.call(
                dispatch, transient=TRANSIENT_FAULTS,
                on_retry=lambda e, a: self.stats.record_retry(
                    "serve.decode_step"))
        except TRANSIENT_FAULTS as e:
            for i, req in enumerate(self.active):
                if req is not None:
                    self._finish_active(i, FAILED, error=e)
                    self.stats.failed += 1
            return
        self.stats.decode_steps += 1
        pos = np.asarray(self.cache["pos"])
        for i, req in enumerate(self.active):
            if req is None:
                continue
            tok = int(next_tokens[i])
            req.tokens_out.append(tok)
            self.stats.tokens_generated += 1
            if (tok == req.eos_id
                    or len(req.tokens_out) >= req.max_new_tokens
                    or int(pos[i]) >= self.max_seq - 1):
                self._finish_active(i, COMPLETED)
                self.stats.completed += 1

    # -- main loop -----------------------------------------------------------
    def tick(self) -> bool:
        """One scheduler round: expire lapsed deadlines, refill free slots,
        one batched decode step.  Returns True while work remains."""
        self._expire(self._clock())
        self._fill_slots()
        if not any(r is not None for r in self.active):
            return len(self._queue) > 0
        self.step()
        return True

    def run(self, max_steps: int = 1000) -> List[Request]:
        """Drive ticks until drained (or ``max_steps``).  Returns the
        authoritative terminal-state list from the lifecycle tracker —
        including requests submitted after ``run`` started, in termination
        order, with no quadratic membership scans."""
        for _ in range(max_steps):
            if not self.tick():
                break
        return self.tracker.finished()
