"""Transfer mixes: the ServeState staged through the deep-copy runtime.

Traffic parameters (``traffic/<name>.json``):

* ``op``: ``stage`` -- each op stages the whole host ServeState into a
  freshly compiled ``TransferProgram`` on one warm ``TransferSession``,
  after releasing the previous op's program and device tree (a model load,
  a policy swap or a restore);
  ``resume`` -- one program; each op writes one of ``snapshots`` seeded
  session snapshots into the next slot of the host ServeState in place,
  flags it with ``program.mark_dirty`` and passes ``to_device`` (a saved
  session resumed into a slot).
* ``slots``, ``max_seq``: the ServeState's cache; ``dp``: the policy is
  ``serve_transfer_policy(dp)``.

A pass ends when every leaf of its device tree is ready.  Each pass's
device tree is reduced on the device to per-leaf digests (per slot for
cache and slot-table leaves); after the window every pass's digests are
compared with those of the seeded arrays the host tree was copied from,
which never went through the engine, and the last pass's tree is read back
leaf by leaf and compared with the host tree byte for byte.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np


def _digest_tree(state):
    from bench.digest import device_digest, device_digest_rows
    import jax

    return {"params": jax.tree_util.tree_map(device_digest, state["params"]),
            "cache": {k: device_digest_rows(v, 1 if v.ndim >= 2 else 0)
                      for k, v in state["cache"].items()},
            "slots": {k: device_digest_rows(v, 0)
                      for k, v in state["slots"].items()}}


def _snapshot_digest(snap):
    from bench.digest import device_digest

    return {part: {k: device_digest(v) for k, v in rows.items()}
            for part, rows in snap.items()}


def _set_row(arr: np.ndarray, slot: int, part: str, value) -> None:
    if part == "cache" and arr.ndim >= 2:
        arr[:, slot] = value
    else:
        arr[slot] = value


def control_to_device(host):
    """The control: a plain per-leaf copy in the precision below the
    configuration's (bfloat16 through float8 e4m3, float32 through
    bfloat16), cast back on the device."""
    import jax
    import jax.numpy as jnp

    lower = {np.dtype(jnp.bfloat16): jnp.float8_e4m3fn,
             np.dtype(np.float32): jnp.bfloat16}

    def put(a):
        a = np.asarray(a)
        to = lower.get(a.dtype)
        if to is None:
            return jax.device_put(a)
        return jax.device_put(a.astype(to)).astype(a.dtype)

    return jax.tree_util.tree_map(put, host)


class TransferRun:
    def __init__(self, ctx):
        import jax
        from bench import weights
        from bench.model import check_params, model_api
        from repro.core.engine import TransferSession
        from repro.runtime import serve_transfer_policy

        cfg, tr = ctx.cfg, ctx.traffic
        self.ctx = ctx
        ctx.phase("process and JAX start")
        self.op = tr["op"]
        self.slots, max_seq = tr["slots"], tr["max_seq"]
        api = model_api(cfg)
        params = weights.make_params(ctx.family, cfg, ctx.seed)
        check_params(api, params)
        cache_abs = api.init_cache(self.slots, max_seq, abstract_only=True)
        cache = weights.make_random(
            {k: (v.shape, v.dtype) for k, v in cache_abs.items()}, ctx.seed, 1)
        table = weights.make_random(
            {"rid": ((self.slots,), np.int32), "pos": ((self.slots,), np.int32)},
            ctx.seed, 2)
        dev = {"params": params, "cache": cache, "slots": table}
        jax.block_until_ready(dev)
        ctx.phase("weights and state on the device")
        self._digest = jax.jit(_digest_tree)
        self.expected0 = self._digest(dev)
        self.snapshots: List[Dict[str, Dict[str, np.ndarray]]] = []
        self.snap_digests: List[Any] = []
        if self.op == "resume":
            rows = {"cache": {k: (v.shape[:1] + v.shape[2:] if v.ndim >= 2
                                  else (), v.dtype)
                              for k, v in cache_abs.items()},
                    "slots": {"rid": ((), np.int32), "pos": ((), np.int32)}}
            snap_digest = jax.jit(_snapshot_digest)
            for j in range(tr["snapshots"]):
                snap = {part: weights.make_random(shp, ctx.seed, 10 + 2 * j + n)
                        for n, (part, shp) in enumerate(sorted(rows.items()))}
                self.snap_digests.append(snap_digest(snap))
                self.snapshots.append(jax.device_get(snap))
        jax.block_until_ready((self.expected0, self.snap_digests))
        ctx.phase("digests of the seeded arrays")
        # the host ServeState, writable (resume writes slots in place)
        self.host = jax.tree_util.tree_map(np.array, jax.device_get(dev))
        del dev, params, cache, table
        ctx.phase("host copy")

        self.session = TransferSession()
        self.policy = serve_transfer_policy(tr["dp"])
        self.program = None
        self.tree = None
        self.op_index = 0
        self.slot_holds: List[int] = [-1] * self.slots   # snapshot per slot
        self.passes: List[dict] = []
        self.digests: List[Any] = []
        self.expected_slots: List[List[int]] = []
        if self.op == "resume":
            self.program = self.session.compile(self.host, self.policy)
            jax.block_until_ready(self.program.to_device(self.host))
        # warm-up: one op of the window's kind, not recorded
        self._op(record=False)
        ctx.phase("staged and warmed")
        self.link_bytes_per_s = self._link_probe() if ctx.trace else None

    # -- the window --------------------------------------------------------
    def _to_device(self, host):
        if self.ctx.control:
            return control_to_device(host)
        return self.program.to_device(host)

    def _op(self, span=None, record=True) -> None:
        import jax
        from bench.harness import span as real_span

        span = span or real_span
        t_op = time.perf_counter()
        if self.op == "stage":
            with span("bench.release"):
                if self.program is not None:
                    self.program.clear()
                self.tree = None
            with span("session.compile"):
                self.program = self.session.compile(self.host, self.policy)
        else:
            slot = self.op_index % self.slots
            j = self.op_index % len(self.snapshots)
            for part, rows in self.snapshots[j].items():
                for k, v in rows.items():
                    _set_row(self.host[part][k], slot, part, v)
            self.slot_holds[slot] = j
            with span("program.mark_dirty"):
                self.program.mark_dirty(self.host, "cache")
        self.program.reset_ledgers()
        with span("program.to_device"):
            t0 = time.perf_counter()
            tree = self._to_device(self.host)
            t1 = time.perf_counter()
            jax.block_until_ready(tree)
            t2 = time.perf_counter()
        self.tree = tree
        with span("bench.check_digest"):
            # waited for here: the next op rewrites the host tree in place,
            # and a leaf the CPU backend aliases would change under it
            digest = jax.block_until_ready(self._digest(tree))
        self.op_index += 1
        if not record:
            return
        stats = self.program.last_stats
        ledger = self.program.merged_ledger()
        sync = stats.sync_s if stats and not self.ctx.control else 0.0
        finish = stats.finish_s if stats and not self.ctx.control else 0.0
        self.passes.append({
            "op_s": t2 - t_op, "to_device_s": t1 - t0,
            "host_before_barrier_s": (t1 - t0) - sync - finish,
            "device_put_s": ledger.enqueue_s, "barrier_s": sync,
            "finish_s": finish, "unpack_wait_s": t2 - t1,
            "h2d_bytes": ledger.h2d_bytes, "h2d_calls": ledger.h2d_calls})
        self.digests.append(digest)
        self.expected_slots.append(list(self.slot_holds))

    def window(self, seconds: float, span) -> None:
        t0 = time.perf_counter()
        while True:
            self._op(span)
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_s = time.perf_counter() - t0

    def _link_probe(self) -> float:
        """Host-to-device bytes per second of one plain ``device_put`` of
        a buffer the size of the largest bucket (the faster of two)."""
        import jax

        size = max(n for key in self.program.regions
                   if hasattr(self.program.scheme(key), "layout")
                   and self.program.scheme(key).layout is not None
                   for n in self.program.scheme(key).layout.bucket_bytes().values())
        buf = np.ones(size, np.uint8)
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            jax.block_until_ready(jax.device_put(buf))
            best = min(best, time.perf_counter() - t0)
        return size / best

    # -- results -----------------------------------------------------------
    @property
    def attempted(self) -> int:
        return len(self.passes)

    failed = 0

    def end_to_end(self) -> dict:
        return {"pass_ms": 1e3 * self.window_s / len(self.passes)}

    @property
    def counters(self) -> dict:
        n = len(self.passes)
        total = {k: sum(p[k] for p in self.passes) for k in self.passes[0]}
        return {"passes": n, "window_s": self.window_s,
                "mean": {k: v / n for k, v in total.items()},
                "link_bytes_per_s": self.link_bytes_per_s}

    def check(self) -> dict:
        import jax

        # the last pass's tree, read back leaf by leaf (no engine)
        got = jax.tree_util.tree_leaves(jax.device_get(self.tree))
        want = jax.tree_util.tree_leaves(self.host)
        bad_leaves = sum(
            not (g.dtype == w.dtype and g.shape == w.shape
                 and np.array_equal(np.ascontiguousarray(g).view(np.uint8),
                                    np.ascontiguousarray(w).view(np.uint8)))
            for g, w in zip(got, want))
        self.tree = None
        if self.program is not None:
            self.program.clear()
        self.session.clear()

        observed = jax.device_get(self.digests)
        base = jax.device_get(self.expected0)
        snaps = jax.device_get(self.snap_digests)
        bad_passes = 0
        for obs, holds in zip(observed, self.expected_slots):
            want = jax.tree_util.tree_map(np.array, base)
            for slot, j in enumerate(holds):
                if j < 0:
                    continue
                for part, rows in snaps[j].items():
                    for k, v in rows.items():
                        want[part][k][slot] = v
            same = jax.tree_util.tree_map(np.array_equal, obs, want)
            bad_passes += not all(jax.tree_util.tree_leaves(same))
        n = len(self.passes)
        return {"passes_mismatched": {"value": bad_passes, "limit": 0,
                                      "ok": n > 0 and bad_passes <= 0},
                "leaves_mismatched": {"value": bad_leaves, "limit": 0,
                                      "ok": bad_leaves <= 0}}


def setup(ctx) -> TransferRun:
    return TransferRun(ctx)
