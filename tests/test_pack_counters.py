"""What ``ArenaEntry.pack_host`` compares, copies and skips, booked into the
region ledgers of a program pass as exact byte counts, and its fence wait
booked as time the caller was blocked."""
import time

import numpy as np
import pytest

from repro.core import TransferSession, transfer_scheme
from repro.core import engine as engine_lib
from repro.core.engine import PackRecord
from repro.core.schemes import TransferLedger

POLICY = "cache/**=marshal+delta; **=marshal"
PARAMS, CACHE = "**", "cache/**"
# params: w f32 16x8 (512 B), b f16 16 (32 B); cache: k, v f32 4x8x4
# (512 B each), pos i32 4 (16 B)
W, B, K, V, POS = 512, 32, 512, 512, 16
PARAMS_BYTES, CACHE_BYTES = W + B, K + V + POS


def _tree():
    rng = np.random.default_rng(0)
    return {"params": {"w": rng.standard_normal((16, 8)).astype(np.float32),
                       "b": rng.standard_normal(16).astype(np.float16)},
            "cache": {"k": rng.standard_normal((4, 8, 4)).astype(np.float32),
                      "v": rng.standard_normal((4, 8, 4)).astype(np.float32),
                      "pos": np.arange(4, dtype=np.int32)}}


def _counts(ledger):
    return (ledger.compared_bytes, ledger.staged_bytes,
            ledger.identity_skipped_bytes)


def _pass(program, tree):
    program.reset_ledgers()
    out = program.to_device(tree)
    return (_counts(program.region_ledger(PARAMS)),
            _counts(program.region_ledger(CACHE)), out)


def test_pack_counts_of_a_two_region_program_are_exact():
    session = TransferSession()
    tree = _tree()
    program = session.compile(tree, POLICY)

    # cold: nothing was packed before, so nothing is compared or skipped;
    # every leaf is copied into staging once
    params, cache, _ = _pass(program, tree)
    assert params == (0, PARAMS_BYTES, 0)
    assert cache == (0, CACHE_BYTES, 0)

    # unchanged repeat: the marshal region compares every leaf and finds
    # it equal; the delta region skips its leaves by identity
    params, cache, _ = _pass(program, tree)
    assert params == (PARAMS_BYTES, 0, 0)
    assert cache == (0, 0, CACHE_BYTES)

    # one row of k changed in place, flagged: the delta region compares
    # every cache leaf; the f32 bucket rotates to its spare buffer, which
    # has never held k or v, so both are copied (pos's bucket is clean)
    tree["cache"]["k"][1] += 1.0
    program.mark_dirty(tree, "cache")
    params, cache, out = _pass(program, tree)
    assert params == (PARAMS_BYTES, 0, 0)
    assert cache == (CACHE_BYTES, K + V, 0)
    np.testing.assert_array_equal(np.asarray(out["cache"]["k"]),
                                  tree["cache"]["k"])

    # another row of k: the spare buffer now lags by k alone
    tree["cache"]["k"][2] += 1.0
    program.mark_dirty(tree, "cache")
    params, cache, _ = _pass(program, tree)
    assert cache == (CACHE_BYTES, K, 0)

    # merged: the sum of the regions
    merged = program.merged_ledger()
    assert _counts(merged) == (PARAMS_BYTES + CACHE_BYTES, K, 0)

    # a new program on the same session after clear(): the session keeps
    # the entries and their staging, so the marshal region compares and
    # finds nothing to copy, and the delta region skips by identity while
    # it ships the whole region cold
    program.clear()
    assert _counts(program.merged_ledger()) == (0, 0, 0)
    program = session.compile(tree, POLICY)
    params, cache, _ = _pass(program, tree)
    assert params == (PARAMS_BYTES, 0, 0)
    assert cache == (0, 0, CACHE_BYTES)
    assert program.region_ledger(CACHE).h2d_bytes >= CACHE_BYTES


@pytest.mark.parametrize("step", ["cold", "repeat", "dirty", "recompiled"])
def test_compared_skipped_and_new_bytes_cover_every_leaf(step):
    """Per pack: compared + identity-skipped + never-packed-before bytes
    equal the region's non-empty leaf bytes."""
    session = TransferSession()
    tree = _tree()
    program = session.compile(tree, POLICY)
    fresh = step == "cold"
    if not fresh:
        program.to_device(tree)
    if step == "dirty":
        tree["cache"]["v"][0] -= 1.0
        program.mark_dirty(tree, "cache")
    if step == "recompiled":
        program.clear()
        program = session.compile(tree, POLICY)
    program.reset_ledgers()
    program.to_device(tree)
    for key, total in ((PARAMS, PARAMS_BYTES), (CACHE, CACHE_BYTES)):
        led = program.region_ledger(key)
        new = total if fresh else 0
        assert led.compared_bytes + led.identity_skipped_bytes + new == total


def test_the_record_of_one_pack_is_booked_once():
    ledger = TransferLedger()
    ledger.record_pack(PackRecord(compared_bytes=7, staged_bytes=5,
                                  identity_skipped_bytes=3, fence_wait_s=0.25))
    assert _counts(ledger) == (7, 5, 3)
    assert ledger.sync_s == 0.25 and ledger.wall_s == 0.25
    other = TransferLedger()
    other.record_pack(PackRecord(1, 2, 4))
    merged = TransferLedger().merge(ledger, other)
    assert _counts(merged) == (8, 7, 7) and merged.sync_s == 0.25
    ledger.reset()
    assert _counts(ledger) == (0, 0, 0) and ledger.wall_s == 0.0


def _exits(ledger):
    return ledger.compare_exits, ledger.compare_unread_bytes


def test_compare_exits_and_unread_bytes_are_booked_merged_and_reset():
    ledger = TransferLedger()
    ledger.record_pack(PackRecord(compared_bytes=9, compare_unread_bytes=6,
                                  compare_exits=2))
    assert _exits(ledger) == (2, 6) and ledger.compared_bytes == 9
    other = TransferLedger()
    other.record_pack(PackRecord(compared_bytes=4, compare_unread_bytes=1,
                                 compare_exits=1))
    assert _exits(TransferLedger().merge(ledger, other)) == (3, 7)
    ledger.reset()
    assert _exits(ledger) == (0, 0)


def test_a_mismatch_ends_the_compare_and_its_unread_bytes_are_booked(
        monkeypatch):
    # 4 words (32 B) a chunk: k's 512 B are 16 chunks
    monkeypatch.setattr(engine_lib, "COMPARE_CHUNK", 4)
    session = TransferSession()
    tree = _tree()
    program = session.compile(tree, POLICY)
    program.to_device(tree)
    tree["cache"]["k"][1, 0, 0] += 1.0      # byte 128 of k: its fifth chunk
    program.mark_dirty(tree, "cache")
    params, cache, out = _pass(program, tree)
    # what was submitted to the compare is unchanged by the early exit
    assert params == (PARAMS_BYTES, 0, 0)
    assert cache == (CACHE_BYTES, K + V, 0)
    assert _exits(program.region_ledger(PARAMS)) == (0, 0)
    assert _exits(program.region_ledger(CACHE)) == (1, K - 5 * 32)
    # booked once: the ledger holds the one pack's record
    entry = program.scheme(CACHE)._entry
    assert _exits(program.region_ledger(CACHE)) == (
        entry.last_pack.compare_exits, entry.last_pack.compare_unread_bytes)
    assert _exits(program.merged_ledger()) == (1, K - 5 * 32)
    np.testing.assert_array_equal(np.asarray(out["cache"]["k"]),
                                  tree["cache"]["k"])
    # an unchanged repeat, flagged: everything is read, nothing exits
    program.mark_dirty(tree, "cache")
    params, cache, _ = _pass(program, tree)
    assert cache == (CACHE_BYTES, 0, 0)
    assert _exits(program.merged_ledger()) == (0, 0)
    program.clear()
    assert _exits(program.merged_ledger()) == (0, 0)


class _SlowFence:
    """A device value still in flight: waiting on it takes ``s`` seconds."""

    def __init__(self, s):
        self.s = s

    def is_ready(self):
        return False

    def is_deleted(self):
        return False

    def block_until_ready(self):
        time.sleep(self.s)
        return self


def test_a_fence_wait_is_booked_as_sync_time():
    """A pack that rotates onto a buffer with a value in flight waits it;
    the scheme books that wait into ``sync_s`` and ``wall_s`` stays the
    sum of its parts."""
    session = TransferSession()
    scheme = transfer_scheme("marshal+delta", session)
    tree = {"x": np.zeros(64, np.float32)}
    scheme.to_device(tree)                       # cold: fills buffer 1
    entry = scheme._entry
    bucket = next(iter(entry.staging))
    entry.add_fence(bucket, [_SlowFence(0.05)])  # on buffer 1
    tree = {"x": np.ones(64, np.float32)}
    scheme.to_device(tree)                       # rotates to buffer 0
    assert entry.last_pack.fence_wait_s == 0.0
    scheme.ledger.reset()
    tree = {"x": np.full(64, 2.0, np.float32)}
    scheme.to_device(tree)                       # back to buffer 1: waits
    assert entry.last_pack.fence_wait_s >= 0.05
    led = scheme.ledger
    assert led.sync_s >= 0.05
    assert led.wall_s == pytest.approx(led.enqueue_s + led.sync_s + led.finish_s)
    assert _counts(led) == (256, 256, 0)
