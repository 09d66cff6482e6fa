"""The byte compare ``ArenaEntry.pack_host`` runs against staging: word-wise,
in chunks into one reused bool buffer, ended by the first chunk that
differs; bitwise, whatever the dtype or the alignment of the staged slice."""
import tracemalloc

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TransferSession
from repro.core import engine as engine_lib
from repro.core.engine import _bytes_equal

STEP = 16            # words per chunk of the scratch used below
N = 1001             # elements: 2002, 4004 B, neither a multiple of 8
DTYPES = [jnp.bfloat16, np.float32, np.int32]


def _word(a, b):
    """The word width the compare must use for these two arrays."""
    align = a.ctypes.data | b.ctypes.data
    return next(w for w in (8, 4, 2, 1) if align % w == 0)


def _pair(dtype, offset):
    """A staged slice ``offset`` elements into a larger buffer, and a
    contiguous leaf holding the same bytes (random bits, NaNs included)."""
    size = N * np.dtype(dtype).itemsize
    leaf = np.random.default_rng(0).integers(
        0, 256, size, dtype=np.uint8).view(dtype)
    buf = np.zeros(N + 8, dtype)
    staged = buf[offset:offset + N]
    staged.view(np.uint8)[:] = leaf.view(np.uint8)
    return staged, leaf


def _flip(arr, byte):
    arr.view(np.uint8)[byte] ^= 0x40


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: np.dtype(d).name)
@pytest.mark.parametrize("offset", [0, 1, 3], ids=lambda o: f"offset{o}")
@pytest.mark.parametrize("where", ["equal", "first", "last", "tail",
                                   "last_chunk", "middle"])
def test_the_word_compare_agrees_with_a_byte_compare(dtype, offset, where):
    staged, leaf = _pair(dtype, offset)
    n = leaf.nbytes
    w = _word(staged, leaf)
    if offset:
        assert w < 8          # the slice is not 8-aligned
    body = n - n % w
    assert n % 8              # there is a tail past the last whole word
    byte = {"equal": None, "first": 0, "last": n - 1, "tail": n - n % 8 + 1,
            "last_chunk": body - 3 * w, "middle": n // 2}[where]
    if byte is not None:
        _flip(leaf, byte)
    scratch = np.ones(STEP, np.bool_)
    equal, read = _bytes_equal(staged, leaf, scratch)
    # the same answer as comparing every byte
    assert equal == np.array_equal(staged.view(np.uint8), leaf.view(np.uint8))
    assert equal == (byte is None)
    if byte is None:
        assert read == n
    elif byte < body:
        # every chunk up to and including the one the difference lies in
        chunk = byte // w // STEP
        assert read == min((chunk + 1) * STEP * w, body)
    else:
        assert read == n
    if where == "first":
        assert read == STEP * w < n


@pytest.mark.parametrize("dtype", [jnp.bfloat16, np.float32],
                         ids=lambda d: np.dtype(d).name)
def test_nans_compare_by_their_bits_and_signed_zeros_differ(dtype):
    bits = np.uint16 if np.dtype(dtype).itemsize == 2 else np.uint32
    quiet = np.array([0x7FC1 if bits is np.uint16 else 0x7FC00001], bits)
    other = np.array([0x7FC2 if bits is np.uint16 else 0x7FC00002], bits)
    scratch = np.empty(STEP, np.bool_)
    a = np.tile(quiet, 40).view(dtype)
    assert np.isnan(a.astype(np.float32)).all()
    assert _bytes_equal(a, a.copy(), scratch) == (True, a.nbytes)
    b = a.copy()
    b[17] = other.view(dtype)[0]
    assert not _bytes_equal(a, b, scratch)[0]
    pos = np.zeros(40, dtype)
    neg = -pos
    assert (pos == neg).all()
    assert not _bytes_equal(pos, neg, scratch)[0]


def test_empty_leaves_are_equal():
    assert _bytes_equal(np.zeros(0, np.float32), np.zeros(0, np.float32),
                        np.empty(STEP, np.bool_)) == (True, 0)


def _staged(entry, leaf_index=0):
    slot = entry.layout.slots[leaf_index]
    return entry.staging[slot.bucket][slot.offset:slot.offset + slot.size]


def test_a_repeat_pack_of_a_large_leaf_makes_no_leaf_sized_temporary():
    session = TransferSession()
    tree = {"x": np.random.default_rng(1).standard_normal(
        (4096, 4096)).astype(np.float32)}                 # 64 MiB
    entry = session.get_entry(tree)
    entry.pack_host(tree)
    tracemalloc.start()
    try:
        entry.pack_host(tree)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert entry.last_pack.compared_bytes == tree["x"].nbytes
    assert entry.last_pack.staged_bytes == 0
    assert peak < 4 << 20


def test_a_dirty_row_ends_the_compare_and_is_restaged_byte_for_byte():
    session = TransferSession()
    x = np.random.default_rng(2).standard_normal(
        (4096, 4096)).astype(np.float32)
    tree = {"x": x}
    entry = session.get_entry(tree)
    entry.pack_host(tree, trust_identity=True)
    version = entry.versions["float32"]
    x[1000] += 1.0
    entry.mark_dirty()
    entry.pack_host(tree, trust_identity=True)
    rec = entry.last_pack
    assert rec.compared_bytes == x.nbytes
    assert rec.compare_exits == 1
    chunk_bytes = engine_lib.COMPARE_CHUNK * 8
    first = 1000 * x.shape[1] * 4
    read = (first // chunk_bytes + 1) * chunk_bytes
    assert rec.compare_unread_bytes == x.nbytes - read
    assert entry.versions["float32"] == version + 1
    assert rec.staged_bytes == x.nbytes
    np.testing.assert_array_equal(_staged(entry).view(np.uint8),
                                  x.reshape(-1).view(np.uint8))
