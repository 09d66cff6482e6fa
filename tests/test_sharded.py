"""Per-device sharded arenas: placement, per-device ledger equality, and
the mesh-aware differential against ``full_deepcopy(sharding=...)``.

Runs at whatever host device count the process was started with (the CI
multi-device job forces 8 via XLA_FLAGS); every assertion is written
against ``jax.device_count()``, so the same tests exercise the 1-device
degenerate case locally and the real 8-way split in CI.
"""
import copy

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import (TransferSpec, clear_cache, declare, full_deepcopy,
                        plan, resolve_shards, shard_ranges, transfer_scheme)
from repro.jaxenv import make_mesh
from repro.scenarios import (derive_motion, iter_scenarios, motion_matches,
                             run_scenario)

K = jax.device_count()


@pytest.fixture(autouse=True)
def fresh_cache():
    clear_cache()
    yield
    clear_cache()


@pytest.fixture()
def sharding():
    mesh = make_mesh((K,), ("data",))
    return NamedSharding(mesh, P("data"))


@pytest.fixture()
def tree():
    rng = np.random.default_rng(0)
    return {"w": rng.standard_normal(8 * K).astype(np.float32),
            "v": rng.standard_normal(24 * K).astype(np.float32),
            "ids": np.arange(4 * K, dtype=np.int32)}


# ------------------------------------------------------------ marshal sharded

def test_sharded_marshal_roundtrip_matches_deepcopy(sharding, tree):
    ref = copy.deepcopy(tree)
    s = transfer_scheme(TransferSpec("marshal", sharding=sharding))
    dev = s.to_device(tree)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(dev[k]), ref[k])
    back = s.from_device(dev, tree)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]), ref[k])


def test_sharded_marshal_per_device_ledger_exact(sharding, tree):
    s = transfer_scheme(TransferSpec("marshal", sharding=sharding))
    s.to_device(tree)
    layout = s.layout
    total = sum(layout.bucket_bytes().values())
    n_buckets = len(layout.bucket_sizes)
    assert s.ledger.h2d_bytes == total
    assert s.ledger.h2d_calls == n_buckets * K
    per_dev = s.ledger.per_device()
    assert len(per_dev) == K
    assert set(per_dev.values()) == {(total // K, n_buckets)}


def test_sharded_bucket_placement(sharding, tree):
    """Each device holds exactly its contiguous sub-range of every bucket —
    the per-device arena, not a replicated copy."""
    s = transfer_scheme(TransferSpec("marshal", sharding=sharding))
    s.to_device(tree)
    entry = s._entry
    bufs = s._put_sharded(entry.staging)
    for b, arr in bufs.items():
        n = entry.layout.bucket_sizes[b]
        assert len(arr.addressable_shards) == K
        for shard in arr.addressable_shards:
            assert shard.data.shape == (n // K,)
        np.testing.assert_array_equal(np.asarray(arr), entry.staging[b])


def test_sharded_matches_full_deepcopy_differential(sharding, tree):
    """Mesh-aware differential (ROADMAP item): the sharded arena transfer
    and ``full_deepcopy(sharding=...)`` must agree leaf-for-leaf."""
    ref = full_deepcopy(copy.deepcopy(tree), sharding=sharding)
    s = transfer_scheme(TransferSpec("marshal", sharding=sharding))
    dev = s.to_device(tree)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(dev[k]), np.asarray(ref[k]))


def test_sharded_and_unsharded_entries_are_distinct_cache_points(tree, sharding):
    a = transfer_scheme("marshal")
    b = transfer_scheme(TransferSpec("marshal", sharding=sharding))
    a.to_device(tree)
    b.to_device(tree)
    if K > 1:
        assert a._entry is not b._entry
        assert b.layout.bucket_sizes["float32"] % K == 0
    else:
        assert a._entry is b._entry     # k=1 pads nothing: same point


# ------------------------------------------------------- pointerchain sharded

def test_sharded_pointerchain_moves_declared_chains_per_device(sharding, tree):
    s = transfer_scheme(TransferSpec("pointerchain", sharding=sharding))
    dev = s.to_device(tree, paths=["w", "v"])
    np.testing.assert_array_equal(np.asarray(dev["w"]), tree["w"])
    assert dev["ids"] is tree["ids"]        # undeclared: never left the host
    nbytes = tree["w"].nbytes + tree["v"].nbytes
    assert s.ledger.h2d_bytes == nbytes
    assert s.ledger.h2d_calls == 2 * K
    assert set(s.ledger.per_device().values()) == {(nbytes // K, 2)}


# ------------------------------------------------- per-shard chain resolution

def test_resolve_shards_partitions_each_chain():
    layout = plan({"a": np.zeros(6 * K, np.float32),
                   "b": np.zeros(2 * K, np.float32)}, shard_multiple=K)
    ranges = shard_ranges(layout)
    assert all(len(r) == K for r in ranges.values())
    for ref in declare({"a": np.zeros(6 * K, np.float32),
                        "b": np.zeros(2 * K, np.float32)}, "a", "b"):
        slices = resolve_shards(ref, layout)
        # the slices tile the slot exactly, in shard order
        slot = layout.slots[ref.flat_index]
        assert sum(s.size for s in slices) == slot.size
        assert slices[0].lo == slot.offset
        assert slices[-1].hi == slot.offset + slot.size
        for x, y in zip(slices, slices[1:]):
            assert x.hi == y.lo and x.shard < y.shard
        # local offsets point inside each shard's own sub-buffer
        for s in slices:
            lo, hi = ranges[s.bucket][s.shard]
            assert lo + s.local_lo == s.lo and s.hi <= hi


def test_shard_ranges_requires_divisibility():
    layout = plan({"a": np.zeros(7, np.float32)})   # 7 elements, no padding
    if K > 1:
        with pytest.raises(ValueError):
            shard_ranges(layout, K)
    padded = plan({"a": np.zeros(7, np.float32)}, shard_multiple=K)
    assert padded.bucket_sizes["float32"] % K == 0


# ------------------------------------------------------------ scenario family

def test_sharded_scenario_closed_form_matches_structural_and_ledger():
    sc = next(s for s in iter_scenarios("smoke") if s.family == "sharded")
    assert sc.num_shards == K
    tree = sc.build()
    sc.validate(tree)
    for spec in sc.specs():
        closed = sc.expected_motion(spec, tree)
        derived = derive_motion(tree, sc.used_paths, sc.uvm_access, spec,
                                num_shards=K)
        assert closed == derived, (str(spec), closed, derived)
        m = run_scenario(sc, spec, tree=tree)
        assert m.ok and m.motion_ok, (str(spec), m)
        if K > 1:
            assert m.per_device is not None
            assert set(m.per_device.values()) == \
                {(closed.per_device_bytes, closed.per_device_calls)}


def test_sharded_scenarios_include_delta():
    """The spec redesign removed the delta x sharding exclusivity: sharded
    scenarios now run marshal+delta too (its cold pass has marshal's exact
    motion; the steady state is tests/test_sharded_delta.py)."""
    sc = next(s for s in iter_scenarios("smoke") if s.family == "sharded")
    delta_specs = [s for s in sc.specs() if s.delta]
    assert len(delta_specs) == 1 and delta_specs[0].num_shards == K
    s = transfer_scheme(TransferSpec("marshal", delta=True,
                                     sharding=sc.sharding()))
    s.to_device(sc.build())
    total = sum(s.layout.bucket_bytes().values())
    assert s.ledger.h2d_bytes == total
    assert s.ledger.h2d_calls == len(s.layout.bucket_sizes) * K
