"""Deprecation shims: the pre-spec surface must WARN and stay behaviorally
identical (ISSUE 4 satellite).

Every legacy entry point — ``make_scheme``, the ``SCHEMES`` registry, the
old keyword constructors, ``Scenario.scheme_names``/``make_scheme`` —
emits DeprecationWarning; the schemes they build are proven equivalent to
the spec-built ones by LEDGER EQUALITY (``TransferLedger.as_dict()``) on
the dense paper preset, not just by name.

This file is the one EXCLUDED from the CI ``-W error::DeprecationWarning``
leg — everywhere else, in-tree code must be fully migrated off the old
constructors.
"""
import jax
import numpy as np
import pytest

from repro import scenarios as S
from repro.core import (MarshalScheme, SCHEMES, TransferSpec, clear_cache,
                        make_scheme, transfer_scheme)


@pytest.fixture(autouse=True)
def fresh_cache():
    clear_cache()
    yield
    clear_cache()


def _dense():
    return next(sc for sc in S.iter_scenarios("smoke")
                if sc.family == "dense")


@pytest.mark.parametrize("name", ["uvm", "marshal", "marshal_delta",
                                  "pointerchain"])
def test_make_scheme_warns_and_matches_spec_ledger(name):
    """The shim warns, and on the dense preset its scheme's full
    Algorithm-2 ledger equals the spec-built executor's, field for field
    (bytes, DMA batches, per-device maps — everything but timings)."""
    sc = _dense()
    tree = sc.build()
    with pytest.warns(DeprecationWarning, match="deprecated"):
        old = make_scheme(name)
    new = transfer_scheme(name)          # every registry name parses
    assert old.name == new.name
    assert old.spec == new.spec
    m_old = S.run_scenario(sc, scheme=old, tree=tree)
    # the pack counts depend on what the session's staging already holds:
    # both schemes start from an empty session
    clear_cache()
    m_new = S.run_scenario(sc, scheme=new, tree=tree)
    assert m_old.ok and m_new.ok and m_old.motion_ok and m_new.motion_ok
    drop_timings = lambda d: {k: v for k, v in d.items()
                              if not k.endswith("_s")}
    assert drop_timings(old.ledger.as_dict()) \
        == drop_timings(new.ledger.as_dict())


def test_schemes_registry_warns_and_builds_equivalent():
    with pytest.warns(DeprecationWarning, match="deprecated"):
        s = SCHEMES["marshal_delta"]()
    assert isinstance(s, MarshalScheme)
    assert s.spec == TransferSpec.parse("marshal+delta")


def test_legacy_positional_constructors_warn():
    """Pre-redesign POSITIONAL call sites (device, align_elems/sharding)
    must hit the shim too, not bind into the new session parameter."""
    with pytest.warns(DeprecationWarning, match="deprecated"):
        s = MarshalScheme(None, 64)          # old (device, align_elems)
    assert s.spec == TransferSpec.parse("marshal+align64")
    with pytest.warns(DeprecationWarning, match="deprecated"):
        s = MarshalScheme(jax.devices()[0], 8)
    assert s.spec == TransferSpec.parse("marshal+align8@dev0")


def test_legacy_keyword_constructors_warn():
    with pytest.warns(DeprecationWarning, match="deprecated"):
        s = MarshalScheme(delta=True)
    assert s.spec == TransferSpec.parse("marshal+delta")
    with pytest.warns(DeprecationWarning, match="deprecated"):
        s = MarshalScheme(align_elems=64)
    assert s.spec == TransferSpec.parse("marshal+align64")
    with pytest.warns(DeprecationWarning, match="deprecated"):
        s = MarshalScheme(device=jax.devices()[0])
    assert s.spec.device == 0


def test_legacy_sharding_kwarg_builds_sharded_spec():
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    sharding = NamedSharding(mesh, PartitionSpec("data"))
    with pytest.warns(DeprecationWarning, match="deprecated"):
        s = MarshalScheme(sharding=sharding)
    assert s.sharding is sharding
    assert str(s.spec) == f"marshal@dp{jax.device_count()}"


def test_scenario_scheme_names_and_make_scheme_warn():
    sc = _dense()
    with pytest.warns(DeprecationWarning, match="deprecated"):
        names = sc.scheme_names()
    assert names == tuple(s.name for s in sc.specs())
    with pytest.warns(DeprecationWarning, match="deprecated"):
        old = sc.make_scheme("marshal")
    assert old.spec == sc.scheme_for("marshal").spec


def test_unknown_scheme_name_still_raises_keyerror():
    with pytest.raises(KeyError):
        make_scheme("bogus")


def test_spec_built_schemes_do_not_warn(recwarn):
    """The migrated surface is warning-free — what the CI
    -W error::DeprecationWarning leg enforces tree-wide."""
    sc = _dense()
    for spec in sc.specs():
        S.run_scenario(sc, spec)
    assert not [w for w in recwarn.list
                if issubclass(w.category, DeprecationWarning)]
