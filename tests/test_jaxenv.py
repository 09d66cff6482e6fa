"""The repo's set-up of the installed JAX: Auto-axis meshes, the sharded
@dp1 schemes they carry, the compilation cache directory, compiled kernels
by default, and the per-device-kind peak table."""
import inspect

import jax
import numpy as np
import pytest
from jax.sharding import AxisType

from repro import jaxenv
from repro.core import TransferSession, transfer_scheme


@pytest.mark.parametrize("shape,axes", [((1,), ("data",)),
                                        ((1, 1), ("data", "model"))])
def test_make_mesh_axes_are_auto(shape, axes):
    mesh = jaxenv.make_mesh(shape, axes)
    assert mesh.axis_names == axes
    assert tuple(mesh.axis_types) == (AxisType.Auto,) * len(axes)


def test_launch_meshes_use_the_auto_helper():
    from repro.launch.mesh import make_debug_mesh

    mesh = make_debug_mesh(data=1, model=1)
    assert set(mesh.axis_types) == {AxisType.Auto}


def _tree():
    rng = np.random.default_rng(3)
    return {"w": rng.standard_normal(48).astype(np.float32),
            "v": {"b": rng.standard_normal(16).astype(np.float32)},
            "ids": np.arange(4, dtype=np.int32)}


def _same_bytes(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.asarray(x).dtype == np.asarray(y).dtype
        and np.array_equal(np.ascontiguousarray(x).view(np.uint8),
                           np.ascontiguousarray(y).view(np.uint8))
        for x, y in zip(la, lb))


@pytest.mark.parametrize("spec", ["marshal@dp1", "marshal+delta@dp1"])
def test_dp1_schemes_round_trip_through_from_device(spec):
    tree = _tree()
    scheme = transfer_scheme(spec, TransferSession())
    dev = scheme.to_device(tree)
    assert _same_bytes(scheme.from_device(dev, tree), tree)
    # a second pass (steady for delta) after one leaf changes
    tree["w"] = tree["w"] + 1.0
    dev = scheme.to_device(tree)
    assert _same_bytes(scheme.from_device(dev, tree), tree)


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv(jaxenv.CACHE_ENV, raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = jaxenv.use_compile_cache()
        assert path == str(jaxenv.REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_sets_nothing_else(monkeypatch, tmp_path):
    monkeypatch.setenv(jaxenv.CACHE_ENV, str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert jaxenv.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


@pytest.mark.parametrize("fn,arg", [
    ("repro.kernels.marshal_pack.ops:pack_tree", "interpret"),
    ("repro.kernels.marshal_pack.ops:unpack_tree", "interpret"),
    ("repro.kernels.marshal_pack.ops:pack_pool", "interpret"),
    ("repro.kernels.ssd_scan.ops:ssd_chunked_kernel", "interpret"),
])
def test_kernel_ops_default_to_compiled(fn, arg):
    import importlib

    mod, name = fn.split(":")
    sig = inspect.signature(getattr(importlib.import_module(mod), name))
    assert sig.parameters[arg].default is False


def test_roofline_peaks_are_keyed_by_device_kind():
    from benchmarks import roofline

    assert roofline.peaks_for("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks_for("cpu")
