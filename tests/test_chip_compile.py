"""Compile the serving path's programs and kernels for a described TPU v5e.

Nothing runs: the TPU compiler, installed alongside JAX, compiles for a
chip that is described, not attached.  It refuses what the chip would
refuse (a kernel tile the chip cannot hold, a program that does not fit
its memory), which interpret mode and the CPU backend cannot show.  Every
shape here is mamba2-1.3b's published width.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import arena as arena_lib
from repro.core import engine as engine_lib
from repro.kernels.marshal_pack import kernel as mp_k
from repro.kernels.ssd_scan import kernel as ssd_k
from repro.models import registry

V5E_HBM_BYTES = 16e9
SLOTS, MAX_SEQ, PROMPT = 8, 128, 16


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compilation cache off: a
    compile for a described chip is written to it but cannot be read back
    without the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def api():
    return registry.get("mamba2-1.3b")


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _total_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def test_decode_step_compiles_at_full_width_and_fits(api, one_chip):
    params = _on(one_chip, api.abstract())
    cache = _on(one_chip, api.init_cache(SLOTS, MAX_SEQ, abstract_only=True))
    tokens = jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(api.decode_step).lower(params, tokens, cache).compile()
    assert 0 < _total_bytes(compiled) < V5E_HBM_BYTES


def test_prefill_compiles_at_full_width(api, one_chip):
    params = _on(one_chip, api.abstract())
    cache = _on(one_chip, api.init_cache(1, MAX_SEQ, abstract_only=True))
    tokens = jax.ShapeDtypeStruct((1, PROMPT), jnp.int32, sharding=one_chip)
    compiled = jax.jit(api.prefill).lower(params, tokens, cache).compile()
    assert 0 < _total_bytes(compiled) < V5E_HBM_BYTES


def test_arena_unpack_of_full_width_params_compiles(api, one_chip):
    """The gather an ``ArenaEntry`` runs to attach the staged params bucket
    (``serve_transfer_policy``'s ``marshal+align128`` params region)."""
    layout = arena_lib.plan(api.abstract(), align_elems=128)
    buffers = {b: jax.ShapeDtypeStruct((n,), np.dtype(b), sharding=one_chip)
               for b, n in layout.bucket_sizes.items()}
    compiled = jax.jit(
        lambda bufs: tuple(engine_lib.unpack_leaves(bufs, layout))
    ).lower(buffers).compile()
    assert 0 < _total_bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_marshal_pack_gather_tiles_compiles_as_a_kernel(one_chip, dtype):
    n_tiles = 1024
    src = jax.ShapeDtypeStruct((n_tiles * mp_k.SUBLANE, mp_k.LANE), dtype,
                               sharding=one_chip)
    tmap = jax.ShapeDtypeStruct((n_tiles,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(mp_k.gather_tiles).lower(src, tmap).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_chunks_compiles_as_a_kernel_at_mamba2_widths(api, one_chip):
    cfg = api.cfg
    B, nc, Q = 1, 2, cfg.ssm_chunk
    nh, hd, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    assert (Q, nh, hd, N) == (256, 64, 64, 128)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args = (sds((B, nc, nh, Q, hd), jnp.bfloat16),
            sds((B, nc, nh, 1, Q), jnp.float32),
            sds((B, nc, nh, 1, Q), jnp.float32),
            sds((B, nc, Q, N), jnp.bfloat16),
            sds((B, nc, Q, N), jnp.bfloat16))
    compiled = jax.jit(ssd_k.ssd_chunks).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
