"""Per-kernel shape/dtype sweeps: pallas (interpret=True) vs. pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.decode_attention import kernel as da_k, ref as da_ref
from repro.kernels.rmsnorm import kernel as rn_k, ref as rn_ref
from repro.kernels.marshal_pack import kernel as mp_k, ops as mp_ops, ref as mp_ref
from repro.kernels.ssd_scan import kernel as ssd_k, ops as ssd_ops, ref as ssd_ref
from repro.models.ssm import ssd_chunked

RNG = np.random.default_rng(7)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------- flash attn
@pytest.mark.parametrize("B,H,KV,Sq,Sk,hd", [
    (2, 4, 2, 256, 256, 64),
    (1, 8, 8, 128, 384, 128),
    (2, 4, 1, 256, 256, 64),
    (1, 2, 2, 96, 160, 64),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, KV, Sq, Sk, hd, causal, dtype):
    q = jnp.asarray(RNG.standard_normal((B, Sq, H, hd)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, Sk, KV, hd)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, Sk, KV, hd)), dtype)
    out = fa_ops.mha(q, k, v, causal=causal, interpret=True)
    exp = fa_ref.attention_ref(
        q.transpose(0, 2, 1, 3).astype(jnp.float32),
        k.transpose(0, 2, 1, 3).astype(jnp.float32),
        v.transpose(0, 2, 1, 3).astype(jnp.float32),
        causal=causal).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


def test_flash_matches_model_attention_blockwise():
    """Kernel semantics == the model's jnp blockwise attention."""
    from repro.models import layers as L
    from repro.configs.base import ModelConfig
    cfg = ModelConfig("t", "dense", 1, 64, 4, 2, 128, 100, head_dim=16)
    B, S = 2, 64
    rngk = jax.random.PRNGKey(0)
    x = jax.random.normal(rngk, (B, S, 64), jnp.float32)
    p = {"wq": jax.random.normal(rngk, (64, 4, 16)) * 0.1,
         "wk": jax.random.normal(jax.random.PRNGKey(1), (64, 2, 16)) * 0.1,
         "wv": jax.random.normal(jax.random.PRNGKey(2), (64, 2, 16)) * 0.1,
         "wo": jax.random.normal(jax.random.PRNGKey(3), (4, 16, 64)) * 0.1}
    out_model, _ = L.multihead_attention(cfg, p, x,
                                         positions=jnp.arange(S)[None],
                                         block_q=16)
    # same computation via the kernel path
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = L.rope(q, jnp.arange(S)[None], cfg.rope_theta)
    k = L.rope(k, jnp.arange(S)[None], cfg.rope_theta)
    ctx = fa_ops.mha(q, k, v, causal=True, interpret=True, block_q=16,
                     block_k=16)
    out_kernel = jnp.einsum("bshk,hkd->bsd", ctx, p["wo"])
    np.testing.assert_allclose(np.asarray(out_model), np.asarray(out_kernel),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------- decode attn
@pytest.mark.parametrize("B,H,KV,S,hd,bk", [
    (2, 4, 2, 512, 64, 128),
    (3, 8, 1, 300, 128, 128),
    (1, 16, 2, 2048, 64, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, H, KV, S, hd, bk, dtype):
    q = jnp.asarray(RNG.standard_normal((B, H, hd)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, KV, S, hd)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, KV, S, hd)), dtype)
    valid = jnp.asarray(RNG.integers(1, S, size=(B,)), jnp.int32)
    out = da_k.decode_attention(q, k, v, valid, interpret=True, block_k=bk)
    exp = da_ref.decode_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32), valid)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


# ---------------------------------------------------------------- rmsnorm
@pytest.mark.parametrize("shape", [(4, 128), (2, 3, 256), (1000, 64), (7, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = jnp.asarray(RNG.standard_normal(shape), dtype)
    w = jnp.asarray(RNG.standard_normal(shape[-1]), dtype)
    out = rn_k.rmsnorm(x, w, interpret=True)
    exp = rn_ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


# ---------------------------------------------------------------- marshal pack
@pytest.mark.parametrize("n_tiles", [1, 4, 17])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_gather_tiles_sweep(n_tiles, dtype):
    src = jnp.asarray(
        (RNG.standard_normal((n_tiles * mp_k.SUBLANE, mp_k.LANE)) * 10)
    ).astype(dtype)
    tmap = jnp.asarray(RNG.permutation(n_tiles).astype(np.int32))
    out = mp_k.gather_tiles(src, tmap, interpret=True)
    exp = mp_ref.pack_ref(src.reshape(-1), tmap,
                          mp_k.SUBLANE * mp_k.LANE).reshape(-1, mp_k.LANE)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


def test_pack_tree_roundtrip():
    tree = {"a": jnp.arange(100, dtype=jnp.float32).reshape(10, 10),
            "b": {"c": jnp.full((3, 700), 2.0, jnp.float32)}}
    packed, meta = mp_ops.pack_tree(tree, interpret=True)
    out = mp_ops.unpack_tree(packed, meta, interpret=True)
    for x, y in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------- ssd scan
@pytest.mark.parametrize("B,S,nh,hd,N,chunk", [
    (2, 64, 3, 8, 4, 16),
    (1, 128, 2, 16, 8, 32),
    (2, 32, 1, 8, 16, 8),
])
def test_ssd_kernel_vs_jnp_chunked(B, S, nh, hd, N, chunk):
    x = jnp.asarray(RNG.standard_normal((B, S, nh, hd)), jnp.float32)
    dt = jnp.asarray(np.abs(RNG.standard_normal((B, S, nh))) * 0.1 + 0.01,
                     jnp.float32)
    A = jnp.asarray(-np.abs(RNG.standard_normal(nh)) - 0.1, jnp.float32)
    Bm = jnp.asarray(RNG.standard_normal((B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.standard_normal((B, S, N)), jnp.float32)
    y1, s1 = ssd_ops.ssd_chunked_kernel(x, dt, A, Bm, Cm, chunk=chunk,
                                        interpret=True)
    y2, s2 = ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)


def test_ssd_chunked_matches_sequential_recurrence():
    """The chunked algorithm == literal per-token SSM recurrence."""
    B, S, nh, hd, N = 2, 48, 2, 8, 4
    x = jnp.asarray(RNG.standard_normal((B, S, nh, hd)), jnp.float32)
    dt = jnp.asarray(np.abs(RNG.standard_normal((B, S, nh))) * 0.1 + 0.01,
                     jnp.float32)
    A = jnp.asarray(-np.abs(RNG.standard_normal(nh)) - 0.1, jnp.float32)
    Bm = jnp.asarray(RNG.standard_normal((B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.standard_normal((B, S, N)), jnp.float32)
    y, s = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
    state = np.zeros((B, nh, hd, N))
    ys = []
    for t in range(S):
        dtA = np.exp(np.asarray(dt[:, t]) * np.asarray(A)[None])
        upd = np.einsum("bn,bhd,bh->bhdn", np.asarray(Bm[:, t]),
                        np.asarray(x[:, t]), np.asarray(dt[:, t]))
        state = state * dtA[:, :, None, None] + upd
        ys.append(np.einsum("bn,bhdn->bhd", np.asarray(Cm[:, t]), state))
    np.testing.assert_allclose(np.asarray(y), np.stack(ys, 1),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(s), state, rtol=1e-3, atol=1e-3)
