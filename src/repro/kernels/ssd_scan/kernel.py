"""Mamba2 SSD chunk kernel — the O(Q^2) intra-chunk term on the MXU.

Grid (B, nc, nh): one (chunk x head) tile per step.  B/C are shared across
heads (ngroups=1), so their BlockSpec index_map drops the head index — each
head's grid step re-reads the same (Q, N) tile from VMEM-resident rather
than duplicating it in HBM.

Outputs per step: the intra-chunk output y_diag (Q, hd) and the chunk
summary state (hd, N).  The inter-chunk recurrence (linear in nc) and the
off-diagonal contribution run outside in jnp (``ops.ssd_chunked_kernel``) —
they are O(S) and bandwidth-trivial next to the O(S*Q) kernel work.

Stability: dtA <= 0, so every exp() argument (in-chunk segment sums) is
<= 0 — no overflow; matches the reference segsum formulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, dt_ref, dtA_ref, b_ref, c_ref, y_ref, st_ref, cum_ref):
    x = x_ref[0, 0, 0].astype(jnp.float32)        # (Q, hd)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)      # (1, Q) row vector
    dtA = dtA_ref[0, 0, 0].astype(jnp.float32)    # (1, Q)
    Bm = b_ref[0, 0].astype(jnp.float32)          # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)          # (Q, N)

    Q = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    mask = row >= col
    # running sums as matmuls against triangular ones (Mosaic has no
    # cumsum): cum[j] = sum_{k<=j} dtA[k], as a row and as a column
    cum_row = jax.lax.dot_general(dtA, (row <= col).astype(jnp.float32),
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    cum_col = jnp.sum(jnp.where(mask, dtA, 0.0), axis=1, keepdims=True)
    seg = cum_col - cum_row                       # (Q, Q): cum[i] - cum[j]
    L = jnp.where(mask, jnp.exp(seg), 0.0)

    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    dtx = x * dt[0][:, None]
    y = jax.lax.dot_general(scores * L, dtx, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    decay = jnp.exp(jnp.sum(dtA, axis=1, keepdims=True) - cum_col)  # (Q, 1)
    st = jax.lax.dot_general(dtx, Bm * decay,
                             (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (hd, N)

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    st_ref[0, 0, 0] = st
    cum_ref[0, 0, 0] = cum_row


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunks(x, dt, dtA, Bm, Cm, *, interpret: bool = False):
    """x: (B,nc,nh,Q,hd), dt/dtA: (B,nc,nh,1,Q), Bm/Cm: (B,nc,Q,N).
    Returns y_diag (B,nc,nh,Q,hd), states (B,nc,nh,hd,N), cum (B,nc,nh,1,Q)."""
    B, nc, nh, Q, hd = x.shape
    N = Bm.shape[-1]
    grid = (B, nc, nh)
    kernel = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, hd), lambda b, c, h: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, Q), lambda b, c, h: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, Q), lambda b, c, h: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c, h: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c, h: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, hd), lambda b, c, h: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, hd, N), lambda b, c, h: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, Q), lambda b, c, h: (b, c, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nc, nh, Q, hd), x.dtype),
            jax.ShapeDtypeStruct((B, nc, nh, hd, N), jnp.float32),
            jax.ShapeDtypeStruct((B, nc, nh, 1, Q), jnp.float32),
        ],
        interpret=interpret,
    )
    return kernel(x, dt, dtA, Bm, Cm)
