"""jit wrapper: full chunked SSD using the Pallas chunk kernel.

Drop-in replacement for ``repro.models.ssm.ssd_chunked`` (same signature /
semantics); the inter-chunk recurrence and off-diagonal term are jnp.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .kernel import ssd_chunks


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunked_kernel(x, dt, A, Bm, Cm, chunk: int,
                       init_state: Optional[jax.Array] = None, *,
                       interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,nh,hd), dt: (B,S,nh), A: (nh,), Bm/Cm: (B,S,N)."""
    Bsz, S, nh, hd = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    nc = S // Q
    assert nc * Q == S

    xc = x.reshape(Bsz, nc, Q, nh, hd).transpose(0, 1, 3, 2, 4)     # B,nc,nh,Q,hd
    dtc = dt.reshape(Bsz, nc, Q, nh).transpose(0, 1, 3, 2)[:, :, :, None, :]
    dtA = (dt * A[None, None, :]).reshape(Bsz, nc, Q, nh) \
        .transpose(0, 1, 3, 2)[:, :, :, None, :]
    Bc = Bm.reshape(Bsz, nc, Q, N)
    Cc = Cm.reshape(Bsz, nc, Q, N)

    y_diag, states, cum = ssd_chunks(xc, dtc, dtA, Bc, Cc, interpret=interpret)
    cum = cum[:, :, :, 0, :]                                        # B,nc,nh,Q

    # inter-chunk recurrence (linear scan over nc)
    chunk_decay = jnp.exp(cum[:, :, :, -1])                         # B,nc,nh
    if init_state is None:
        init_state = jnp.zeros((Bsz, nh, hd, N), jnp.float32)

    def step(state, inputs):
        dec, new = inputs
        out_state = state
        state = state * dec[:, :, None, None] + new
        return state, out_state

    xs = (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(states, 1, 0))
    final_state, prev = jax.lax.scan(step, init_state.astype(jnp.float32), xs)
    prev = jnp.moveaxis(prev, 0, 1)                                 # B,nc,nh,hd,N

    y_off = jnp.einsum("bcqn,bchdn,bchq->bchqd", Cc.astype(jnp.float32),
                       prev, jnp.exp(cum))
    y = (y_diag.astype(jnp.float32) + y_off).transpose(0, 1, 3, 2, 4) \
        .reshape(Bsz, S, nh, hd)
    return y.astype(x.dtype), final_state
