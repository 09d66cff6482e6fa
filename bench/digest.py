"""Position-weighted 32-bit digests of arrays, on the device and on the host.

``digest(x)`` reads ``x``'s bytes as unsigned words (one word per element,
of the element's width, widened to 32 bits) and returns
``sum_i word_i * (2*K*i + 2*C + 1) mod 2**32`` over the C-order flattening.
Every multiplier is odd, so a change to any one word changes the digest,
and the weights depend on the position, so moved or swapped data does too.
``digest_rows(x, axis)`` is the digest of each slice ``x[..., j, ...]``
along ``axis``: one number per serving slot.

The device form is what a pass's answer is reduced to, in the window and
at no host cost; the host form is the plain loop the tests hold it to.
"""
from __future__ import annotations

import numpy as np

K = 0x9E3779B1
C = 0x7F4A7C15
_UNSIGNED = {1: np.uint8, 2: np.uint16, 4: np.uint32}


def host_digest(x: np.ndarray) -> int:
    words = np.ascontiguousarray(x).reshape(-1).view(
        _UNSIGNED[x.dtype.itemsize]).astype(np.uint64)
    i = np.arange(words.size, dtype=np.uint64)
    mult = (2 * K * i + 2 * C + 1) % 2 ** 32
    return int(np.sum((words * mult) % 2 ** 32) % 2 ** 32)


def host_digest_rows(x: np.ndarray, axis: int) -> np.ndarray:
    moved = np.moveaxis(x, axis, 0)
    return np.array([host_digest(row) for row in moved], np.uint32)


def device_digest(x):
    import jax
    import jax.numpy as jnp

    flat = jnp.reshape(x, (-1,))
    words = jax.lax.bitcast_convert_type(
        flat, _UNSIGNED[flat.dtype.itemsize]).astype(jnp.uint32)
    i = jax.lax.iota(jnp.uint32, flat.size)
    mult = i * jnp.uint32(2 * K % 2 ** 32) + jnp.uint32((2 * C + 1) % 2 ** 32)
    return jnp.sum(words * mult, dtype=jnp.uint32)


def device_digest_rows(x, axis: int):
    import jax
    import jax.numpy as jnp

    return jax.vmap(device_digest)(jnp.moveaxis(x, axis, 0))
