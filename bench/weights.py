"""Seeded weights (and seeded state contents) made on the device in one
jitted call, in the type they are served in.

The params tree's layout and initialisation are the configuration's
family's (``bench/families/<family>.py``); the harness checks the tree
against the program's abstract tree before use.
"""
from __future__ import annotations

from typing import Any, Dict


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, also one beyond 32 bits."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _flat(tree, prefix=""):
    for k in sorted(tree):
        v = tree[k]
        p = f"{prefix}{k}"
        if isinstance(v, dict):
            yield from _flat(v, p + ".")
        else:
            yield p, v


def _nest(items):
    out: Dict[str, Any] = {}
    for path, val in items:
        node = out
        *head, last = path.split(".")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = val
    return out


def make_params(family, cfg: dict, seed: int):
    """The params tree on the default device, in ``param_dtype``, made by
    one jitted call from ``seed`` by the rules of the ``family`` module."""
    import jax
    import jax.numpy as jnp

    leaves = list(_flat(family.param_shapes(cfg)))
    dtype = jnp.dtype(cfg["param_dtype"])

    def make(key):
        keys = jax.random.split(key, len(leaves))
        return _nest((p, family.init_leaf(p, s, k, cfg).astype(dtype))
                     for (p, s), k in zip(leaves, keys))

    return jax.jit(make)(seed_key(seed))


def make_random(shapes_dtypes: Dict[str, Any], seed: int, salt: int):
    """Seeded random contents for state leaves (``name -> (shape, dtype)``),
    on the device in one jitted call: floats standard normal, integers
    uniform in [0, 128)."""
    import jax
    import jax.numpy as jnp

    names = sorted(shapes_dtypes)

    def make(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for name, k in zip(names, keys):
            shape, dtype = shapes_dtypes[name]
            dtype = jnp.dtype(dtype)
            if jnp.issubdtype(dtype, jnp.integer):
                out[name] = jax.random.randint(k, shape, 0, 128, dtype)
            else:
                out[name] = jax.random.normal(k, shape, jnp.float32).astype(dtype)
        return out

    return jax.jit(make)(jax.random.fold_in(seed_key(seed), salt))
