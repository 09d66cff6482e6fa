"""How this repository sets up the installed JAX (0.9).

  * :func:`make_mesh` — every mesh the repo builds.  ``jax.make_mesh``
    defaults to ``Explicit`` axis types in JAX 0.9, under which
    ``with_sharding_constraint`` refuses the logical-axis rules of
    ``models/pspec.py`` and a ``dynamic_update_slice`` of a sharded leaf
    into a replicated arena bucket is a type error.  The repo's code is
    written for GSPMD's ``Auto`` propagation, so every axis is ``Auto``.
  * :func:`use_compile_cache` — JAX's persistent compilation cache.  The
    directory is part of every entry's key, so it is fixed: the one
    ``JAX_COMPILATION_CACHE_DIR`` names when it is set, otherwise
    ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

REPO_ROOT = Path(__file__).resolve().parents[2]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def make_mesh(shape: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence[jax.Device]] = None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads that
    directory from the environment and nothing else is set here."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
