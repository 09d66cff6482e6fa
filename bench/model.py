"""The system under test, built from a configuration file: the program's
model with the file's sizes, and a check that the benchmark's seeded
params have the program's layout."""
from __future__ import annotations

import dataclasses
from typing import Any


def model_api(cfg: dict):
    """The program's model for every key of ``cfg`` that is a
    ``ModelConfig`` field; the file's other keys (its source, cuts and
    notes) are for readers."""
    from repro.configs.base import ModelConfig
    from repro.models import registry

    names = {f.name for f in dataclasses.fields(ModelConfig)}
    return registry.get_model(ModelConfig(**{k: v for k, v in cfg.items()
                                             if k in names}))


def check_params(api, params: Any) -> None:
    """The seeded params tree has the program's layout, shapes and dtypes."""
    import jax

    want = jax.tree_util.tree_map(lambda s: (s.shape, str(s.dtype)),
                                  api.abstract())
    got = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), params)
    if want != got:
        raise ValueError(f"seeded params do not match the program's layout:\n"
                         f"program {want}\nbench   {got}")
