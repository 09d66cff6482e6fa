"""Fused-collective benchmark (the paper's marshalling, applied to ICI).

Lowers the explicit-DP shard_map train step on an 8-device debug mesh under
three gradient schemes and counts collectives in the compiled HLO:

    pertensor   one psum per gradient leaf      (per-leaf deep copy / UVM-ish)
    arena       one psum per dtype bucket       (Algorithm 1 on the wire)
    arena+int8  bucket psum with shared-scale int8 + error feedback

Runs in a subprocess so XLA_FLAGS can force 8 host devices without touching
this process's device count.  The child is held to the CPU
(``JAX_PLATFORMS=cpu``): it counts HLO on virtual CPU devices and must not
reach for an accelerator the parent may hold.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
from repro.launch.mesh import make_debug_mesh
from repro.launch.hlo_analysis import collective_stats
from repro.models import registry
from repro.optim import make_optimizer, constant
from repro.runtime.train import (init_error_state, make_dp_train_step,
                                 train_state, abstract_train_state)

api = registry.get("llama3.2-1b", smoke=True)
opt = make_optimizer("sgdm")
mesh = make_debug_mesh(data=8, model=1)
state_abs = abstract_train_state(api, opt)
batch_abs = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
out = {}
for scheme, compress in (("pertensor", False), ("arena", False),
                         ("arena", True)):
    step = make_dp_train_step(api, opt, constant(1e-3), mesh,
                              grad_scheme=scheme, compress=compress)
    err_abs = jax.tree_util.tree_map(
        lambda x: x, init_error_state(api, compress, mesh=mesh))
    lowered = jax.jit(step).lower(state_abs, batch_abs, err_abs)
    stats = collective_stats(lowered.compile().as_text())
    emitted = str(jax.make_jaxpr(step)(state_abs, batch_abs, err_abs)
                  ).count("psum")
    name = scheme + ("+int8" if compress else "")
    out[name] = {"count": stats["total_count"],
                 "bytes": stats["total_bytes"],
                 "emitted_psums": emitted,
                 "per_op": {k: v for k, v in stats["per_op"].items()
                            if v["count"]}}
print(json.dumps(out))
"""


def run(out=sys.stdout):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    if res.returncode != 0:
        print("collective_fusion FAILED:", res.stderr[-2000:], file=out)
        raise RuntimeError("collective fusion bench failed")
    data = json.loads(res.stdout.strip().splitlines()[-1])
    print("scheme,emitted_psums,compiled_collectives,collective_bytes", file=out)
    for name, s in data.items():
        print(f"{name},{s['emitted_psums']},{s['count']},{s['bytes']}", file=out)
    return data


if __name__ == "__main__":
    run()
