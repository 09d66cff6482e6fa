import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any jax import (jax locks the device
count at first init); this module therefore must be the process entry point:

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out artifacts/dryrun

Per cell it emits JSON with:
  * compiled.memory_analysis()  (bytes per device -> "does it fit")
  * compiled.cost_analysis()    (HLO flops / bytes -> roofline terms)
  * collective bytes parsed from the compiled HLO (all-gather, all-reduce,
    reduce-scatter, all-to-all, collective-permute) -> the ICI roofline term
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.shapes import SHAPES, skip_reason
from repro.launch import hlo_analysis, probe
from repro.launch.mesh import (adapt_batch_rule, make_production_mesh,
                               rules_for, tree_shardings)
from repro.models import pspec, registry
from repro.optim import make_optimizer, warmup_cosine
from repro.runtime.train import (abstract_train_state, make_train_step,
                                 train_state_axes)


def _batch_shardings(api, shape, mesh, rules):
    axes = api.input_axes(shape)
    return tree_shardings(mesh, axes, rules, api.input_specs(shape))


def lower_cell(arch: str, shape_name: str, mesh, *, smoke: bool = False,
               layer_probe: bool = True) -> Dict[str, Any]:
    """Lower + compile one (arch, shape) on ``mesh``; return analysis dict."""
    api = registry.get(arch, smoke=smoke)
    cfg = api.cfg
    shape = SHAPES[shape_name]
    if smoke:
        shape = shape.smoke()
    reason = skip_reason(cfg, shape_name)
    if reason:
        return {"arch": arch, "shape": shape_name, "skipped": reason}

    mode = shape.mode
    rules = rules_for(cfg, mesh, mode)
    rules = adapt_batch_rule(rules, mesh, shape.global_batch)

    t0 = time.time()
    with pspec.activate(mesh, rules):
        if mode == "train":
            opt = make_optimizer(cfg.optimizer)
            lr = warmup_cosine(3e-4, 100, 10_000)
            step_fn = make_train_step(api, opt, lr)
            state_abs = abstract_train_state(api, opt)
            state_sh = tree_shardings(mesh, train_state_axes(api, opt), rules,
                                      state_abs)
            in_sh = (state_sh, _batch_shardings(api, shape, mesh, rules))
            args = (state_abs, api.input_specs(shape))
            fn = jax.jit(step_fn, in_shardings=in_sh,
                         out_shardings=(state_sh, None),
                         donate_argnums=(0,))
        elif mode == "prefill":
            params_abs = api.abstract()
            params_sh = tree_shardings(mesh, api.axes(), rules, params_abs)
            cache_abs = api.abstract_cache(shape)
            cache_sh = tree_shardings(mesh, api.cache_axes(shape), rules,
                                      cache_abs)
            specs = api.input_specs(shape)
            tokens = specs.pop("tokens")
            extra_sh = {k: _batch_shardings(api, shape, mesh, rules)[k]
                        for k in specs}
            tok_sh = NamedSharding(mesh, pspec.logical_to_spec(
                ("batch", None), rules))

            def step_fn(params, tok, cache, **kw):
                return api.prefill(params, tok, cache, **kw)

            fn = jax.jit(step_fn,
                         in_shardings=(params_sh, tok_sh, cache_sh),
                         out_shardings=(None, cache_sh),
                         donate_argnums=(2,))
            args = (params_abs, tokens, cache_abs)
            if specs:
                fn = jax.jit(lambda params, tok, cache, extra: api.prefill(
                                 params, tok, cache, **extra),
                             in_shardings=(params_sh, tok_sh, cache_sh, extra_sh),
                             out_shardings=(None, cache_sh),
                             donate_argnums=(2,))
                args = (params_abs, tokens, cache_abs, specs)
        else:  # decode
            params_abs = api.abstract()
            params_sh = tree_shardings(mesh, api.axes(), rules, params_abs)
            cache_abs = api.abstract_cache(shape)
            cache_sh = tree_shardings(mesh, api.cache_axes(shape), rules,
                                      cache_abs)
            tokens = api.input_specs(shape)["tokens"]
            tok_sh = NamedSharding(mesh, pspec.logical_to_spec(
                ("batch", None), rules))
            fn = jax.jit(api.decode_step,
                         in_shardings=(params_sh, tok_sh, cache_sh),
                         out_shardings=(None, cache_sh),
                         donate_argnums=(2,))
            args = (params_abs, tokens, cache_abs)

        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        bodies = (probe.layer_bodies(api, shape, mesh, rules)
                  if layer_probe else [])

    mem = compiled.memory_analysis()
    cost = hlo_analysis.cost_dict(compiled)
    coll = hlo_analysis.collective_stats(compiled.as_text())
    n_dev = int(np.prod(mesh.devices.shape))

    result = {
        "arch": arch, "shape": shape_name, "mode": mode,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "mesh_axes": list(mesh.axis_names),
        "devices": n_dev,
        "device_kind": mesh.devices.flat[0].device_kind,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops": float(cost.get("flops", -1.0)) if cost else -1.0,
        "bytes_accessed": float(cost.get("bytes accessed", -1.0)) if cost else -1.0,
        "memory": hlo_analysis.memory_dict(mem),
        "collectives": coll,
        "bodies": bodies,
    }
    result["corrected"] = probe.corrected_terms(result, bodies)
    return result


def run_grid(archs, shapes, meshes, out_dir: Optional[str], smoke: bool):
    os.makedirs(out_dir, exist_ok=True) if out_dir else None
    results = []
    for mesh_name in meshes:
        mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
        for arch in archs:
            for shape_name in shapes:
                tag = f"{arch}|{shape_name}|{mesh_name}"
                try:
                    res = lower_cell(arch, shape_name, mesh, smoke=smoke)
                    res["mesh_name"] = mesh_name
                    status = ("SKIP: " + res["skipped"]) if "skipped" in res \
                        else f"ok ({res['compile_s']:.0f}s compile)"
                except Exception as e:  # noqa: BLE001 - report and continue
                    res = {"arch": arch, "shape": shape_name,
                           "mesh_name": mesh_name, "error": str(e),
                           "traceback": traceback.format_exc()}
                    status = f"ERROR: {e}"
                print(f"[dryrun] {tag}: {status}", flush=True)
                results.append(res)
                if out_dir:
                    fname = f"{arch}_{shape_name}_{mesh_name}.json".replace("/", "_")
                    with open(os.path.join(out_dir, fname), "w") as f:
                        json.dump(res, f, indent=1)
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs (CI of the dry-run itself)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    archs = list(registry.ARCH_IDS) if (args.all or args.arch is None) \
        else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    results = run_grid(archs, shapes, meshes, args.out, args.smoke)
    bad = [r for r in results if "error" in r]
    print(f"[dryrun] {len(results) - len(bad)}/{len(results)} cells ok")
    if bad:
        for r in bad:
            print(f"  FAILED {r['arch']}|{r['shape']}|{r['mesh_name']}: "
                  f"{r['error'][:200]}")
        sys.exit(1)


if __name__ == "__main__":
    main()
