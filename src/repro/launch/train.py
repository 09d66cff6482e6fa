"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
        --steps 100 [--ckpt-dir /tmp/ck] [--grad-scheme arena --compress]

On a real TPU slice this runs the pjit step over `make_production_mesh()`;
on CPU (or --smoke) it runs single-device with the same loop, checkpoints,
watchdog and failure-recovery semantics.  `--dp-shardmap` switches to the
explicit shard_map data-parallel step whose gradient collective schedule is
the paper's transfer-scheme choice (pertensor | arena [+ int8]).
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.shapes import SHAPES
from repro.data import Prefetcher, SyntheticLM
from repro.jaxenv import make_mesh, use_compile_cache
from repro.launch.mesh import (make_production_mesh, rules_for,
                               tree_shardings)
from repro.models import pspec, registry
from repro.optim import make_optimizer, warmup_cosine
from repro.runtime import loop as loop_mod
from repro.runtime.train import (init_error_state, make_dp_train_step,
                                 make_train_step, state_transfer_policy,
                                 train_state, train_state_axes)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b",
                    choices=list(registry.ARCH_IDS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--production-mesh", action="store_true",
                    help="build the 16x16 mesh (needs >=256 devices)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--dp-shardmap", action="store_true",
                    help="explicit-DP step with chosen gradient collective")
    ap.add_argument("--grad-scheme", default="arena",
                    choices=["pertensor", "arena"])
    ap.add_argument("--compress", action="store_true",
                    help="int8+error-feedback gradient compression")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    use_compile_cache()
    api = registry.get(args.arch, smoke=args.smoke)
    cfg = api.cfg
    opt = make_optimizer(cfg.optimizer)
    lr = warmup_cosine(args.lr, min(100, args.steps // 10 + 1), args.steps)
    data = SyntheticLM(cfg.vocab_size, args.seq, args.batch)

    state_shardings = None
    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        rules = rules_for(cfg, mesh, "train")
        with pspec.activate(mesh, rules):
            base_step = make_train_step(api, opt, lr)
            state_shardings = tree_shardings(
                mesh, train_state_axes(api, opt), rules)
            step = jax.jit(base_step, in_shardings=(state_shardings, None),
                           out_shardings=(state_shardings, None),
                           donate_argnums=(0,))
    elif args.dp_shardmap:
        n = len(jax.devices())
        mesh = make_mesh((n, 1), ("data", "model"))
        dp_step = make_dp_train_step(api, opt, lr, mesh,
                                     grad_scheme=args.grad_scheme,
                                     compress=args.compress)
        err = init_error_state(api, args.compress, mesh=mesh)

        def step(state, batch):
            new_state, metrics, new_err = dp_step(state, batch, step.err)
            step.err = new_err
            return new_state, metrics
        step.err = err
    else:
        step = jax.jit(make_train_step(api, opt, lr), donate_argnums=(0,))

    res = loop_mod.run(
        step, lambda: train_state(api, opt, jax.random.PRNGKey(0)),
        lambda s: {k: np.asarray(v) for k, v in data.batch(s).items()},
        num_steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, state_shardings=state_shardings,
        # restored checkpoints stage through ONE policy program: arena
        # params + delta opt state + marshalled metadata.  NOT on the
        # dp-shardmap path: its shard_map step needs replicated,
        # uncommitted state, and a program's device_put commits placement.
        state_policy=state_transfer_policy()
        if state_shardings is None and not args.dp_shardmap else None,
        log_every=args.log_every)

    losses = [m["loss"] for m in res.metrics_history]
    print(f"done: loss {losses[0]:.4f} -> {np.mean(losses[-5:]):.4f} "
          f"({args.steps} steps, {res.restarts} restarts, "
          f"{len(res.straggler_steps)} stragglers)")


if __name__ == "__main__":
    main()
