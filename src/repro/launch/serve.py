"""Serving driver.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
        --requests 16 --slots 4 [--ckpt-dir /tmp/ck] [--dp 4]

Loads params from a marshalled checkpoint when given (selective restore —
only the ``params`` chains are read from disk), otherwise random init from
:data:`SEED`, and runs the continuous-batching server over a synthetic
request stream.  :func:`serve` is the callable entry point; :func:`main`
parses the command line, calls it and prints the result.
"""
from __future__ import annotations

import argparse
import time
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro import checkpoint as ckpt
from repro.jaxenv import use_compile_cache
from repro.models import registry
from repro.runtime import Request, Server, serve_transfer_policy

SEED = 0  # of the random params and of the request stream


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="python -m repro.launch.serve")
    ap.add_argument("--arch", default="llama3.2-1b",
                    choices=list(registry.ARCH_IDS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--dp", type=int, default=1,
                    help="devices the params arena is sharded over "
                         "(serve_transfer_policy(dp))")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--max-queue", type=int, default=1024,
                    help="admission queue hard bound (submits shed above "
                         "the watermark instead of buffering forever)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline; lapsed requests terminate "
                         "typed (timed_out), not silently")
    return ap


def _params(api, args):
    """The params as a host tree: the server stages its own device copy,
    and a second one alive while it does would cost the params' size."""
    if not args.ckpt_dir:
        return jax.device_get(api.init(jax.random.PRNGKey(SEED)))
    # pointerchain over the manifest: read ONLY the params subtree
    sel = ckpt.selective_restore(args.ckpt_dir, ["params"])
    print(f"restored {len(sel)} param chains from {args.ckpt_dir}")
    return ckpt.load(args.ckpt_dir)["params"]


def serve(args: argparse.Namespace) -> Tuple[List[Request], Server]:
    """Build the server, submit ``args.requests`` synthetic requests
    (prompts of 4-15 tokens) and run it until they terminate.  Returns the
    terminal-state request list and the server."""
    api = registry.get(args.arch, smoke=args.smoke)
    server = Server(api, _params(api, args), slots=args.slots,
                    max_seq=args.max_seq, max_queue=args.max_queue,
                    policy=serve_transfer_policy(args.dp))
    rng = np.random.default_rng(SEED)
    for i in range(args.requests):
        server.submit(Request(
            rid=i,
            prompt=rng.integers(0, api.cfg.vocab_size,
                                size=int(rng.integers(4, 16))).astype(np.int32),
            max_new_tokens=args.max_new,
            deadline_s=args.deadline_s))
    done = server.run(max_steps=args.requests * args.max_new + 50)
    return done, server


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    use_compile_cache()
    t0 = time.perf_counter()
    done, server = serve(args)
    dt = time.perf_counter() - t0
    tok = sum(len(r.tokens_out) for r in done)
    stats = server.stats
    print(f"served {len(done)}/{args.requests} requests, {tok} tokens, "
          f"{dt:.2f}s including set-up and compilation")
    print(f"policy {server.policy} | completed {stats.completed} "
          f"shed {stats.shed} timed-out {stats.timed_out} "
          f"failed {stats.failed} retries {stats.retries_total} "
          f"policy-fallbacks {stats.policy_fallbacks}")


if __name__ == "__main__":
    main()
