"""Benchmark driver: one section per paper table/figure + framework benches.

    PYTHONPATH=src python -m benchmarks.run            # full suite
    PYTHONPATH=src python -m benchmarks.run --quick    # CI-sized sweep
    PYTHONPATH=src python -m benchmarks.run --smoke    # registry smoke only

Sections (paper artifact -> module):
    datasize            Eq. 1-3 / Tables 1-2     benchmarks.datasize
    linear              §4.1 / Figs. 5-6         benchmarks.linear_scenario
    dense               §4.2 / Fig. 7            benchmarks.dense_scenario
    transfer            registry x scheme steady state benchmarks.transfer_steady
    transfer_overlap    pipelined executor overlap     benchmarks.transfer_overlap
    elastic             n -> m restart restore split   benchmarks.elastic_restart
    serve               open-loop request stream       benchmarks.serve_load
    instructions        §6.3 / Tables 3-4        benchmarks.instruction_count
    marshal_kernel      Alg. 1 as a TPU kernel   benchmarks (inline)
    checkpoint          marshalled ckpt I/O      benchmarks.checkpoint_bench
    collective_fusion   arena-fused psums        benchmarks.collective_fusion
    roofline            §Roofline summary        benchmarks.roofline

The transfer section iterates the full ``repro.scenarios`` registry and
writes ``BENCH_transfer.json`` (repo root) in the schema-versioned row
format of ``benchmarks.bench_schema`` (v6): TransferSpec x scenario x
{spec, first_wall_us, cached_wall_us, h2d_bytes, h2d_calls, enqueue_us,
sync_us, skipped_bytes, delta_calls, sharded, n_devices, per_device_*,
*_by_device, steady_*} plus one PROGRAM row per scenario policy ({policy,
region_ledgers, steady_region_ledgers, overlap_wall_us, sync_offload_us,
finish_us}) — the machine-readable perf trajectory (compare across PRs
with ``scripts/update_experiments.py --transfer --old prev.json``, gate
regressions with ``python -m benchmarks.bench_schema old new --gate``;
old-schema rows still parse).  ``--smoke``
runs ONLY the registry sweep at tiny sizes (benchmarks.smoke), including
the steady-state delta contracts of the steady_reuse/sharded_delta
families and every scenario's declared policy program, and fails on any
value- or data-motion-check mismatch: the CI harness-breakage canary.
``--spec`` (comma-separated canonical spec strings, e.g.
``marshal+delta@dp8``) narrows the smoke and transfer sweeps to those
specs; ``--policy`` (repeatable policy strings, e.g.
``'params/**=marshal+delta@dp8; **=marshal'``) compiles each into a
TransferProgram over every scenario tree and enforces the per-region
ledger contracts.  ``--async`` additionally drives every policy program
through the PIPELINED executor (``to_device_async``) in the smoke sweep —
same trees, same per-region contracts, async==sync enforced as a failure.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def _section(name):
    print(f"\n===== {name} =====", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="registry x spec sweep at tiny sizes, then exit "
                         "(fails on check/data-motion mismatches)")
    ap.add_argument("--spec", default="",
                    help="comma-separated TransferSpec strings (e.g. "
                         "marshal+delta@dp8) restricting the smoke/transfer "
                         "sweeps; legacy scheme names also parse")
    ap.add_argument("--policy", action="append", default=[],
                    help="path-scoped TransferPolicy string (repeatable), "
                         "e.g. 'params/**=marshal+delta@dp8; **=marshal' — "
                         "compiled into a TransferProgram over every "
                         "scenario tree in the smoke/transfer sweeps")
    ap.add_argument("--async", dest="async_", action="store_true",
                    help="smoke: drive every policy program through the "
                         "pipelined executor too (async==sync enforced)")
    ap.add_argument("--skip", default="",
                    help="comma-separated section names to skip")
    ap.add_argument("--baseline", default="",
                    help="committed BENCH_transfer.json to diff the fresh "
                         "rows against after the transfer+elastic sections "
                         "(bench_schema --baseline; exits 1 on steady-wall "
                         "regression)")
    args = ap.parse_args(argv)
    from repro.jaxenv import use_compile_cache
    use_compile_cache()
    skip = set(filter(None, args.skip.split(",")))
    specs = list(filter(None, args.spec.split(","))) or None
    policies = [p for p in args.policy if p.strip()] or None
    t0 = time.time()

    if args.smoke:
        _section("scenario registry smoke (all scenarios x all specs)")
        from . import smoke
        smoke.run(specs=specs, policies=policies, async_executor=args.async_)
        print(f"\n[benchmarks.run] done in {time.time() - t0:.1f}s")
        return

    if "datasize" not in skip:
        _section("datasize (Eq. 1-3, Tables 1-2)")
        from . import datasize
        import io
        buf = io.StringIO()
        datasize.run(out=buf)
        lines = buf.getvalue().splitlines()
        print("\n".join(lines[:8] + [f"... ({len(lines)} rows total)"]))

    if "linear" not in skip:
        _section("linear scenario (Figs. 5-6)")
        from . import linear_scenario
        if args.quick:
            linear_scenario.run(ks=(2, 6), ns=(10**3,), repeats=1)
        else:
            linear_scenario.run()

    if "dense" not in skip:
        _section("dense scenario (Fig. 7)")
        from . import dense_scenario
        if args.quick:
            dense_scenario.run(qs=(4,), ns=(10**3,), repeats=1)
        else:
            dense_scenario.run()

    if "transfer" not in skip:
        _section("transfer steady state (arena engine, first vs cached call)")
        from . import transfer_steady
        json_path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "BENCH_transfer.json")
        transfer_steady.run(quick=args.quick,
                            repeats=3 if args.quick else 5,
                            json_path=json_path, specs=specs,
                            policies=policies)

    if "transfer_overlap" not in skip:
        _section("transfer overlap (pipelined executor, zero-stall ckpt)")
        from . import transfer_overlap
        json_path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "BENCH_overlap.json")
        transfer_overlap.run(quick=args.quick,
                             repeats=3 if args.quick else 5,
                             json_path=json_path)

    if "elastic" not in skip:
        _section("elastic restart (n -> m mesh restore, trajectory asserted)")
        from . import elastic_restart
        json_path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "BENCH_transfer.json")
        # runs AFTER the transfer section on purpose: transfer_steady owns
        # and rewrites BENCH_transfer.json; elastic rows merge into it
        elastic_restart.run_bench(quick=args.quick, json_path=json_path)

    if args.baseline:
        # after the transfer+elastic sections have rewritten the fresh row
        # file: diff it against the committed baseline and fail loudly on a
        # steady-wall regression (bench_schema --baseline semantics)
        _section(f"baseline diff (vs {args.baseline})")
        from . import bench_schema
        fresh = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "BENCH_transfer.json")
        rc = bench_schema.run_baseline(args.baseline, fresh)
        if rc:
            sys.exit(rc)

    if "serve" not in skip:
        _section("serve load (open-loop request stream, faulted legs)")
        from . import serve_load
        json_path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "BENCH_serve.json")
        serve_load.run_bench(preset="quick" if args.quick else "full",
                             json_path=json_path)

    if "instructions" not in skip:
        _section("instruction count (Tables 3-4)")
        from . import instruction_count
        instruction_count.run(ks=(2, 4, 6, 8, 10) if args.quick
                              else (2, 3, 4, 5, 6, 7, 8, 9, 10))

    if "marshal_kernel" not in skip:
        _section("marshal_pack kernel (Alg. 1 as a Pallas kernel)")
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.kernels.marshal_pack import kernel as mk
        from .timer import bench
        dev = jax.devices()[0]
        # Pallas TPU kernels compile only for a TPU; elsewhere the
        # interpreter runs them, which checks results and times nothing real
        interpret = dev.platform != "tpu"
        n_tiles = 64
        src = jnp.asarray(np.random.default_rng(0).standard_normal(
            (n_tiles * mk.SUBLANE, mk.LANE)), jnp.float32)
        tmap = jnp.asarray(np.random.default_rng(1).permutation(n_tiles)
                           .astype(np.int32))
        fn = lambda: jax.block_until_ready(  # lint: allow=DC201 -- timed kernel sync

            mk.gather_tiles(src, tmap, interpret=interpret))
        mode = "interpret" if interpret else "compiled"
        r = bench(f"marshal_pack_{mode}", fn, min_time=0.05, repeats=2)
        mb = src.nbytes / 1e6
        print(f"platform={dev.platform} device_kind={dev.device_kind} "
              f"devices={jax.device_count()} mode={mode}")
        print("name,us_per_call,derived")
        print(r.csv(f"{mb:.2f}MB/call"))

    if "checkpoint" not in skip:
        _section("checkpoint (marshalled vs per-leaf)")
        from . import checkpoint_bench
        checkpoint_bench.run()

    if "collective_fusion" not in skip:
        _section("collective fusion (arena psum vs per-tensor)")
        from . import collective_fusion
        collective_fusion.run()

    if "roofline" not in skip:
        _section("roofline summary (from artifacts/dryrun)")
        art = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "artifacts", "dryrun")
        if os.path.isdir(art) and os.listdir(art):
            from . import roofline
            rows = roofline.run(art)
            print(f"({len(rows)} cells analysed)")
        else:
            print("no dry-run artifacts found; run "
                  "`python -m repro.launch.dryrun --all --mesh both "
                  "--out artifacts/dryrun` first")

    print(f"\n[benchmarks.run] done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
