"""Sanitizer overhead benchmark (the DESIGN.md §13.3 contract).

Two measurements, two thresholds:

* **steady microloop** (default): back-to-back ``TransferProgram`` passes
  — the most hook-dense path possible (every pass is nothing BUT packs,
  fences, enqueues and drains).  True overhead here is the sanitizer's
  bandwidth tax (one word-fold fingerprint over moved bytes, an amortized
  byte-compare over identity-skipped bytes): ~10% of a pure-transfer
  pass, riding on host timing noise of the same magnitude.  The gate is
  :data:`MICRO_BOUND` — generous enough to be noise-proof, tight enough
  to catch a bandwidth regression in the hooks (the original crc32
  fingerprint measured +109% here).

* **``--smoke``**: wall time of ``benchmarks.run --smoke`` with
  ``REPRO_SANITIZE=1`` vs. without, interleaved trials.  This is the
  workload the <10% :data:`OVERHEAD_CONTRACT` of DESIGN.md §13.3 is
  defined over, and what EXPERIMENTS.md records.

Run::

    PYTHONPATH=src python -m benchmarks.sanitizer_overhead [--smoke]

Exit status is non-zero when the applicable threshold breaks, so CI can
gate on it directly.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time
from typing import Optional, Sequence

import numpy as np

from repro.analysis import sanitizer

from .timer import bench

#: the DESIGN.md §13.3 contract, over the ``--smoke`` workload.
OVERHEAD_CONTRACT = 0.10
#: regression tripwire for the hook-dense steady microloop (see module doc).
MICRO_BOUND = 0.50

POLICY = "params/**=marshal+db; opt/**=marshal+delta; **=marshal+db"


def _tree(n: int):
    rng = np.random.default_rng(0)
    return {
        "params": {"w": rng.standard_normal(n).astype(np.float32),
                   "b": rng.standard_normal(n // 8).astype(np.float32)},
        "opt": {"m": rng.standard_normal(n).astype(np.float64),
                "v": rng.standard_normal(n).astype(np.float64)},
    }


def _steady_pass_us(n: int, *, sanitize: bool, min_time: float) -> float:
    """Mean us/pass of a steady mutate-then-ship program loop."""
    # imported here: the --smoke leg spawns children that may need the
    # accelerator, so its parent never loads the engine (or JAX)
    from repro.core.engine import TransferSession

    prev = sanitizer._ACTIVE
    sanitizer._ACTIVE = None
    if sanitize:
        sanitizer.enable(fresh=True)
    try:
        session = TransferSession()
        tree = _tree(n)
        program = session.compile(tree, POLICY)
        program.to_device(tree)

        def one_pass():
            # one dirty region per pass: params/w changes, opt stays
            # identity-clean so both the pack path and the delta
            # identity-skip path are exercised every iteration
            tree["params"]["w"] = tree["params"]["w"] + 1.0
            program.to_device(tree)

        return bench(f"steady_pass[san={'on' if sanitize else 'off'}]",
                     one_pass, min_time=min_time).us_per_call
    finally:
        sanitizer._ACTIVE = prev


def run_micro(n: int = 65536, min_time: float = 0.2, trials: int = 3) -> dict:
    # interleave the off/on legs and take each side's MIN: host-level noise
    # (frequency scaling, allocator state) moves both legs together between
    # trials, and the min is the standard robust microbenchmark statistic —
    # a single-shot ratio of two adaptive timings is noise-dominated here.
    off, on = [], []
    for _ in range(trials):
        off.append(_steady_pass_us(n, sanitize=False, min_time=min_time))
        on.append(_steady_pass_us(n, sanitize=True, min_time=min_time))
    overhead = min(on) / min(off) - 1.0
    return {"n_elems": n, "off_us": min(off), "on_us": min(on),
            "overhead": overhead, "bound": MICRO_BOUND}


def _smoke_seconds(sanitize: bool) -> float:
    env = dict(os.environ)
    env["REPRO_SANITIZE"] = "1" if sanitize else "0"
    t0 = time.perf_counter()
    subprocess.run([sys.executable, "-m", "benchmarks.run", "--smoke"],
                   env=env, stdout=subprocess.DEVNULL,
                   stderr=subprocess.DEVNULL, check=True)
    return time.perf_counter() - t0


def run_smoke(trials: int = 3) -> dict:
    off, on = [], []
    for _ in range(trials):
        off.append(_smoke_seconds(False))
        on.append(_smoke_seconds(True))
    overhead = min(on) / min(off) - 1.0
    return {"off_s": min(off), "on_s": min(on), "overhead": overhead,
            "contract": OVERHEAD_CONTRACT}


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.sanitizer_overhead")
    ap.add_argument("--smoke", action="store_true",
                    help="measure over benchmarks.run --smoke (the DESIGN "
                         "§13.3 contract workload) instead of the microloop")
    ap.add_argument("--n", type=int, default=65536,
                    help="microloop: elements per large leaf")
    ap.add_argument("--min-time", type=float, default=0.2)
    ap.add_argument("--trials", type=int, default=3)
    args = ap.parse_args(argv)

    if args.smoke:
        r = run_smoke(args.trials)
        print(f"benchmarks.run --smoke: off={r['off_s']:.2f}s "
              f"on={r['on_s']:.2f}s overhead={r['overhead']:+.1%} "
              f"(contract <{r['contract']:.0%})")
        bad = r["overhead"] >= r["contract"]
    else:
        r = run_micro(args.n, args.min_time, args.trials)
        print(f"steady program pass, n={r['n_elems']}: "
              f"off={r['off_us']:.1f}us on={r['on_us']:.1f}us "
              f"overhead={r['overhead']:+.1%} (tripwire <{r['bound']:.0%}; "
              f"the <{OVERHEAD_CONTRACT:.0%} contract is over --smoke)")
        bad = r["overhead"] >= r["bound"]
    if bad:
        print("OVERHEAD THRESHOLD BROKEN", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
