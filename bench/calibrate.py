"""Readings that set a cell's limits: many seeds of one cell in one
process, the program's and the control's.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5 \
        [--trace-seeds 1] [--control-seeds 4,5,6]

Each seed is a whole run of the cell (set-up, window, check), as
``bench/run.py`` makes it, printed as one JSON line; a control seed puts
the control in the program's place (``correct`` must come out false).
The benchmark's runs never run the control.  Later seeds of one process
reuse its compiled programs, so their ``setup_s`` is not the benchmark's.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> int:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # the checkout's own cache is not size-capped: a capped cache keeps
    # access times and a lock, which made each of ~1,600 small first-run
    # writes slower as the directory grew (1,265 s of compiles against
    # 154 s uncapped for a serving configuration on one TPU v5e)
    jax.config.update("jax_compilation_cache_max_size", -1)
    from bench.harness import NoChip, run_cell

    ap = argparse.ArgumentParser(prog="bench/calibrate.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", default="",
                    help="seeds run with the control in the program's place")
    ap.add_argument("--trace-seeds", default="")
    args = ap.parse_args()
    traced = {int(s) for s in args.trace_seeds.split(",") if s}
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    t_start = T_START
    for seed, control in [(s, False) for s in seeds] + [(s, True) for s in controls]:
        try:
            out = run_cell(ROOT, args.workload, seed, args.seconds,
                           seed in traced and not control, t_start=t_start,
                           control=control, log=lambda m: print(m, flush=True))
        except NoChip as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"seed": seed, "control": control, **out}),
              flush=True)
        gc.collect()
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
