"""The benchmark harness: find a cell by name, run it, report one line.

Everything that belongs to one configuration, model family, traffic mix or
per-layer metric is a file of its own, found by the name ``BENCHMARK.json``
or a configuration gives:

* a configuration is ``bench/configs/<name>.json`` (the entry's ``file``);
  its ``family`` names ``bench/families/<family>.py``, the layout and
  initialisation of its weights and cache;
* a traffic mix is ``bench/traffic/<name>.json``, whose ``generator``
  names the general generator in ``bench/generators/<generator>.py``
  that reads it;
* a per-layer metric is ``bench/metrics/<name>.py``, whose ``read(ctx)``
  returns the number or ``None`` when it finds nothing to read.

``root`` is a checkout: ``root/BENCHMARK.json`` and ``root/bench/...``.
A generator module has ``setup(ctx) -> run`` where ``run`` has
``window(seconds, span)`` (the measured loop), ``end_to_end()`` (the
cell's end-to-end numbers other than ``setup_s``), ``check()`` (the
comparison that decides ``correct``; it frees the program's state first),
``counters`` (what the per-layer readers read) and ``attempted`` /
``failed``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable, List, Optional

BENCH = Path(__file__).resolve().parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    family: Any                    # the module bench/families/<family>.py
    traffic: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def _for_cell(metrics: List[dict], cell: str) -> List[dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def find_cell(root: Path, name: str) -> Cell:
    """Look a cell up in ``root/BENCHMARK.json`` and load its files."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    try:
        w = next(w for w in spec["workloads"] if w["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json") from None
    entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name, config, load_family(root, config["family"]), traffic,
                int(w["chips"]),
                _for_cell(spec["end_to_end"], name),
                _for_cell(spec["per_layer"], name))


def _load_file(path: Path, prefix: str):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(root: Path, name: str) -> Callable[[Any], Optional[float]]:
    return _load_file(root / "bench" / "metrics" / f"{name}.py",
                      "bench_metric_").read


def load_family(root: Path, name: str):
    return _load_file(root / "bench" / "families" / f"{name}.py",
                      "bench_family_")


def load_generator(name: str):
    if not name.isidentifier():
        raise ValueError(f"bad generator name {name!r}")
    return importlib.import_module(f"bench.generators.{name}")


class CompileClock:
    """Counts backend compilations and their seconds, and persistent-cache
    hits (jax.monitoring)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.count, self.seconds, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_):
        if name == self.EVENT:
            self.count += 1
            self.seconds += secs

    def _on_event(self, name, **_):
        if name == self.HIT:
            self.hits += 1


@dataclasses.dataclass
class Context:
    """What a generator and the per-layer readers see of a run."""

    root: Path
    cell: Cell
    seed: int
    trace: bool
    devices: list
    control: bool = False          # the control in the program's place
    log: Callable[[str], None] = print
    t_phase: float = 0.0           # end of the last set-up phase logged
    run: Any = None                # the generator's run, once set up
    summary: Any = None            # trace.Summary of the traced window

    @property
    def cfg(self) -> dict:
        return self.cell.config

    @property
    def family(self):
        return self.cell.family

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def phase(self, name: str) -> None:
        """Log the seconds since the last phase (set-up's parts)."""
        now = time.perf_counter()
        self.log(f"  {name}: {now - self.t_phase:.3f} s")
        self.t_phase = now

    def peaks(self) -> dict:
        from bench.peaks import peaks_for

        return peaks_for(self.devices[0].device_kind)


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def check_devices(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU: platform {devices[0].platform!r} "
                     f"({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, require_chip: bool = True,
             control: bool = False, log=print) -> dict:
    """Set up, measure and check one run of a cell; return the result
    dict (``checks`` last).  ``t_start`` is the process's start on
    ``time.perf_counter``'s clock."""
    import jax

    cell = find_cell(root, workload)
    devices = (check_devices(cell.chips) if require_chip
               else jax.devices()[:cell.chips])
    clock = CompileClock()
    ctx = Context(root, cell, seed, trace, devices, control=control, log=log,
                  t_phase=t_start)
    generator = load_generator(cell.traffic["generator"])
    ctx.run = run = generator.setup(ctx)

    trace_dir = root / ".bench_trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir), profiler_options=_trace_options())
    compiles0, compile_s0 = clock.count, clock.seconds
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    log(f"set-up {setup_s:.3f} s: {compiles0} compiles ({compile_s0:.3f} s), "
        f"{clock.hits} persistent-cache hits, cache "
        f"{jax.config.jax_compilation_cache_dir}")
    with span("bench.window"):
        run.window(seconds, span)
    window_s = time.perf_counter() - t0
    compiles = clock.count - compiles0
    if trace:
        jax.profiler.stop_trace()
    # a persistent-cache load counts as a compile here too
    log(f"window {window_s:.3f} s; compiles in window {compiles} "
        f"({clock.seconds - compile_s0:.3f} s)")
    peak = memory_peak(devices)

    checks = run.check()
    gc.collect()
    correct = all(c["ok"] for c in checks.values())

    if trace:
        from bench import trace as trace_lib

        ctx.summary = trace_lib.reduce(trace_lib.load_events(
            trace_lib.find_trace(str(trace_dir))))
        metrics = {}
        for m in cell.per_layer:
            value = load_reader(root, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(run.end_to_end(), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=ctx.summary.busy_s, window_s=ctx.summary.window_s)
        out["breakdown"] = ctx.summary.breakdown()
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    return out


def main(argv=None, *, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    root = BENCH.parent
    try:
        result = run_cell(root, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start,
                          log=lambda m: print(m, flush=True))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
