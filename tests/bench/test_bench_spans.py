"""The program's own spans in a trace: seconds and self seconds by span,
device idle split at span boundaries, the byte counts on the spans, and
the per-layer readers built on them."""
import json
import time
import types
from pathlib import Path

import numpy as np
import pytest

from bench import harness, spans, trace

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6  # ns
HOST, DEV0, DEV1 = "/host:CPU", "/device:TPU:0", "/device:TPU:1"
READERS = ("pack_compare_ms", "pack_copy_ms", "fence_wait_ms", "compared_mb",
           "staged_mb")


def _ev(plane, name, start_ms, dur_ms, line=None):
    line = line or ("XLA Modules" if plane.startswith("/device:") else "python")
    return trace.Event(plane, line, name, start_ms * MS, dur_ms * MS)


# -- seconds and self seconds ----------------------------------------------

def test_span_seconds_and_self_seconds():
    events = [
        _ev(HOST, "bench.window", 10, 100),
        # a pass that starts before the window: clipped to it
        _ev(HOST, "TransferProgram.to_device", 0, 50),
        _ev(HOST, "TransferProgram.begin", 5, 30),
        _ev(HOST, "ArenaEntry.pack_host.compare", 12, 10),
        _ev(HOST, "ArenaEntry.pack_host.copy", 22, 5),
        _ev(HOST, "TransferProgram.barrier", 40, 8),
        # a second pass, and a span of another thread that overlaps it
        _ev(HOST, "TransferProgram.to_device", 60, 40),
        _ev(HOST, "ArenaEntry.pack_host.compare", 60, 40),
        _ev(HOST, "Server.tick", 70, 10, line="other"),
        _ev(DEV0, "jit__unpack(1)", 20, 5),
    ]
    total, self_s = spans.span_seconds(events)
    assert total["TransferProgram.to_device"] == pytest.approx(0.040 + 0.040)
    assert total["TransferProgram.begin"] == pytest.approx(0.025)
    assert total["ArenaEntry.pack_host.compare"] == pytest.approx(0.010 + 0.040)
    assert total["Server.tick"] == pytest.approx(0.010)
    # to_device less begin (25 in the window) and barrier (8); the second
    # pass is all compare (the longer of two spans that start together is
    # the outer one)
    assert self_s["TransferProgram.to_device"] == pytest.approx(0.040 - 0.025 - 0.008)
    assert self_s["TransferProgram.begin"] == pytest.approx(0.025 - 0.010 - 0.005)
    assert self_s["ArenaEntry.pack_host.compare"] == pytest.approx(0.050)
    assert self_s["Server.tick"] == pytest.approx(0.010)
    assert "bench.window" not in total


# -- idle by span --------------------------------------------------------------

def test_a_long_gap_is_split_among_the_nested_spans_it_crosses():
    events = [
        _ev(HOST, "bench.window", 0, 100),
        _ev(HOST, "TransferProgram.to_device", 10, 80),
        _ev(HOST, "TransferProgram.begin", 20, 50),
        _ev(HOST, "ArenaEntry.pack_host.compare", 30, 20),
        _ev(DEV0, "jit__unpack(1)", 0, 5),
        _ev(DEV0, "jit__unpack(1)", 95, 5),
    ]
    idle = spans.idle_by_span(events)
    # the one gap, 5-95: 5-10 and 90-95 outside, 10-20 and 70-90 in the
    # pass, 20-30 and 50-70 in begin, 30-50 in compare
    assert idle[spans.OUTSIDE] == pytest.approx(0.010)
    assert idle["TransferProgram.to_device"] == pytest.approx(0.030)
    assert idle["TransferProgram.begin"] == pytest.approx(0.030)
    assert idle["ArenaEntry.pack_host.compare"] == pytest.approx(0.020)


def test_a_gap_after_many_short_spans_goes_to_the_outer_span():
    """Nine short spans inside an outer one, then a gap: the outer span
    covers it.  ``_open_span``'s look-back of 8 does not reach the outer
    span; the sweep does."""
    events = [_ev(HOST, "bench.window", 0, 100),
              _ev(HOST, "program.to_device", 0, 100),
              _ev(DEV0, "jit__unpack(1)", 0, 20)]
    events += [_ev(HOST, "Server.tick", 1 + 2 * i, 1) for i in range(9)]
    idle = spans.idle_by_span(events)
    assert idle == {"program.to_device": pytest.approx(0.080)}
    assert trace.reduce(events).gaps_s == {spans.OUTSIDE: pytest.approx(0.080)}


def test_idle_by_span_sums_to_the_idle_time_of_each_device():
    events = [
        _ev(HOST, "bench.window", 10, 100),
        _ev(HOST, "Server.tick", 10, 40),
        _ev(HOST, "TransferProgram.to_device", 60, 50),
        _ev(HOST, "TransferProgram.barrier", 80, 5),
        _ev(DEV0, "jit_decode_step(12)", 5, 10),
        _ev(DEV0, "jit_decode_step(12)", 20, 6),
        _ev(DEV0, "jit_argmax(4)", 24, 6),
        _ev(DEV0, "jit__unpack(3)", 70, 20),
        _ev(DEV1, "jit__unpack(3)", 30, 40),
    ]
    s = trace.reduce(events)
    idle = spans.idle_by_span(events)
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s, abs=1e-12)
    # device 0 idle 15-20, 30-70, 90-110; device 1 10-30, 70-110
    assert idle["Server.tick"] == pytest.approx((0.005 + 0.020 + 0.020) / 2)
    assert idle[spans.OUTSIDE] == pytest.approx((0.010 + 0.0) / 2)
    assert idle["TransferProgram.barrier"] == pytest.approx(0.005 / 2)
    assert idle["TransferProgram.to_device"] == pytest.approx(
        (0.010 + 0.020 + 0.010 + 0.025) / 2)
    per_device = {}
    for plane in (DEV0, DEV1):
        one = [e for e in events if e.plane in (HOST, plane)]
        per_device[plane] = sum(spans.idle_by_span(one).values())
    assert per_device[DEV0] == pytest.approx(0.100 - 0.035)
    assert per_device[DEV1] == pytest.approx(0.100 - 0.040)


def test_a_recorded_chip_trace_split_at_span_boundaries():
    """The recorded v5e trace (``test_bench_trace``): the midpoint rule
    gives every idle gap to ``Server.tick``; the sweep gives the parts
    between ticks to no span."""
    events = trace.load_events(str(DATA / "tpu_v5e_20_steps.xplane.pb"))
    s = trace.reduce(events)
    idle = spans.idle_by_span(events)
    assert idle["Server.tick"] <= s.gaps_s["Server.tick"]
    assert idle[spans.OUTSIDE] == pytest.approx(
        s.gaps_s["Server.tick"] - idle["Server.tick"], abs=1e-9)
    assert set(idle) == {"Server.tick", spans.OUTSIDE}
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s, abs=1e-9)


# -- the program's spans in a CPU trace ------------------------------------------

POLICY = "cache/**=marshal+delta; **=marshal"


def _traced_passes(log_dir):
    """Two passes of a two-region program (three buckets in the marshal
    region, two in the delta region): cold, then one row of k changed."""
    import jax

    from bench.harness import _trace_options, span
    from repro.core import TransferSession

    rng = np.random.default_rng(1)
    tree = {"params": {"w": rng.standard_normal((16, 8)).astype(np.float32),
                       "b": rng.standard_normal(16).astype(np.float16),
                       "n": np.arange(3, dtype=np.int32)},
            "cache": {"k": rng.standard_normal((4, 8)).astype(np.float32),
                      "pos": np.arange(4, dtype=np.int32)}}
    program = TransferSession().compile(tree, POLICY)
    jax.profiler.start_trace(str(log_dir), profiler_options=_trace_options())
    with span("bench.window"):
        jax.block_until_ready(program.to_device(tree))
        tree["cache"]["k"][2] += 1.0
        program.mark_dirty(tree, "cache")
        jax.block_until_ready(program.to_device(tree))
    jax.profiler.stop_trace()
    return spans.load(trace.find_trace(str(log_dir)))


def test_a_traced_pass_writes_the_program_spans_nested_in_it(tmp_path):
    loaded = _traced_passes(tmp_path)
    events = [e for e in loaded if e.name in spans.PROGRAM_SPANS]
    names = {e.name for e in events}
    # no fence is in flight on this path, so no fence is waited
    assert names == set(spans.PROGRAM_SPANS) - {spans.FENCE_WAIT}
    assert all("#" not in e.name for e in events)
    passes = sorted((e for e in events if e.name == spans.TO_DEVICE),
                    key=lambda e: e.start_ns)
    assert len(passes) == 2

    def inside(e, outer):
        return (e.plane, e.line) == (outer.plane, outer.line) and \
            outer.start_ns <= e.start_ns and e.end_ns <= outer.end_ns

    for e in events:
        if e.name != spans.TO_DEVICE:
            assert any(inside(e, p) for p in passes), e.name
    begins = [e for e in events if e.name == "TransferProgram.begin"]
    for e in events:
        if e.name in (spans.COMPARE, spans.COPY, "TransferScheme.device_put"):
            assert any(inside(e, b) for b in begins), e.name
    # per pass: one compare per region; one copy per bucket that changed
    # (all five when cold; the f32 bucket of the cache region after)
    compares = [[e for e in events if e.name == spans.COMPARE and inside(e, p)]
                for p in passes]
    copies = [[e for e in events if e.name == spans.COPY and inside(e, p)]
              for p in passes]
    assert [len(c) for c in compares] == [2, 2]
    assert [len(c) for c in copies] == [5, 1]
    assert copies[1][0].stat("dtype") == "float32"
    assert copies[1][0].stat("bytes") == 4 * 8 * 4
    # bytes compared: nothing cold; every leaf of both regions after
    assert [sum(e.stat("bytes") for e in c) for c in compares] == [
        0, 512 + 32 + 12 + 128 + 16]
    s = spans.summarize(loaded)
    assert s.bytes_by_span[spans.COPY] == 512 + 32 + 12 + 128 + 16 + 128


# -- the readers -------------------------------------------------------------

def _ctx(root, cell="cell.x", passes=2, summary=True):
    run = types.SimpleNamespace(counters={"passes": passes})
    return types.SimpleNamespace(
        root=root, cell=types.SimpleNamespace(name=cell), run=run,
        summary=object() if summary else None, log=lambda m: None)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_reports_nothing_without_its_input(tmp_path, name):
    import jax

    from bench.harness import _trace_options, span

    read = harness.load_reader(Path(harness.BENCH.parent), name)
    # no trace summary, no run
    assert read(_ctx(tmp_path, summary=False)) is None
    assert read(types.SimpleNamespace(summary=None, run=None)) is None
    # a trace of a program that writes no spans of its own: the window and
    # the benchmark's span around a pass, nothing inside it
    log_dir = tmp_path / ".bench_trace" / "cell.x"
    jax.profiler.start_trace(str(log_dir), profiler_options=_trace_options())
    with span("bench.window"):
        with span("program.to_device"):
            jax.block_until_ready(jax.numpy.ones(4) + 1)
    jax.profiler.stop_trace()
    assert read(_ctx(tmp_path)) is None


@pytest.mark.parametrize("cell", ["tiny-ssm.stage", "tiny-hybrid.resume"])
def test_the_traced_run_of_a_cell_reports_the_new_metrics(tiny_root, cell):
    """All five read in a traced run; the bytes per pass match the closed
    form of the configuration's leaf sizes: a stage pass compares the
    params (its cache region is skipped by identity), a resume pass
    compares the params and, after mark_dirty, the whole cache."""
    from bench import costs

    out = harness.run_cell(tiny_root, cell, 2 ** 31 + 9, 0.3, True,
                           t_start=time.perf_counter(), require_chip=False,
                           log=lambda m: None)
    assert out["correct"], out["checks"]
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(READERS) <= set(metrics)
    c = harness.find_cell(tiny_root, cell)
    tr = c.traffic
    params = costs.params_bytes(c.family, c.config)
    cache = sum(costs.cache_bytes(c.family, c.config, tr["slots"],
                                  tr["max_seq"]).values())
    if tr["op"] == "stage":
        assert metrics["compared_mb"] == params / 1e6
        assert metrics["staged_mb"] == 0.0
        assert metrics["pack_copy_ms"] == 0.0
    else:
        assert metrics["compared_mb"] == (params + cache) / 1e6
        assert 0.0 < metrics["staged_mb"] <= cache / 1e6
        assert metrics["pack_copy_ms"] > 0.0
    assert metrics["pack_compare_ms"] > 0.0
    assert metrics["fence_wait_ms"] >= 0.0
    json.dumps(out)
