"""The reduction from a profiler trace to busy/idle time, device time by
program, and idle gaps by the host span that was open."""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6  # ns


def _ev(plane, line, name, start_ms, dur_ms):
    return trace.Event(plane, line, name, start_ms * MS, dur_ms * MS)


def test_reduce_counts_busy_union_programs_and_gaps():
    dev0, dev1, host = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
    events = [
        _ev(host, "python", "bench.window", 10, 100),
        _ev(host, "python", "Server.tick", 10, 40),
        _ev(host, "python", "program.to_device", 60, 50),
        # device 0: overlapping program runs, one starting before the
        # window; op events are not read
        _ev(dev0, "XLA Modules", "jit_decode_step(12)", 5, 10),
        _ev(dev0, "XLA Modules", "jit_decode_step(12)", 20, 6),
        _ev(dev0, "XLA Modules", "jit_argmax(4)", 24, 6),
        _ev(dev0, "XLA Modules", "jit__unpack(3)", 70, 20),
        _ev(dev0, "XLA Ops", "fusion.9", 40, 20),
        _ev(dev1, "XLA Modules", "jit__unpack(3)", 30, 40),
    ]
    s = trace.reduce(events)
    assert s.window_s == pytest.approx(0.100)
    assert s.devices == 2
    # device 0 busy 5 + 10 + 20 = 35 ms inside the window; device 1: 40 ms
    assert s.busy_s == pytest.approx((0.035 + 0.040) / 2)
    assert s.idle_share == pytest.approx(1 - 0.0375 / 0.1)
    assert s.program_s["jit_decode_step"] == pytest.approx(0.005 + 0.006)
    assert s.runs("jit_decode_step") == 2
    assert s.device_s(["jit__unpack"]) == pytest.approx(0.060)
    # idle, by the span open at each gap's middle, averaged over devices:
    # device 0: 15-20 (tick), 30-70 (middle 50: the tick has ended, the
    # pass not begun), 90-110 (to_device); device 1: 10-30 (tick),
    # 70-110 (to_device)
    assert s.gaps_s["Server.tick"] == pytest.approx((0.005 + 0.020) / 2)
    assert s.gaps_s["program.to_device"] == pytest.approx((0.020 + 0.040) / 2)
    assert s.gaps_s["outside any span"] == pytest.approx(0.040 / 2)
    bd = s.breakdown()
    assert bd["device_ops"][0][0] == "jit__unpack"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        trace.reduce([_ev("/device:TPU:0", "XLA Modules", "f", 0, 1)])


def test_program_names_lose_their_run_suffix():
    assert trace.program_name("jit_decode_step(1234)") == "jit_decode_step"
    assert trace.program_name("jit__unpack") == "jit__unpack"


def test_a_recorded_host_trace_gives_the_window_and_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    from bench.harness import _trace_options, span

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=_trace_options())
    with span("bench.window"):
        for _ in range(3):
            with span("Server.tick"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = trace.load_events(trace.find_trace(str(tmp_path)))
    names = [e.name for e in events]
    assert names.count("bench.window") == 1 and names.count("Server.tick") == 3
    s = trace.reduce(events)
    assert s.window_s > 0


def test_a_recorded_chip_trace():
    """Recorded on one TPU v5e: 20 runs of a jitted bf16 2048x2048 matmul
    (``jit_step``), each in a ``Server.tick`` span with a 2 ms sleep,
    inside ``bench.window``.  The device timeline reads about 1 ms earlier
    than the host spans in this trace, so the first run falls just before
    the window."""
    s = trace.reduce(trace.load_events(str(DATA / "tpu_v5e_20_steps.xplane.pb")))
    assert s.devices == 1
    assert s.runs("jit_step") == 19
    assert s.busy_s == pytest.approx(s.program_s["jit_step"])
    assert 0.0015 < s.busy_s < 0.002
    assert s.window_s == pytest.approx(0.0655, abs=1e-3)
    assert s.gaps_s["Server.tick"] == pytest.approx(s.window_s - s.busy_s)
