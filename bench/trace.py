"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device busy and idle time in the measured window, device time and
run count by program, and idle gaps labelled by the benchmark's host span
that was open while the device waited.

The window is the host span ``bench.window`` that the harness opens around
the measured loop.  A device is a plane whose name starts with
``/device:``; its busy time is the union of the intervals of its
``XLA Modules`` events (one per program run), clipped to the window.  A
program's device time is the sum of its ``XLA Modules`` events in the
window.  (The op-level line is not read: a serving window holds over a
million op events, and a program's runs already bound its busy time.)  Host spans are the events of the
host plane's threads whose names the benchmark itself writes (see
``SPANS``).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
SPANS = ("session.compile", "program.to_device", "program.mark_dirty",
         "bench.check_digest", "bench.release", "Server.submit",
         "Server.tick")
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                    # mean over the devices that ran
    devices: int
    program_s: Dict[str, float]      # device seconds by program name
    program_runs: Dict[str, int]
    gaps_s: Dict[str, float]         # idle seconds by open host span (mean)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def device_s(self, names: Iterable[str]) -> float:
        return sum(self.program_s.get(n, 0.0) for n in names)

    def runs(self, name: str) -> int:
        return self.program_runs.get(name, 0)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.program_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def program_name(event_name: str) -> str:
    """``jit_decode_step(1234)`` -> ``jit_decode_step``."""
    return _SUFFIX.sub("", event_name)


def find_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load_events(path: str) -> List[Event]:
    """The events the reduction reads: the devices' program runs, and
    the host threads' spans named in ``SPANS`` or ``WINDOW``."""
    from jax.profiler import ProfileData

    keep = set(SPANS) | {WINDOW}
    out: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name != "XLA Modules":
                continue
            for ev in line.events:
                if device or ev.name in keep:
                    out.append(Event(plane.name, line.name, ev.name,
                                     float(ev.start_ns), float(ev.duration_ns)))
    return out


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            if hi > merged[-1][1]:
                merged[-1] = (merged[-1][0], hi)
        else:
            merged.append((lo, hi))
    return merged


def _clip(lo: float, hi: float, w0: float, w1: float) -> Optional[Tuple[float, float]]:
    lo, hi = max(lo, w0), min(hi, w1)
    return (lo, hi) if hi > lo else None


def reduce(events: Sequence[Event]) -> Summary:
    windows = [e for e in events if e.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    w0, w1 = windows[0].start_ns, windows[0].end_ns
    spans = sorted((e for e in events if e.name in SPANS),
                   key=lambda e: e.start_ns)
    starts = [e.start_ns for e in spans]

    by_plane: Dict[str, Dict[str, List[Event]]] = collections.defaultdict(
        lambda: collections.defaultdict(list))
    for e in events:
        if e.plane.startswith("/device:"):
            by_plane[e.plane][e.line].append(e)

    program_s: Dict[str, float] = collections.Counter()
    program_runs: Dict[str, int] = collections.Counter()
    gaps_s: Dict[str, float] = collections.Counter()
    busy_total, devices = 0.0, 0
    for plane in sorted(by_plane):
        lines = by_plane[plane]
        for e in lines.get("XLA Modules", ()):
            iv = _clip(e.start_ns, e.end_ns, w0, w1)
            if iv:
                name = program_name(e.name)
                program_s[name] += (iv[1] - iv[0]) * 1e-9
                program_runs[name] += 1
        busy = _union([iv for e in lines.get("XLA Modules", ())
                       if (iv := _clip(e.start_ns, e.end_ns, w0, w1))])
        if not busy:
            continue
        devices += 1
        busy_total += sum(hi - lo for lo, hi in busy) * 1e-9
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for lo, hi in zip(edges[::2], edges[1::2]):
            if hi > lo:
                gaps_s[_open_span(spans, starts, (lo + hi) / 2)] += (hi - lo) * 1e-9
    window_s = (w1 - w0) * 1e-9
    n = max(devices, 1)
    return Summary(window_s, busy_total / n, devices, dict(program_s),
                   dict(program_runs), {k: v / n for k, v in gaps_s.items()})


def _open_span(spans: Sequence[Event], starts: Sequence[float],
               t: float) -> str:
    """The innermost benchmark span open at time ``t``: the latest-started
    span that still covers it (the benchmark's spans nest or follow each
    other, so a short look back finds it)."""
    i = bisect.bisect_right(starts, t) - 1
    for e in spans[max(0, i - 8):i + 1][::-1]:
        if t < e.end_ns:
            return e.name
    return "outside any span"
