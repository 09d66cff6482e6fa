"""The program's own host spans in a traced run's window: seconds and self
seconds by span, the byte counts the program puts on its spans, and each
device's idle time split at span boundaries.

The program (``src/repro/core/``) opens these spans on the blocking path
of a transfer pass, nested on the caller's thread:

* ``TransferProgram.to_device`` -- the whole pass, around
  ``TransferProgram.begin`` (every region's pack and enqueue),
  ``TransferProgram.barrier`` (the pass's one ``block_until_ready``) and
  ``TransferProgram.finish`` (per-region bookkeeping, unpack dispatch);
* ``ArenaEntry.pack_host.compare`` -- a region's memcmp against staging,
  with ``bytes`` = the bytes compared;
* ``ArenaEntry.pack_host.fence_wait`` -- a wait on a staging buffer's
  fence before a rotation (only when there is one to wait);
* ``ArenaEntry.pack_host.copy`` -- one bucket's memcpy into its spare
  staging buffer, with ``bytes`` = the bytes copied;
* ``TransferScheme.device_put`` -- the ``device_put`` calls of a region.

The profiler keeps a span's keyword metadata as the event's stats and its
name without them.  A trace of a program that writes none of these spans
gives ``None`` for every reading here, so the readers report nothing.
"""
from __future__ import annotations

import bisect
import dataclasses
import heapq
import json
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace

TO_DEVICE = "TransferProgram.to_device"
COMPARE = "ArenaEntry.pack_host.compare"
FENCE_WAIT = "ArenaEntry.pack_host.fence_wait"
COPY = "ArenaEntry.pack_host.copy"
PROGRAM_SPANS = (TO_DEVICE, "TransferProgram.begin", "TransferProgram.barrier",
                 "TransferProgram.finish", COMPARE, FENCE_WAIT, COPY,
                 "TransferScheme.device_put")
OUTSIDE = "outside any span"


@dataclasses.dataclass(frozen=True)
class Span(trace.Event):
    meta: Tuple[Tuple[str, object], ...] = ()

    def stat(self, key: str):
        return dict(self.meta).get(key)


@dataclasses.dataclass
class SpanSummary:
    span_s: Dict[str, float]          # seconds of each span in the window
    span_self_s: Dict[str, float]     # less the part its child spans cover
    bytes_by_span: Dict[str, int]     # sum of the spans' ``bytes`` stat
    idle_by_span_s: Dict[str, float]  # device idle by innermost span (mean)


def load(path: str) -> List[trace.Event]:
    """The devices' program runs, the window, and the host spans of the
    benchmark (``trace.SPANS``) and the program (with their stats), in one
    pass over the trace."""
    from jax.profiler import ProfileData

    keep = set(trace.SPANS + PROGRAM_SPANS) | {trace.WINDOW}
    out: List[trace.Event] = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name != "XLA Modules":
                continue
            for ev in line.events:
                if device:
                    out.append(trace.Event(plane.name, line.name, ev.name,
                                           float(ev.start_ns),
                                           float(ev.duration_ns)))
                    continue
                name = ev.name.partition("#")[0]
                if name in keep:
                    out.append(Span(plane.name, line.name, name,
                                    float(ev.start_ns), float(ev.duration_ns),
                                    _stats(ev)))
    return out


def _stats(ev) -> Tuple[Tuple[str, object], ...]:
    # the profiler's stats type is built on first use and warns then
    # (no ``__module__``); under ``-W error`` that warning aborts the process
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", "builtin type", DeprecationWarning)
        return tuple(ev.stats)


def _window(events: Sequence[trace.Event]) -> Tuple[float, float]:
    windows = [e for e in events if e.name == trace.WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {trace.WINDOW} span, found {len(windows)}")
    return windows[0].start_ns, windows[0].end_ns


def _host_spans(events: Sequence[trace.Event]) -> List[trace.Event]:
    return [e for e in events
            if not e.plane.startswith("/device:") and e.name != trace.WINDOW]


def span_seconds(events: Sequence[trace.Event]
                 ) -> Tuple[Dict[str, float], Dict[str, float]]:
    """Seconds of each named host span inside the window, and its self
    seconds: that less the part its child spans (those it encloses on the
    same thread) cover."""
    w0, w1 = _window(events)
    total: Dict[str, float] = {}
    self_s: Dict[str, float] = {}
    by_thread: Dict[Tuple[str, str], List[trace.Event]] = {}
    for e in _host_spans(events):
        by_thread.setdefault((e.plane, e.line), []).append(e)
    for spans in by_thread.values():
        stack: List[trace.Event] = []
        for e in sorted(spans, key=lambda e: (e.start_ns, -e.dur_ns)):
            while stack and stack[-1].end_ns <= e.start_ns:
                stack.pop()
            iv = trace._clip(e.start_ns, e.end_ns, w0, w1)
            if iv is not None:
                s = (iv[1] - iv[0]) * 1e-9
                total[e.name] = total.get(e.name, 0.0) + s
                self_s[e.name] = self_s.get(e.name, 0.0) + s
                if stack:   # inside the window too, as it holds this one
                    self_s[stack[-1].name] -= s
            stack.append(e)
    return total, self_s


def idle_by_span(events: Sequence[trace.Event]) -> Dict[str, float]:
    """Each device's idle time in the window, split at host span
    boundaries: every idle piece goes to the innermost (latest-started)
    span that covers it, else to ``"outside any span"``; the mean over the
    devices that ran.  Per device the values sum to its idle seconds."""
    w0, w1 = _window(events)
    # by start, the longer first: of two spans that start together the
    # later in this order is the inner one
    spans = sorted(((iv[0], iv[1], e.name) for e in _host_spans(events)
                    if (iv := trace._clip(e.start_ns, e.end_ns, w0, w1))),
                   key=lambda t: (t[0], t[0] - t[1]))
    runs: Dict[str, List[Tuple[float, float]]] = {}
    for e in events:
        if e.plane.startswith("/device:") and e.line == "XLA Modules":
            iv = trace._clip(e.start_ns, e.end_ns, w0, w1)
            if iv:
                runs.setdefault(e.plane, []).append(iv)
    out: Dict[str, float] = {}
    devices = 0
    for plane in sorted(runs):
        busy = trace._union(runs[plane])
        devices += 1
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        idle = [(lo, hi) for lo, hi in zip(edges[::2], edges[1::2]) if hi > lo]
        for label, s in _split(idle, spans).items():
            out[label] = out.get(label, 0.0) + s
    n = max(devices, 1)
    return {k: v / n for k, v in out.items()}


def _split(idle: List[Tuple[float, float]],
           spans: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Sweep the idle intervals (sorted, disjoint) against the spans
    (sorted by start), crediting each piece to the latest-started span
    open over it."""
    cuts = sorted({t for lo, hi, _ in spans for t in (lo, hi)})
    out: Dict[str, float] = {}
    active: List[Tuple[float, int, float, str]] = []  # (-start, -i, end, name)
    nxt = 0
    for lo, hi in idle:
        points = [lo]
        j = bisect.bisect_right(cuts, lo)
        while j < len(cuts) and cuts[j] < hi:
            points.append(cuts[j])
            j += 1
        points.append(hi)
        for a, b in zip(points, points[1:]):
            while nxt < len(spans) and spans[nxt][0] <= a:
                s0, s1, name = spans[nxt]
                heapq.heappush(active, (-s0, -nxt, s1, name))
                nxt += 1
            while active and active[0][2] <= a:
                heapq.heappop(active)
            label = active[0][3] if active else OUTSIDE
            out[label] = out.get(label, 0.0) + (b - a) * 1e-9
    return out


def summarize(events: Sequence[trace.Event]) -> SpanSummary:
    w0, w1 = _window(events)
    span_s, self_s = span_seconds(events)
    nbytes: Dict[str, int] = {}
    for e in _host_spans(events):
        if isinstance(e, Span) and w0 <= e.start_ns < w1:
            b = e.stat("bytes")
            if b is not None:
                nbytes[e.name] = nbytes.get(e.name, 0) + int(b)
    return SpanSummary(span_s, self_s, nbytes, idle_by_span(events))


_CACHE: Dict[str, SpanSummary] = {}


def of_run(ctx) -> Optional[SpanSummary]:
    """The span summary of a traced run's window (read once per trace and
    logged), or ``None`` when the run has no trace, no passes, or its
    program writes no spans."""
    if ctx.summary is None or not getattr(ctx.run, "counters", {}).get("passes"):
        return None
    path = trace.find_trace(str(ctx.root / ".bench_trace" / ctx.cell.name))
    if path not in _CACHE:
        _CACHE[path] = summarize(load(path))
        s = _CACHE[path]
        ctx.log("program spans: " + json.dumps(
            {"span_s": s.span_s, "span_self_s": s.span_self_s,
             "bytes_by_span": s.bytes_by_span,
             "idle_by_span_s": s.idle_by_span_s}))
    s = _CACHE[path]
    return s if TO_DEVICE in s.span_s else None


def _per_pass(ctx, table: str, name: str) -> Optional[float]:
    s = of_run(ctx)
    if s is None:
        return None
    return getattr(s, table).get(name, 0) / ctx.run.counters["passes"]


def seconds_per_pass(ctx, name: str) -> Optional[float]:
    """A span's seconds in the window over the passes in it: 0 when the
    program writes spans but never opened this one."""
    return _per_pass(ctx, "span_s", name)


def bytes_per_pass(ctx, name: str) -> Optional[float]:
    """The ``bytes`` on a span's events in the window over the passes in
    it: 0 when the program writes spans but never opened this one."""
    return _per_pass(ctx, "bytes_by_span", name)
