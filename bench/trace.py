"""Reduce a profiler trace (`.xplane.pb`) to what the per-layer metrics read.

- `busy_seconds`: the union of the intervals in which an operation ran on
  a device, inside the window, averaged over the devices traced.
- `family_seconds`: device time of the operations whose name (on a TPU
  the op's HLO text, custom calls named after their jitted wrapper)
  matches a pattern; each per-layer metric keeps its own patterns.
- `top_ops`: the device operations that took most time, summed by name.
- `idle_gaps`: device idle time inside the window, attributed to the
  harness span (`bench.*` TraceAnnotation) that overlaps each gap most.

The window is the harness's `bench.window` span. Times are in seconds.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINES = ("XLA Ops",)
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_HEAD = re.compile(r"^(%[\w.\-]+) = \(?(\w+\[[^\]]*\])")


@dataclasses.dataclass(frozen=True, slots=True)
class Event:
    name: str               # on a TPU, the op's HLO text
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    ops: dict               # device plane name -> [Event], sorted by start
    spans: list             # host spans named bench.*, sorted by start
    window: tuple           # (start, end) of the bench.window span


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} .xplane.pb files under "
                                f"{trace_dir}, expected one")
    return paths[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: dict = {}
    spans = []
    names: dict = {}        # one string per distinct op, not per event
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = [Event(names.setdefault(ev.name, ev.name),
                         ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                   for line in plane.lines if line.name in OPS_LINES
                   for ev in line.events]
            ops[plane.name] = sorted(evs, key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            spans += [Event(ev.name, ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9)
                      for line in plane.lines for ev in line.events
                      if ev.name.startswith(SPAN_PREFIX)]
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW_SPAN} spans in {path}")
    if not any(ops.values()):
        raise ValueError(f"no device operation in {path}")
    spans = sorted((s for s in spans if s.name != WINDOW_SPAN),
                   key=lambda s: s.start)
    return Trace(ops, spans, (windows[0].start, windows[0].end))


def from_events(ops: dict, spans: list, window: tuple) -> Trace:
    """A Trace from events already in hand (tests build them by hand)."""
    return Trace({k: sorted(v, key=lambda e: e.start) for k, v in ops.items()},
                 sorted(spans, key=lambda s: s.start), window)


def merged(events, window) -> list:
    """Union of the events' intervals clipped to `window`, as sorted
    disjoint (start, end) pairs."""
    lo, hi = window
    out: list = []
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def window_seconds(trace: Trace) -> float:
    return trace.window[1] - trace.window[0]


def busy_seconds(trace: Trace) -> float:
    """Busy time inside the window, averaged over the devices traced."""
    per_device = [sum(b - a for a, b in merged(evs, trace.window))
                  for evs in trace.ops.values()]
    return sum(per_device) / len(per_device)


def _in_window(trace: Trace):
    lo, hi = trace.window
    for evs in trace.ops.values():
        for e in evs:
            if e.end > lo and e.start < hi:
                yield e


def family_seconds(trace: Trace, patterns) -> float:
    """Device time, summed over devices, of the window's operations whose
    name matches any of `patterns` (regular expressions)."""
    rx = re.compile("|".join(f"(?:{p})" for p in patterns))
    return sum(e.end - e.start for e in _in_window(trace) if rx.search(e.name))


def op_label(name: str) -> str:
    """`%name.N f32[shape]` from an XLA op's text, layouts left out."""
    m = OP_HEAD.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def top_ops(trace: Trace, n: int = 10) -> list:
    """[[op, seconds], ...]: device time summed by `op_label`."""
    by_name: dict = {}
    labels: dict = {}
    for e in _in_window(trace):
        key = labels.get(e.name) or labels.setdefault(e.name,
                                                      op_label(e.name))
        by_name[key] = by_name.get(key, 0.0) + (e.end - e.start)
    return [[k, v] for k, v in sorted(by_name.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """[[span, seconds], ...]: the window's device idle time, each gap
    given to the harness span that overlaps it most (`host.other` where
    none does), summed by span and averaged over devices. The harness's
    spans run one after another on one thread, so the spans that overlap
    a gap are the last to start before it and those that start inside it."""
    lo, hi = trace.window
    starts = [s.start for s in trace.spans]
    by_span: dict = {}
    for evs in trace.ops.values():
        edges = [lo] + [t for iv in merged(evs, trace.window) for t in iv] \
            + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            best, best_overlap = "host.other", 0.0
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            while i < len(starts) and starts[i] < b:
                s = trace.spans[i]
                overlap = min(b, s.end) - max(a, s.start)
                if overlap > best_overlap:
                    best, best_overlap = s.name, overlap
                i += 1
            by_span[best] = by_span.get(best, 0.0) + (b - a) / len(trace.ops)
    return [[k, v] for k, v in sorted(by_span.items(),
                                      key=lambda kv: -kv[1])[:n]]
