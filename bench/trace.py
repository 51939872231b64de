"""Profiler capture and the reduction from a trace to numbers.

A trace is read into plain event lists (``load``), so that the
reduction runs the same on a recorded trace in the tests as on the
chip's.  Device operations are the events on the ``OPS_LINE`` line of
each ``/device:TPU:<i>`` plane; host spans are the benchmark's own
``TraceAnnotation`` events (names starting with ``bench.``) on the host
plane.  The profiler puts both on one clock, in nanoseconds.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start: float             # ns
    dur: float               # ns
    args: dict

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    device: dict             # chip index -> [Event] device operations
    spans: list              # [Event] benchmark host spans
    window: tuple            # (start, end) ns of the traced window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def to_json(self) -> dict:
        ev = lambda e: [e.name, e.start, e.dur, e.args]   # noqa: E731
        return {"device": {str(c): [ev(e) for e in evs]
                           for c, evs in self.device.items()},
                "spans": [ev(e) for e in self.spans],
                "window": list(self.window)}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        ev = lambda x: Event(x[0], float(x[1]), float(x[2]), dict(x[3]))  # noqa: E731
        return cls({int(c): [ev(x) for x in evs]
                    for c, evs in d["device"].items()},
                   [ev(x) for x in d["spans"]], tuple(d["window"]))


@contextlib.contextmanager
def capture(logdir: str):
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _value(v):
    return v if isinstance(v, (int, float, str)) else str(v)


def load(logdir: str) -> Trace:
    """Read the ``.xplane.pb`` the profiler wrote under ``logdir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    device, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                device.setdefault(int(m.group(1)), []).extend(
                    Event(e.name, float(e.start_ns), float(e.duration_ns),
                          {k: _value(v) for k, v in e.stats})
                    for e in line.events)
            elif not m and plane.name.startswith("/host"):
                spans.extend(
                    Event(e.name, float(e.start_ns), float(e.duration_ns),
                          {k: _value(v) for k, v in e.stats})
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX))
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if len(win) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN!r} span, "
                           f"found {len(win)}")
    return Trace(device, spans, (win[0].start, win[0].end))


def describe(logdir: str, path: str, limit: int = 40) -> None:
    """Write the planes, lines and first events of a trace as JSON: what
    to look at by hand before trusting names in the reduction."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = ProfileData.from_file(paths[0])
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"line": line.name, "events": len(evs), "first": [
                {"name": e.name, "start_ns": e.start_ns,
                 "dur_ns": e.duration_ns,
                 "stats": {k: _value(v) for k, v in e.stats}}
                for e in evs[:limit]]})
        out.append({"plane": plane.name, "lines": lines})
    with open(path, "w") as f:
        json.dump(out, f, indent=1, default=str)


# --- reduction ---------------------------------------------------------------


def merge(intervals) -> list:
    """Union of [start, end) intervals, as sorted disjoint pairs."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def busy(trace: Trace, chip: int, lo=None, hi=None) -> list:
    """Merged intervals in which an operation ran on ``chip``, clipped to
    [lo, hi) (default: the traced window)."""
    lo = trace.window[0] if lo is None else lo
    hi = trace.window[1] if hi is None else hi
    return clip(merge((e.start, e.end) for e in trace.device.get(chip, [])),
                lo, hi)


def busy_s(trace: Trace) -> float:
    """Busy seconds in the window, averaged over the chips traced."""
    chips = sorted(trace.device) or [0]
    return sum(length(busy(trace, c)) for c in chips) * 1e-9 / len(chips)


def idle_gaps(trace: Trace, chip: int = 0, top: int = 10) -> list:
    """The ``top`` longest gaps between device operations in the window,
    each labelled by the innermost benchmark span that holds its middle
    (``idle`` where none does): [[label, seconds], ...]."""
    lo, hi = trace.window
    edges = [lo] + [x for iv in busy(trace, chip) for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [s for s in trace.spans if s.name != WINDOW_SPAN]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (s + e)
        holding = [sp for sp in spans if sp.start <= mid < sp.end]
        label = min(holding, key=lambda sp: sp.dur).name if holding \
            else "idle"
        out.append([label, (e - s) * 1e-9])
    return out


_LAYOUT = re.compile(r"\{[^{}]*\}")
_INSTR = re.compile(r"^%?([\w\-]+?)(?:\.\d+)? = (.*?) [\w\-]+\(")


def short_name(hlo: str) -> str:
    """An operation's instruction name without its number, and its result
    type without layouts: ``fused_range_pallas (s32[8,2097152],
    f32[8,2097152])`` for the TPU profiler's full HLO text."""
    m = _INSTR.match(hlo)
    if not m:
        return hlo[:120]
    result = _LAYOUT.sub("", _LAYOUT.sub("", m.group(2)))
    return f"{m.group(1)} {result}"


def top_ops(trace: Trace, chip: int = 0, top: int = 10) -> list:
    """Device operations by total time in the window, grouped by
    :func:`short_name`: [[name, seconds], ...]."""
    lo, hi = trace.window
    tot = {}
    for e in trace.device.get(chip, []):
        d = min(e.end, hi) - max(e.start, lo)
        if d > 0:
            name = short_name(e.name)
            tot[name] = tot.get(name, 0.0) + d
    return [[n, t * 1e-9] for n, t in
            sorted(tot.items(), key=lambda x: -x[1])[:top]]


def ops_matching(trace: Trace, pattern: str, chip: int = 0) -> list:
    """Device operations in the window whose name matches ``pattern``."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    return [e for e in trace.device.get(chip, [])
            if rx.search(e.name) and lo <= e.start < hi]
