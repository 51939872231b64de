"""Structured tracing: one span primitive, on the profiler's clock, and a
bounded span ring with JSONL / Chrome export.

:func:`span` is the program's one way to time a block: it always opens a
``jax.profiler.TraceAnnotation``, so whenever a profiler session is
active (``ServeConfig.profile_dir``, or a caller's own
``jax.profiler.trace``) the span lands in the device trace on the same
clock as the device operations.  With no session active the annotation
costs about a microsecond.  Given a ``recorder`` (the service's ring,
``ServeConfig.trace``), the span is also appended to it.  Attributes are
ints or strings already at hand: computing them must add no device sync.

A :class:`SpanRecorder` is a fixed-capacity ``deque`` of closed spans —
``(name, t0, t1, attrs)`` on the ``time.perf_counter`` clock, the same
clock the serving layer stamps ``Request.t_submit`` with, so service
spans join offline against ``loadgen``'s per-request JSONL without any
clock translation.  The ring is the overhead contract: memory is bounded
by ``capacity`` regardless of uptime, recording is an O(1) append under
a lock, and nothing here ever touches a device (no syncs on the hot
path; the recorder is pure host bookkeeping).

Exports:

  * :meth:`SpanRecorder.to_jsonl` — one span per line, machine-joinable;
  * :meth:`SpanRecorder.to_chrome_trace` — the Chrome trace-event JSON
    array (``chrome://tracing`` / Perfetto ``ph:"X"`` complete events,
    microsecond timestamps).
"""
from __future__ import annotations

import collections
import dataclasses
import json
import threading
import time

import jax


@dataclasses.dataclass
class Span:
    name: str
    t0: float             # time.perf_counter seconds
    t1: float
    attrs: dict

    @property
    def duration_ms(self) -> float:
        return (self.t1 - self.t0) * 1e3

    def as_dict(self) -> dict:
        return {"name": self.name, "t0": self.t0, "t1": self.t1,
                "duration_ms": self.duration_ms, **self.attrs}


class SpanRecorder:
    """Bounded in-memory ring of closed spans (thread-safe)."""

    def __init__(self, capacity: int = 4096):
        self._ring: collections.deque = collections.deque(
            maxlen=max(1, int(capacity)))
        self._lock = threading.Lock()
        self._recorded = 0          # total ever recorded (ring may drop)

    @property
    def capacity(self) -> int:
        return self._ring.maxlen

    @property
    def recorded(self) -> int:
        return self._recorded

    def __len__(self) -> int:
        return len(self._ring)

    def record(self, name: str, t0: float, t1: float, **attrs) -> None:
        with self._lock:
            self._ring.append(Span(name, float(t0), float(t1), attrs))
            self._recorded += 1

    def span(self, name: str, **attrs) -> "_OpenSpan":
        """:func:`span` recording into this ring."""
        return span(name, self, **attrs)

    def snapshot(self) -> list:
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def counts(self) -> dict:
        """Spans per name currently in the ring (metrics surface)."""
        out: dict = {}
        for s in self.snapshot():
            out[s.name] = out.get(s.name, 0) + 1
        return out

    def to_jsonl(self, path) -> int:
        spans = self.snapshot()
        with open(path, "w") as f:
            for s in spans:
                f.write(json.dumps(s.as_dict(), sort_keys=True) + "\n")
        return len(spans)

    def to_chrome_trace(self, path) -> int:
        """Chrome trace-event 'X' (complete) events, ts/dur in µs.
        Thread id groups by span name so each pipeline stage gets its own
        track in the viewer."""
        spans = self.snapshot()
        tids = {}
        events = []
        for s in spans:
            tid = tids.setdefault(s.name, len(tids))
            events.append({
                "name": s.name, "ph": "X", "pid": 0, "tid": tid,
                "ts": s.t0 * 1e6, "dur": (s.t1 - s.t0) * 1e6,
                "args": s.attrs,
            })
        with open(path, "w") as f:
            json.dump(events, f)
        return len(events)


class _OpenSpan:
    """The context manager :func:`span` returns; :meth:`set` adds
    attributes known only once the block has started."""

    __slots__ = ("name", "recorder", "attrs", "_ann", "_t0")

    def __init__(self, name: str, recorder, attrs: dict):
        self.name = name
        self.recorder = recorder
        self.attrs = attrs

    def __enter__(self) -> "_OpenSpan":
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def __exit__(self, *exc) -> bool:
        self._ann.__exit__(*exc)
        if self.recorder is not None:
            self.recorder.record(self.name, self._t0, time.perf_counter(),
                                 **self.attrs)
        return False


def span(name: str, recorder: SpanRecorder | None = None,
         **attrs) -> _OpenSpan:
    """Time a block as a profiler annotation named ``name`` (with
    ``attrs`` as its stats) and, given ``recorder``, as a ring span on
    ``time.perf_counter``."""
    return _OpenSpan(name, recorder, attrs)
