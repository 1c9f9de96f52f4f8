"""Nested wall-time spans with a bounded ring buffer and Chrome-trace export.

``SpanTracer.span("epoch")`` is a context manager timing host wall-clock
only — no device syncs, no ``block_until_ready`` — so wrapping the train
loop in spans cannot serialize the dispatch pipeline it is measuring. What a
span *sees* is therefore host-side time: an epoch span covers dispatch +
drain, not device busy time (use the ``jax.profiler`` hooks in
:mod:`repro.obs.profiler` for device timelines).

Each span also opens a ``jax.profiler.TraceAnnotation`` with its name and
tags, so that while a profiler trace is open it lands on the host plane of
the ``.xplane.pb``, on the same clock as the device's operations. A span
tagged ``step_num`` is a ``StepTraceAnnotation``, which xprof's step view
reads. The annotation is opened only where ``jax`` is already imported:
importing :mod:`repro.obs` stays jax-free (the parallel-ingest worker
processes rely on that).

Completed spans land in a ``deque(maxlen=capacity)`` ring buffer (old spans
fall off; a week-long run cannot OOM on its own telemetry) and are
exportable as Chrome-trace JSON (``chrome://tracing`` / Perfetto's
"Open trace file").
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional


@dataclasses.dataclass
class Span:
    name: str
    t_start: float        # wall-clock seconds (time.time epoch)
    duration: float       # seconds, from perf_counter
    thread_id: int
    tags: Dict[str, Any]


def _annotation(name: str, tags: Dict[str, Any]):
    """The profiler's annotation of a span, or nothing where jax has not
    been imported (a profiler trace then cannot be open either)."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return contextlib.nullcontext()
    if "step_num" in tags:
        return profiler.StepTraceAnnotation(name, **tags)
    return profiler.TraceAnnotation(name, **tags)


class SpanTracer:
    def __init__(self, capacity: int = 8192):
        self.capacity = int(capacity)
        self.spans: deque = deque(maxlen=self.capacity)
        self._depth = threading.local()

    @contextmanager
    def span(self, name: str, on_close=None, **tags):
        """Time a block; record a :class:`Span` on exit (even on error).

        ``on_close(span)`` lets the recorder forward the completed span to
        its sinks without this module depending on them.
        """
        depth = getattr(self._depth, "d", 0)
        self._depth.d = depth + 1
        with _annotation(name, tags):
            t_wall = time.time()
            t0 = time.perf_counter()
            try:
                yield
            finally:
                dur = time.perf_counter() - t0
                self._depth.d = depth
                self.record(name, t_wall, dur, on_close=on_close, **tags)

    def record(self, name: str, t_start: float, duration: float,
               on_close=None, **tags) -> Span:
        """Record a span measured after the fact (``t_start`` on the wall
        clock, ``duration`` in seconds), such as a compile."""
        s = Span(name=name, t_start=t_start, duration=duration,
                 thread_id=threading.get_ident(), tags=dict(tags))
        self.spans.append(s)
        if on_close is not None:
            on_close(s)
        return s

    def clear(self):
        self.spans.clear()

    def chrome_trace(self) -> Dict[str, Any]:
        """The ring buffer as a Chrome-trace/Perfetto ``traceEvents`` dict.

        Complete events (``"ph": "X"``) with microsecond timestamps; the
        recording thread becomes the trace ``tid``, so loader read-ahead
        spans land on their own track next to the train loop's.
        """
        pid = os.getpid()
        events: List[Dict[str, Any]] = []
        for s in list(self.spans):
            events.append({
                "name": s.name, "ph": "X", "pid": pid, "tid": s.thread_id,
                "ts": s.t_start * 1e6, "dur": s.duration * 1e6,
                "cat": "clax", "args": s.tags,
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str) -> int:
        """Write the Chrome-trace JSON to ``path``; returns #events."""
        trace = self.chrome_trace()
        with open(path, "w", encoding="utf-8") as f:
            json.dump(trace, f)
        return len(trace["traceEvents"])
