"""``repro.obs`` — zero-sync observability: on-device metrics drained with
the loss stream, host wall-time spans with Chrome-trace export (annotated
into profiler traces too), compile spans, pluggable event sinks,
counters/gauges, process stats, and programmatic profiler windows. See
README "Observability"."""
from repro.obs.compiles import watch_compiles
from repro.obs.events import EVENT_KINDS, make_event, validate_event
from repro.obs.profiler import ProfileWindow, parse_profile_steps
from repro.obs.recorder import (Recorder, configure, get_recorder,
                                set_recorder, span)
from repro.obs.sinks import (ConsoleReporter, JsonlSink, MemorySink,
                             MetricsSink, read_jsonl)
from repro.obs.spans import Span, SpanTracer
from repro.obs.telemetry import TelemetryDrain

__all__ = [
    "EVENT_KINDS",
    "make_event",
    "validate_event",
    "Recorder",
    "configure",
    "get_recorder",
    "set_recorder",
    "span",
    "MetricsSink",
    "MemorySink",
    "JsonlSink",
    "ConsoleReporter",
    "read_jsonl",
    "Span",
    "SpanTracer",
    "TelemetryDrain",
    "ProfileWindow",
    "parse_profile_steps",
    "watch_compiles",
]
