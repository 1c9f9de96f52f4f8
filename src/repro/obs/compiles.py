"""Compile spans from JAX's own compile events.

:func:`watch_compiles` registers, once per process, a ``jax.monitoring``
duration listener. Each stage JAX times (trace to jaxpr, lower to MLIR, and
the backend compile, which is also where a persistent-cache hit is loaded)
becomes one ``compile`` span on the process-global recorder, tagged with its
``stage`` and the function's name (``fun``), and adds to the counters
``compile.seconds`` (every stage) and ``compile.count`` (backend compiles
and cache loads: one per executable). The span is recorded when the event
arrives, after the fact, so it carries no profiler annotation.

Registration is lazy, never at import: importing :mod:`repro.obs` stays
jax-free.
"""
from __future__ import annotations

import threading
import time

from repro.obs.recorder import get_recorder

STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}

# jax.monitoring's listeners are process-global and cannot be listed by
# owner, so whether ours is registered is process state too.
_lock = threading.Lock()
_watching = False


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    stage = STAGES.get(event)
    if stage is None:
        return
    rec = get_recorder()
    rec.record_span("compile", time.time() - duration_secs, duration_secs,
                    stage=stage, fun=str(kwargs.get("fun_name", "")))
    rec.add("compile.seconds", duration_secs)
    if stage == "backend":
        rec.add("compile.count")


def watch_compiles() -> None:
    """Record every later compile of this process (idempotent)."""
    global _watching
    with _lock:
        if _watching:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _watching = True
