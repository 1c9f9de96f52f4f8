"""A sink on the program's recorder that stamps what the benchmark reads.

The program emits per-step ``train_step`` metrics when it fetches a chunk's
losses, and wall-time spans (``shard_read``, ``serve_batch``, ...). The tap
keeps each with the host clock at which it arrived, and calls ``hook`` on
each ``train_step``, so that a driver can open its window, or a profiler
trace, from the program's own thread.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

# time.time() - time.perf_counter(): span starts come on the wall clock.
WALL_MINUS_PERF = time.time() - time.perf_counter()


class Tap:
    def __init__(self, hook: Optional[Callable[[Dict[str, Any]], None]] = None):
        self.steps: List[Dict[str, Any]] = []   # train_step, with "at"
        self.spans: List[Dict[str, Any]] = []   # spans, with "start"/"end"
        self.hook = hook
        self._lock = threading.Lock()

    def emit(self, event: Dict[str, Any]) -> None:
        now = time.perf_counter()
        kind, name = event.get("kind"), event.get("name")
        if kind == "metric" and name == "train_step":
            with self._lock:
                self.steps.append(dict(event, at=now))
        elif kind == "span":
            start = float(event["t"]) - WALL_MINUS_PERF
            with self._lock:
                self.spans.append(dict(event, start=start,
                                       end=start + float(event["value"])))
        else:
            return
        if self.hook is not None and name == "train_step":
            self.hook(event)

    def close(self) -> None:
        pass
