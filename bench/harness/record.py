"""What a run hands to the per-layer reducers and to the result line."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

from harness.compare import Check
from harness.xtrace import DeviceFigures, Trace


@dataclasses.dataclass
class Record:
    cell: Any                              # harness.cell.Cell
    end_to_end: Dict[str, float]           # host-clock metrics, by name
    attempted: int
    failed: int
    checks: List[Check]
    memory_peak_bytes: int
    device_kind: str = ""
    window_s: float = 0.0                  # the measured window, host clock
    steps: int = 0                         # optimizer steps in the window
    spans: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    trace_dir: Optional[Any] = None        # the profiler's log directory
    trace: Optional[Trace] = None
    devices: List[DeviceFigures] = dataclasses.field(default_factory=list)
    steps_per_execution: int = 0           # steps in one run of the step program
    notes: List[str] = dataclasses.field(default_factory=list)
    # end-to-end figures reported beside the metrics, held to no bound:
    # {name: {"value": ..., "unit": ...}}
    unbounded: Dict[str, Dict[str, Any]] = dataclasses.field(
        default_factory=dict)

    def spans_named(self, name: str) -> List[Dict[str, Any]]:
        return [s for s in self.spans if s["name"] == name]

    def mean_device(self, field: str) -> Optional[float]:
        if not self.devices:
            return None
        return sum(getattr(d, field) for d in self.devices) / len(self.devices)
