"""Device figures from a JAX profiler trace (``.xplane.pb``).

Each TPU has a plane ``/device:TPU:<n>`` whose ``XLA Modules`` line holds
one event per execution of a compiled program and whose ``XLA Ops`` line
holds the operations inside them (a loop's own event spans the operations
it runs; only operations that hold no other count). The host's planes hold what the runtime
was doing on each thread.

The traced window is bounded by device events, never by the profiler's own
start and stop: with ``step_program`` set, it runs from the start of the
first complete execution of the step program to the end of the last (the
first and last executions in the trace may be cut by the profiler's start
and stop, so they are dropped); without it, from the first operation to the
last. Busy time is the union of the operations' intervals inside the window.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|psum|allreduce", re.I)

Interval = Tuple[int, int, str]   # start ns, end ns, name


def union(intervals) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi), n) for s, e, n in intervals
            if e > lo and s < hi]


def length(merged) -> int:
    return sum(e - s for s, e in merged)


def subtract(a, b) -> List[Tuple[int, int]]:
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Interval]
    modules: List[Interval]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Interval]          # host runtime events, all threads
    path: str = ""


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {files}")
    return files[0]


def _events(line) -> List[Interval]:
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
            for e in line.events]


def short_name(hlo: str) -> str:
    """``%fusion.3 = f32[2560]{...} fusion(...)`` -> ``%fusion.3 = f32[2560]{...}``."""
    return " ".join(hlo.split(" ")[:3])[:160]


def leaf_ops(events: List[Interval]) -> List[Interval]:
    """Operations that hold no other operation: a ``while`` or a ``call``
    on the same line spans the operations inside it, and would count their
    gaps as busy."""
    ordered = sorted(events, key=lambda e: (e[0], -e[1]))
    return [e for e, nxt in zip(ordered, ordered[1:] + [None])
            if nxt is None or not (nxt[0] < e[1] and nxt[1] <= e[1])]


def load(log_dir: str) -> Trace:
    import jax

    path = find_xplane(log_dir)
    data = jax.profiler.ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: _events(line) for line in plane.lines}
            ops = [(b, e, short_name(n)) for b, e, n in lines.get(OPS_LINE, [])]
            devices.append(Device(plane.name, leaf_ops(ops),
                                  sorted(lines.get(MODULES_LINE, []))))
        elif plane.name.startswith("/host:") and plane.name != "/host:metadata":
            for line in plane.lines:
                if line.name != "python":
                    host.extend(_events(line))
    devices.sort(key=lambda d: int(DEVICE_PLANE.match(d.name).group(1)))
    return Trace(devices=devices, host=sorted(host), path=path)


def inventory(log_dir: str) -> List[str]:
    """Planes, lines and event counts: what a trace holds, for a reader."""
    import jax

    data = jax.profiler.ProfileData.from_file(find_xplane(log_dir))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            ev = list(line.events)
            if ev:
                out.append(f"{plane.name} | {line.name} | {len(ev)} events | "
                           f"first {ev[0].name!r} at {ev[0].start_ns}")
    return out


def step_program(dev: Device) -> Optional[str]:
    """The program that took the most device time."""
    total: Dict[str, int] = {}
    for s, e, n in dev.modules:
        total[n] = total.get(n, 0) + (e - s)
    return max(total, key=total.get) if total else None


@dataclasses.dataclass
class Window:
    start: int
    end: int
    executions: int       # complete executions of the step program in it

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


def window(dev: Device, by_program: bool) -> Optional[Window]:
    if by_program:
        name = step_program(dev)
        runs = [m for m in dev.modules if m[2] == name][1:-1]
        if not runs:
            return None
        return Window(runs[0][0], runs[-1][1], len(runs))
    if not dev.ops:
        return None
    return Window(dev.ops[0][0], max(e for _, e, _ in dev.ops), 0)


@dataclasses.dataclass
class DeviceFigures:
    window_s: float
    busy_s: float
    executions: int
    collective_s: float
    collective_exposed_s: float
    op_seconds: Dict[str, float]
    gaps: List[Tuple[int, int]]


def figures(dev: Device, by_program: bool) -> Optional[DeviceFigures]:
    w = window(dev, by_program)
    if w is None or w.end <= w.start:
        return None
    ops = clip(dev.ops, w.start, w.end)
    busy = union(ops)
    coll = union([o for o in ops if COLLECTIVE.search(o[2])])
    compute = union([o for o in ops if not COLLECTIVE.search(o[2])])
    per_op: Dict[str, float] = {}
    for s, e, n in ops:
        per_op[n] = per_op.get(n, 0.0) + (e - s) * 1e-9
    return DeviceFigures(
        window_s=w.seconds, busy_s=length(busy) * 1e-9, executions=w.executions,
        collective_s=length(coll) * 1e-9,
        collective_exposed_s=length(subtract(coll, compute)) * 1e-9,
        op_seconds=per_op, gaps=subtract([(w.start, w.end)], busy))


def host_activity(host: List[Interval], s: int, e: int) -> str:
    """What the host was doing over [s, e]: of the host events that overlap
    it most (within a tenth of the best overlap), the shortest."""
    best = []
    for hs, he, name in host:
        if hs >= e:
            break
        over = min(he, e) - max(hs, s)
        if over > 0:
            best.append((over, he - hs, name))
    if not best:
        return "no host event"
    top = max(o for o, _, _ in best)
    return min((d, n) for o, d, n in best if o >= 0.9 * top)[1]


def breakdown(trace: Trace, per_device: List[DeviceFigures], top: int = 10
              ) -> Dict[str, list]:
    """The device operations that took most time (averaged over the chips)
    and the longest idle gaps of the first chip, named by the host."""
    ops: Dict[str, float] = {}
    for f in per_device:
        for n, sec in f.op_seconds.items():
            ops[n] = ops.get(n, 0.0) + sec / len(per_device)
    gaps = sorted(per_device[0].gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[n, s] for n, s in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[host_activity(trace.host, s, e), (e - s) * 1e-9]
                      for s, e in gaps],
    }
