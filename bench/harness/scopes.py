"""Device time by the program's named scopes, and the window's idle time by
the program's spans.

The training step names its parts with ``jax.named_scope``: ``embed`` (each
table's hash, gather and relayout; autodiff names their backward, the
gradient scatter, ``transpose(jvp(embed))``), ``loss`` (the fused loss,
forward and backward) and ``update`` (the optimizer). XLA keeps the scope in
each operation's ``op_name`` metadata. On the v5e trace that metadata is the
``tf_op`` stat of the operation's event metadata on the ``/device:TPU:<n>``
plane (``jit(_chunk_step)/while/body/closed_call/update/add:``), which
``ProfileData`` does not hand out: :func:`op_names` reads it from the
``.xplane.pb`` itself. An operation counts under the outermost of the three
scopes its ``op_name`` names. One with no ``op_name`` at all (an operation
XLA put in, such as the relayout of an (N, 1) table before its gather) counts
under the scope of the operations that read its result, where those agree
on one: their operand lists are in the trace's operation names. The rest
(the scan's slicing, the model's own arithmetic) is unscoped.

The program's spans are annotated into the same trace (``TraceAnnotation``
on the host plane, on the device's clock), so each idle gap of the window is
given to a program span of the train loop's thread: of those that overlap
the gap most (within a tenth), the innermost (``xtrace.host_activity``).

:func:`scopes` reads the trace once per run, keeps the result on the run,
and writes its notes then: the unscoped device time per step, the idle time
by program span, and the compiles in the window.
"""
from __future__ import annotations

import re
from typing import Dict, Iterator, List, Optional, Tuple

from harness import xtrace

SCOPES = ("embed", "loss", "update")
TRAIN_LOOP_SPAN = "dispatch"     # marks the host line of the train loop
_WRAPPED = re.compile(r"^\w+\((.*)\)$")    # jvp(embed), transpose(jvp(embed))
_PROGRAM_ID = re.compile(r"\((\d+)\)$")     # jit__chunk_step(1608...)
_OPERAND = re.compile(r"%[\w.-]+")


def scope_of(op_name: str) -> Optional[str]:
    """The outermost of :data:`SCOPES` that ``op_name`` names, or None."""
    for part in op_name.rstrip(":").split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        if part in SCOPES:
            return part
    return None


# -- the op_name of each device operation, from the .xplane.pb -------------
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map: key 1,
# value 2), .stat_metadata = 5 (map); XEventMetadata.name = 2, .stats = 5;
# XStatMetadata.name = 2; XStat.metadata_id = 1, .str_value = 5.

def _varint(b, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b, start: int, end: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int, or a (start, end) span
    of the buffer for a length-delimited field."""
    i = start
    while i < end:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(b, i)
        elif kind == 2:
            n, i = _varint(b, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"unexpected protobuf wire type {kind}")
        yield key >> 3, value


def _text(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(b, span) -> Iterator[Tuple[int, Tuple[int, int]]]:
    key, value = None, None
    for num, v in _fields(b, *span):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    if value is not None:
        yield key, value


def op_names(path: str) -> Dict[str, Dict[Tuple[int, str], str]]:
    """``{device plane: {(program id, operation's event name): op_name}}``
    from the ``tf_op`` and ``program_id`` stats of each device plane's event
    metadata (an operation's name is unique only within its program); the
    ``op_name`` is "" where the operation has none."""
    with open(path, "rb") as f:
        b = memoryview(f.read())
    out: Dict[str, Dict[Tuple[int, str], str]] = {}
    for num, plane in _fields(b, 0, len(b)):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pnum, v in _fields(b, *plane):
            if pnum == 2:
                name = _text(b, v)
            elif pnum == 4:
                events.extend(value for _, value in _map_values(b, v))
            elif pnum == 5:
                for key, value in _map_values(b, v):
                    for snum, sv in _fields(b, *value):
                        if snum == 2:
                            stat_names[key] = _text(b, sv)
        if not xtrace.DEVICE_PLANE.match(name):
            continue
        tf_op = {k for k, n in stat_names.items() if n == "tf_op"}
        program = {k for k, n in stat_names.items() if n == "program_id"}
        ops = out.setdefault(name, {})
        for span in events:
            event_name, op, pid = None, "", 0
            for enum, v in _fields(b, *span):
                if enum == 2:
                    event_name = _text(b, v)
                elif enum == 5:
                    stat = dict(_fields(b, *v))
                    if stat.get(1) in tf_op and isinstance(stat.get(5), tuple):
                        op = _text(b, stat[5])
                    elif stat.get(1) in program:   # uint64 or int64 value
                        pid = stat.get(3, stat.get(4, 0))
            if event_name is not None:
                ops[(pid, event_name)] = op
    return out


def inherit(op_name: Dict[str, str]) -> Dict[str, Optional[str]]:
    """``{operation's event name: scope}`` from ``{event name: op_name}``,
    each operation with no ``op_name`` given the one scope of the operations
    that read it, where they agree (the event name is the HLO instruction:
    ``%reduce.9 = f32[...] reduce(f32[...] %param.3, ...)``)."""
    scope = {n: scope_of(op) for n, op in op_name.items()}
    operands = {n: set(_OPERAND.findall(n.split(" = ", 1)[-1]))
                for n in scope}
    by_instruction = {n.split(" ", 1)[0]: n for n in scope}
    changed = True
    while changed:
        changed = False
        readers: Dict[str, set] = {}
        for n, refs in operands.items():
            for ref in refs & by_instruction.keys():
                readers.setdefault(by_instruction[ref], set()).add(scope[n])
        for n, found in readers.items():
            if (not op_name[n] and scope[n] is None and len(found) == 1
                    and None not in found):
                scope[n] = found.pop()
                changed = True
    return scope


# -- program spans on the trace's host plane ------------------------------

def program_spans(path: str, names) -> List[xtrace.Interval]:
    """Annotated program spans of the train loop's thread (the host line
    that holds ``dispatch`` spans), or of every host line where none does."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    lines = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [e for e in xtrace._events(line) if e[2] in names]
                if spans:
                    lines.append(spans)
    loop = [s for s in lines if any(n == TRAIN_LOOP_SPAN for _, _, n in s)]
    return sorted(e for spans in (loop or lines) for e in spans)


def _idle_by_span(spans, gaps) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for s, e in gaps:
        name = xtrace.host_activity(spans, s, e)
        if name == "no host event":
            name = "no program span"
        out[name] = out.get(name, 0) + (e - s)
    return out


def scopes(run) -> Optional[Dict[str, float]]:
    """Device seconds per step by scope, averaged over the chips
    (``None`` holds the unscoped rest); ``None`` where the trace has no
    window or no operation in any scope (a program without the scopes)."""
    if hasattr(run, "_scopes"):
        return run._scopes
    run._scopes = None
    if run.trace is None or run.steps_per_execution < 1:
        return None
    names = op_names(run.trace.path)
    per_scope: Dict[Optional[str], float] = {}
    chips = 0
    for dev in run.trace.devices[:run.cell.chips]:
        w = xtrace.window(dev, by_program=True)
        if w is None or w.executions < 1:
            continue
        chips += 1
        steps = w.executions * run.steps_per_execution
        ops_of = {s: [] for s in SCOPES + (None,)}
        step = _PROGRAM_ID.search(xtrace.step_program(dev) or "")
        scoped = inherit({n: op for (pid, n), op in
                          names.get(dev.name, {}).items()
                          if step is None or pid == int(step.group(1))})
        scoped = {xtrace.short_name(n): s for n, s in scoped.items()}
        for s, e, n in xtrace.clip(dev.ops, w.start, w.end):
            ops_of[scoped.get(n)].append((s, e))
        for s, ops in ops_of.items():
            per_scope[s] = (per_scope.get(s, 0.0)
                            + xtrace.length(xtrace.union(ops)) * 1e-9 / steps)
    if not chips or not any(per_scope.get(s) for s in SCOPES):
        return None
    run._scopes = {s: v / chips for s, v in per_scope.items()}
    _notes(run)
    return run._scopes


def _notes(run) -> None:
    rest = run._scopes.get(None, 0.0)
    run.notes.append(f"scopes: {1e3 * rest!r} ms per step of device time "
                     f"in no scope of {SCOPES}")
    if run.devices:
        names = {s["name"] for s in run.spans}   # the program's spans
        idle = _idle_by_span(program_spans(run.trace.path, names),
                             run.devices[0].gaps)
        total = sum(idle.values())
        parts = ", ".join(f"{k} {v * 1e-6!r} ms" for k, v in
                          sorted(idle.items(), key=lambda kv: -kv[1]))
        run.notes.append(f"idle in the traced window: {total * 1e-6!r} ms, "
                         f"by the train loop's innermost span: {parts}")
    compiles = run.spans_named("compile")
    run.notes.append(f"compile spans in the window: {len(compiles)}"
                     + "".join(f"; {c['tags'].get('stage')} "
                               f"{c['tags'].get('fun')}"
                               for c in compiles[:5]))


def ms_per_step(run, scope: str) -> Optional[float]:
    found = scopes(run)
    return None if found is None else 1e3 * found.get(scope, 0.0)
