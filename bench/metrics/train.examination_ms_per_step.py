"""Device time of UBM's examination lookup per optimizer step: the
operations in the traced window whose ``op_name`` holds the scope
``examination`` (``UserBrowsingModel``: the last-click scan and the (K, K)
table lookup, inside ``loss``) or its backward, ``transpose(jvp(...))`` of
it (the lookup's gradient into the table), per step of the complete
chunk executions, averaged over the chips. Read from the ``.xplane.pb`` by
``harness/scopes.py``'s ``op_names``; an operation with no ``op_name``
counts where the operations that read it do, as ``scopes.inherit`` has it,
so this reader and ``train.loss_ms_per_step`` agree. A program without the
scope reads nothing."""
from harness import scopes, xtrace

SCOPE = "examination"


def in_scope(op_name: str) -> bool:
    """Whether ``op_name`` names :data:`SCOPE` among its scopes, also inside
    a transformation's wrapper (``transpose(jvp(loss))/examination``)."""
    for part in op_name.rstrip(":").split("/"):
        while True:
            if part == SCOPE:
                return True
            m = scopes._WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
    return False


def examination_ops(op_name):
    """The operations of ``{event name: op_name}`` in the examination,
    those with no ``op_name`` included where every operation that reads
    them is, by ``scopes.inherit``'s rule. ``inherit`` spreads one of
    ``scopes.SCOPES``: the examination's operations carry one of them here
    and every other named operation another."""
    inside, outside = scopes.SCOPES[1], scopes.SCOPES[0]
    marked = {n: (inside if in_scope(op) else outside) if op else ""
              for n, op in op_name.items()}
    return {n for n, s in scopes.inherit(marked).items() if s == inside}


def reduce(run):
    if run.trace is None or run.steps_per_execution < 1:
        return None
    names = scopes.op_names(run.trace.path)
    total, chips = 0.0, 0
    for dev in run.trace.devices[:run.cell.chips]:
        w = xtrace.window(dev, by_program=True)
        if w is None or w.executions < 1:
            continue
        step = scopes._PROGRAM_ID.search(xtrace.step_program(dev) or "")
        ours = {xtrace.short_name(n) for n in examination_ops(
            {n: op for (pid, n), op in names.get(dev.name, {}).items()
             if step is None or pid == int(step.group(1))})}
        chips += 1
        if not ours:
            continue
        ops = [o for o in xtrace.clip(dev.ops, w.start, w.end)
               if o[2] in ours]
        total += (xtrace.length(xtrace.union(ops)) * 1e-9
                  / (w.executions * run.steps_per_execution))
    if not chips or total <= 0:
        return None
    return 1e3 * total / chips
