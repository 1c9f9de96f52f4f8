"""Requests over bucket rows, summed over every dispatched batch in the
window (the ``n`` and ``bucket`` tags of the ``serve_batch`` spans)."""


def reduce(run):
    spans = run.spans_named("serve_batch")
    rows = sum(int(s["tags"]["bucket"]) for s in spans)
    if rows == 0:
        return None
    return 100.0 * sum(int(s["tags"]["n"]) for s in spans) / rows
