"""Mean duration of the program's ``serve_batch`` spans (one registry call:
pad, run on the device, fetch) in the window."""


def reduce(run):
    spans = run.spans_named("serve_batch")
    if not spans:
        return None
    return 1e3 * sum(s["end"] - s["start"] for s in spans) / len(spans)
