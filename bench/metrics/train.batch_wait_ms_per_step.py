"""Host time the train loop waited for its next device-resident chunk (the
program's ``batch_wait`` spans around ``DevicePrefetcher``'s hand-over that
ended in the window) per optimizer step of the window."""


def reduce(run):
    spans = run.spans_named("batch_wait")
    if run.steps < 1 or not spans:
        return None
    return 1e3 * sum(s["end"] - s["start"] for s in spans) / run.steps
