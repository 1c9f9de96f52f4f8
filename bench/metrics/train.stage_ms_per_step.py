"""Host time the prefetcher's staging thread spent stacking chunks and
putting them on the device (the program's ``stage`` spans that ended in the
window) per optimizer step of the window. It overlaps the device's work; it
holds the step up only where ``train.batch_wait_ms_per_step`` grows too."""


def reduce(run):
    spans = run.spans_named("stage")
    if run.steps < 1 or not spans:
        return None
    return 1e3 * sum(s["end"] - s["start"] for s in spans) / run.steps
