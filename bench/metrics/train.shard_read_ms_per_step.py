"""Host time the streaming loader spent opening and decoding store shards
(the program's ``shard_read`` spans that ended in the window) per optimizer
step of the window."""


def reduce(run):
    if run.steps < 1:
        return None
    spans = run.spans_named("shard_read")
    return 1e3 * sum(s["end"] - s["start"] for s in spans) / run.steps
