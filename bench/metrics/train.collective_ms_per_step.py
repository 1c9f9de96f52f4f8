"""Device time of the collective operations (all-reduce and the like) per
optimizer step in the traced window, averaged over the chips. The part of
it during which no other operation runs on that chip is written to the log
beside it."""


def reduce(run):
    if not run.devices or run.steps_per_execution < 1:
        return None
    steps = run.mean_device("executions") * run.steps_per_execution
    if steps <= 0:
        return None
    total = run.mean_device("collective_s")
    if total <= 0:
        return None
    exposed = run.mean_device("collective_exposed_s")
    run.notes.append(f"collectives: {1e3 * total / steps!r} ms per step, "
                     f"{1e3 * exposed / steps!r} ms of it with no other "
                     "operation running")
    return 1e3 * total / steps
