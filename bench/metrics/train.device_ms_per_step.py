"""Device busy time per optimizer step in the traced window: the union of
the operations' intervals over the steps of the complete chunk executions
in it, averaged over the chips."""


def reduce(run):
    if not run.devices or run.steps_per_execution < 1:
        return None
    steps = run.mean_device("executions") * run.steps_per_execution
    if steps <= 0:
        return None
    return 1e3 * run.mean_device("busy_s") / steps
