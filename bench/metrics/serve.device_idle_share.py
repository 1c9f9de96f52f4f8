"""Share of the traced serving window in which no operation ran on the
device, averaged over the chips. The window runs from the first operation
in the trace to the end of the last."""


def reduce(run):
    if not run.devices:
        return None
    return 100.0 * (1.0 - run.mean_device("busy_s") / run.mean_device("window_s"))
