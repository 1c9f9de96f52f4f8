"""Share of the traced training window in which no operation ran on the
device, averaged over the chips (trace: ``XLA Ops``). The window runs from
the first complete execution of the chunk program to the end of the last,
so the profiler's own start and stop lie outside it."""


def reduce(run):
    if not run.devices:
        return None
    return 100.0 * (1.0 - run.mean_device("busy_s") / run.mean_device("window_s"))
