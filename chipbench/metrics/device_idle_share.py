"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, in %."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100 * (1 - run.trace.busy_s / run.trace.window_s)
