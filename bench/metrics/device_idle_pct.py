"""Share of the traced window in which no op ran on the device."""


def read(run):
    red = run.reduction
    if red is None:
        return None
    return 100.0 * (1.0 - red.busy_ns / red.window_ns)
