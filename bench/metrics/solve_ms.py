"""Closed loop, one call in flight: the window's length over the calls
completed in it. The window ends when the last call started in it returns."""


def read(run):
    if run.completed == 0:
        return None
    return run.window_s / run.completed * 1e3
