"""Per traced call: device time of Stage 2: the Thomas Pallas kernel or the XLA scan, whichever ran."""


def read(run):
    red = run.reduction
    if red is None or "stage2" not in red.class_ns:
        return None
    return red.class_ns["stage2"] / red.calls / 1e6
