"""Per traced call: device time of the Stage-3 Pallas kernel (tiled or wide)."""


def read(run):
    red = run.reduction
    if red is None or "stage3" not in red.class_ns:
        return None
    return red.class_ns["stage3"] / red.calls / 1e6
