"""Per traced call: device time of the Stage-1 Pallas kernel (tiled or wide)."""


def read(run):
    red = run.reduction
    if red is None or "stage1" not in red.class_ns:
        return None
    return red.class_ns["stage1"] / red.calls / 1e6
