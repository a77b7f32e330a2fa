"""Per call: the call span minus the device busy time inside it, i.e. the
host's share of the verb (transfers issued, fuse/split, dispatch, waits)."""


def read(run):
    red = run.reduction
    if red is None:
        return None
    return red.host_ns_per_call / 1e6
