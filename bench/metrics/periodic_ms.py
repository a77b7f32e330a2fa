"""Per traced call: device time of the periodic correction's kernel, the
rank-one update of Sherman-Morrison on a cyclic reduced system. On a TPU
trace it is a custom call named after its jitted wrapper,
``_periodic_correction`` (``_periodic_correction.1``). Nothing is read
where no such op ran: a non-periodic cell, or a program without it."""

import re

KERNEL = re.compile(r"^_periodic_correction")


def read(run):
    red = run.reduction
    if red is None:
        return None
    ns = [t for name, t in red.op_ns.items() if KERNEL.match(name)]
    if not ns:
        return None
    return sum(ns) / red.calls / 1e6
