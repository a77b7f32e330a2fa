"""Set-up: process start to the first timed call (imports, operands from
the seed, session, warm-up compile or compile-cache load)."""


def read(run):
    return run.setup_s
