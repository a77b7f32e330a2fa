"""Executables the solver's executable cache built during the window
(its misses after set-up minus before)."""


def read(run):
    if run.stats_before is None or run.stats_after is None:
        return None
    after = run.stats_after["executable_cache"]["misses"]
    return after - run.stats_before["executable_cache"]["misses"]
