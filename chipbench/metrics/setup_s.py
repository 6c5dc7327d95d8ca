"""Seconds from the start of the process to the start of the window:
imports, caches, the right-hand sides, building the case, the autotune
picks and every compilation or cache load of the cell's shapes."""


def read(run):
    return run.setup_s
