"""Device time of the program's Pallas kernels (``nekbone_*`` ops) per CG
iteration of one right-hand side, mean over the chips, in milliseconds."""
import metriclib


def read(run):
    return metriclib.per_iter_ms(run, "pallas_ns")
