"""Device time of every op that is neither a Pallas kernel nor a
collective (layout conversions, XLA fusions, reductions) per CG iteration,
mean over the chips, in milliseconds."""
import metriclib


def read(run):
    return metriclib.per_iter_ms(run, "other_ns")
