"""1 - (union of the device's busy intervals) / (traced window), mean over
the chips."""
import metriclib


def read(run):
    return metriclib.idle_share(run)
