"""The problem's least time on the chip (Eq. 1 operations against the peak
rate, compulsory bytes against the peak bandwidth, roofline.py) over the
device busy time of the traced solves, in percent, mean over the chips."""
import metriclib


def read(run):
    return metriclib.roofline_pct(run)
