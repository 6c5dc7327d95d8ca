"""The problem's least time on the chip for every component of a vector
solve (Eq. 1 operations and compulsory bytes, roofline.py, on
``components x E n^3`` DOFs) over the device busy time of the traced
solves, in percent, mean over the chips."""
import types

import metriclib


def read(run):
    k = int(run.cfg.get("components", 1))
    return metriclib.roofline_pct(
        types.SimpleNamespace(**{**vars(run), "dofs": run.dofs * k}))
