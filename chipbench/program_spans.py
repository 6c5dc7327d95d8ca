"""What the readers of the program's own spans share.

The program times its host work in named spans (``repro.obs.trace``).
While a ``jax.profiler`` session is on, as in a ``--trace 1`` run's
window and nowhere else in a run, it adds each span's host nanoseconds to
a total per name, ``span_totals()``.  A program without those totals, or
whose window ran no ``solve`` span, gives the readers nothing to read.
"""
from __future__ import annotations

import metriclib


def ms_per_solve(run, name: str) -> float | None:
    """Host milliseconds inside the program's ``name`` spans per answered
    solve of the window; 0 where the program ran solves and no such
    span."""
    try:
        from repro.obs.trace import span_totals
    except ImportError:
        return None
    totals = span_totals()
    solved = metriclib.items(run)
    if not solved or not totals.get("solve", {}).get("count"):
        return None
    return totals.get(name, {}).get("ns", 0) / 1e6 / len(solved)
