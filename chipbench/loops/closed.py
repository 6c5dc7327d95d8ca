"""The loop of ``"loop": "closed"``: one caller sends a solve, waits for
its answer and sends the next, as a time-stepping code waits for each
pressure solve.  Solve ``i`` takes right-hand side ``i mod pool``.

A loop module has ``drive(system, pool, traffic, seconds, seed, sample,
clock)``, which returns ``{"solves": [...], "window_s": ...}``: one entry
per solve offered, with ``iters`` once it was answered, and every answer
offered to ``sample`` as ``(k, x, iters)``.  It may add ``report``, a dict
the run prints with the window's record.
"""
from __future__ import annotations

import time


def drive(system, pool: list, traffic: dict, seconds: float, seed: int,
          sample, clock=time.perf_counter) -> dict:
    """Solve back to back for ``seconds``; every solve that starts in the
    window is waited for and counted."""
    solves = []
    t0 = clock()
    i = 0
    while clock() - t0 < seconds:
        k = i % len(pool)
        ts = clock()
        x, iters = system.solve(k)
        te = clock()
        solves.append({"start": ts - t0, "end": te - t0, "iters": iters,
                       "rhs": k})
        sample.offer((k, x, iters))
        i += 1
    took = sorted(s["end"] - s["start"] for s in solves)
    report = {"solve_s_min": took[0], "solve_s_p50": took[len(took) // 2],
              "solve_s_max": took[-1]} if took else {}
    return {"solves": solves, "window_s": clock() - t0, "report": report}
