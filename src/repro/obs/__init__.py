"""Solver observability: structured traces, metrics, cost-model drift.

Three layers (DESIGN.md §14), all zero-overhead when tracing is off:

* :mod:`repro.obs.trace` — span/event/counter/gauge API writing JSONL
  trace files with a versioned schema, behind a context-local
  :class:`~repro.obs.trace.Recorder` so jitted drivers stay trace-free;
  under a ``jax.profiler`` session every span also lands on the
  profiler's host plane and in per-name totals (``span_totals``).
* :mod:`repro.obs.metrics` — per-solve :class:`~repro.obs.metrics.
  SolveTelemetry` (attached to ``SolveResult`` when tracing is on) and
  the solver-service queue/dispatch metrics.
* :mod:`repro.obs.drift` — compares measured collective counts and
  bytes/iter of the compiled pipelines against the exact ``core/cost.py``
  books and fails loudly when the books no longer describe the program.

Importing ``repro.obs`` stays jax-free; the submodules import jax
lazily where they need it (a span asks ``jax.profiler`` whether a session
is on only once ``jax`` is imported).
"""
from repro.obs import trace  # noqa: F401  (re-export the core surface)
from repro.obs.trace import (  # noqa: F401
    Recorder, active, count, event, gauge, provenance, recording, span,
    span_totals,
)

__all__ = ["trace", "Recorder", "active", "count", "event", "gauge",
           "provenance", "recording", "span", "span_totals"]
