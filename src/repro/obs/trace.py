"""Structured solver traces: spans, events, counters -> JSONL files.

The recording surface of the telemetry subsystem (DESIGN.md §14).
Instrumentation sits only at the host boundaries of the pipelines (the
drivers' per-solve preparation, the s-step cycle loop, the refinement
sweep loop, driver dispatch, autotune sweeps, service drain); nothing is
ever recorded from inside a jitted computation, so the compiled programs
are byte-for-byte the same with tracing on or off.

:func:`span` is the one span call, ``with trace.span(name, **attrs):``.
It feeds whichever consumers are on:

* a ``jax.profiler`` session (``jax.profiler.start_trace``, or a
  profiler server capturing): the span enters
  ``jax.profiler.TraceAnnotation(name)``, so it lies on the host plane of
  the device trace, on the profiler's clock, and its count and host
  nanoseconds add to a process-wide total per name
  (:func:`span_totals`);
* a :class:`Recorder` (:func:`recording`): the span is recorded with its
  attributes;
* neither: the shared :data:`NULL_SPAN` singleton is returned.

Overhead when off: one ``TraceAnnotation.is_enabled()`` test (none at
all before ``jax`` is imported) and one context-local (``contextvars``)
read per span; solve *output* is bitwise identical either way — pinned
by tests/test_obs_trace.py and the ``obs-smoke`` CI leg.

Trace files are JSON Lines with a versioned schema
(:data:`TRACE_SCHEMA`): a ``header`` record first (schema + provenance),
then ``span``/``event`` records in completion order, then one closing
``summary`` record (counters, gauges).  :func:`validate_trace_lines` is
the schema check the obs-smoke leg and the tests share.

:func:`profiling` wires ``start_trace``/``stop_trace`` around a bench
when a log dir is given.
"""
from __future__ import annotations

import contextlib
import contextvars
import json
import os
import pathlib
import platform
import sys
import threading
import time
from typing import Any

__all__ = ["TRACE_SCHEMA", "TRACE_SCHEMA_VERSION", "NULL_SPAN", "Recorder",
           "recording", "active", "span", "span_totals", "event", "count",
           "gauge", "provenance", "machine_tag", "validate_trace_lines",
           "validate_trace_file", "profiling"]

TRACE_SCHEMA = "repro-trace/1"
TRACE_SCHEMA_VERSION = 1

_RECORDER: contextvars.ContextVar["Recorder | None"] = \
    contextvars.ContextVar("repro_obs_recorder", default=None)


class _NullSpan:
    """Shared no-op context manager — what instrumented code enters when
    tracing is off.  A singleton: entering it allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        """Attributes known only inside the span (none kept when off)."""


NULL_SPAN = _NullSpan()


class _Span:
    """One timed region; records itself on ``__exit__`` (completion
    order), carrying the recorder's nesting depth at entry."""

    __slots__ = ("_rec", "name", "attrs", "_t0", "_depth")

    def __init__(self, rec: "Recorder", name: str, attrs: dict):
        self._rec = rec
        self.name = name
        self.attrs = attrs
        self._t0 = 0.0
        self._depth = 0

    def __enter__(self):
        rec = self._rec
        self._depth = rec._depth
        rec._depth += 1
        self._t0 = rec.now_us()
        return self

    def __exit__(self, *exc):
        rec = self._rec
        dur = rec.now_us() - self._t0
        rec._depth -= 1
        ev: dict[str, Any] = {"type": "span", "name": self.name,
                              "t_us": round(self._t0, 3),
                              "dur_us": round(dur, 3),
                              "depth": self._depth}
        if self.attrs:
            ev["attrs"] = self.attrs
        rec.records.append(ev)
        return False

    def set(self, **attrs) -> None:
        """Add attributes known only inside the span (recorded on exit)."""
        self.attrs.update(attrs)


class _ProfiledSpan:
    """A span under a ``jax.profiler`` session: a ``TraceAnnotation`` on
    the host plane around the region, its host time added to
    :func:`span_totals`, and the recorder's span (or :data:`NULL_SPAN`)
    inside it."""

    __slots__ = ("name", "_ann", "_inner", "_t0")

    def __init__(self, name: str, inner):
        self.name = name
        self._ann = _ANNOTATION(name)
        self._inner = inner
        self._t0 = 0

    def __enter__(self):
        self._ann.__enter__()
        self._inner.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        ns = time.perf_counter_ns() - self._t0
        self._inner.__exit__(*exc)
        self._ann.__exit__(*exc)
        with _TOTALS_LOCK:
            count, total = _TOTALS.get(self.name, (0, 0))
            _TOTALS[self.name] = (count + 1, total + ns)
        return False

    def set(self, **attrs) -> None:
        self._inner.set(**attrs)


class Recorder:
    """Collects spans/events/counters for one recording session.

    Timestamps are microseconds since the recorder's creation
    (``time.perf_counter_ns`` — monotonic, never wall-clock).  Not
    thread-safe by design: one recorder belongs to one context (the
    ``contextvars`` slot keeps concurrent contexts independent).
    """

    def __init__(self, *, meta: dict | None = None):
        self.records: list[dict] = []
        self.counters: dict[str, float] = {}
        self.gauges: dict[str, float] = {}
        self.meta = dict(meta or {})
        self._depth = 0
        self._t0 = time.perf_counter_ns()

    def now_us(self) -> float:
        return (time.perf_counter_ns() - self._t0) / 1e3

    # -- recording ------------------------------------------------------
    def span(self, name: str, /, **attrs) -> _Span:
        """Context manager timing one host-side region."""
        return _Span(self, name, attrs)

    def event(self, name: str, /, **attrs) -> None:
        """One instantaneous record."""
        ev: dict[str, Any] = {"type": "event", "name": name,
                              "t_us": round(self.now_us(), 3),
                              "depth": self._depth}
        if attrs:
            ev["attrs"] = attrs
        self.records.append(ev)

    def count(self, name: str, value: float = 1) -> None:
        """Monotonic counter increment (totals land in the summary)."""
        self.counters[name] = self.counters.get(name, 0) + value

    def gauge(self, name: str, value: float) -> None:
        """Last-value-wins gauge (e.g. queue depth)."""
        self.gauges[name] = value

    # -- serialization --------------------------------------------------
    def header(self) -> dict:
        h = {"type": "header", "schema": TRACE_SCHEMA,
             "schema_version": TRACE_SCHEMA_VERSION,
             "provenance": provenance()}
        if self.meta:
            h["meta"] = self.meta
        return h

    def summary(self) -> dict:
        return {"type": "summary", "spans": sum(
                    1 for r in self.records if r["type"] == "span"),
                "events": sum(
                    1 for r in self.records if r["type"] == "event"),
                "counters": dict(self.counters),
                "gauges": dict(self.gauges)}

    def lines(self) -> list[str]:
        recs = [self.header(), *self.records, self.summary()]
        return [json.dumps(r, sort_keys=True, default=_jsonable)
                for r in recs]

    def write(self, path) -> pathlib.Path:
        """Write the trace as JSONL (parent dirs created)."""
        path = pathlib.Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text("\n".join(self.lines()) + "\n")
        return path


def _jsonable(x):
    """Trace attrs may carry numpy/jax scalars; coerce, never crash."""
    for conv in (float, str):
        try:
            return conv(x)
        except (TypeError, ValueError):
            continue
    return repr(x)


# ---------------------------------------------------------------------------
# the context-local slot + module-level convenience surface
# ---------------------------------------------------------------------------

def active() -> Recorder | None:
    """The context's active recorder, or None when none is on.

    Spans do not need it (:func:`span` reads it); code that does work
    only for a recorder (the per-solve telemetry) asks here.
    """
    return _RECORDER.get()


@contextlib.contextmanager
def recording(path=None, *, meta: dict | None = None,
              recorder: Recorder | None = None):
    """Activate a recorder for the enclosed block; yields it.

        with trace.recording("out/solve.trace.jsonl") as rec:
            repro.solve(1024, niter=100)
        # rec.records / the JSONL file now hold the spans

    ``path`` (optional) writes the JSONL trace on exit — also on
    exception, so a failing solve still leaves its evidence.  Nested
    recordings shadow the outer recorder for their extent.
    """
    rec = recorder if recorder is not None else Recorder(meta=meta)
    token = _RECORDER.set(rec)
    try:
        yield rec
    finally:
        _RECORDER.reset(token)
        if path is not None:
            rec.write(path)


# jax.profiler.TraceAnnotation, looked up once jax has been imported (a
# process without jax runs no profiler session).
_ANNOTATION = None
# span name -> (count, host ns) of the spans closed under a profiler session
_TOTALS: dict[str, tuple[int, int]] = {}
_TOTALS_LOCK = threading.Lock()


def _profiling() -> bool:
    """Whether a ``jax.profiler`` session is collecting host events."""
    global _ANNOTATION
    if _ANNOTATION is None:
        if "jax" not in sys.modules:
            return False
        try:
            from jax.profiler import TraceAnnotation
        except Exception:  # noqa: BLE001 — tracing must never sink a solve
            return False
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION.is_enabled()


def span(name: str, /, **attrs):
    """The one span call: ``with trace.span("driver.prepare"):``.

    Under a ``jax.profiler`` session a ``TraceAnnotation(name)`` on the
    host plane, counted in :func:`span_totals`; under a recorder a
    recorded span with ``attrs``; both when both are on; else the shared
    no-op :data:`NULL_SPAN`.  Each form has ``set(**attrs)`` for
    attributes known only inside the span."""
    rec = _RECORDER.get()
    inner = rec.span(name, **attrs) if rec is not None else NULL_SPAN
    return _ProfiledSpan(name, inner) if _profiling() else inner


def span_totals() -> dict[str, dict[str, int]]:
    """Per span name, ``{"count", "ns"}``: how many spans of that name
    closed while a ``jax.profiler`` session was on, and their host
    nanoseconds (``time.perf_counter_ns``), over the process's life.
    Nested spans count in their own name and in their parent's time."""
    with _TOTALS_LOCK:
        return {name: {"count": c, "ns": ns}
                for name, (c, ns) in _TOTALS.items()}


def event(name: str, /, **attrs) -> None:
    rec = _RECORDER.get()
    if rec is not None:
        rec.event(name, **attrs)


def count(name: str, value: float = 1) -> None:
    rec = _RECORDER.get()
    if rec is not None:
        rec.count(name, value)


def gauge(name: str, value: float) -> None:
    rec = _RECORDER.get()
    if rec is not None:
        rec.gauge(name, value)


# ---------------------------------------------------------------------------
# provenance — recorded in every trace header and in BENCH_*.json
# ---------------------------------------------------------------------------

def machine_tag() -> str:
    """Hostname-free machine fingerprint: OS, ISA, core count.

    Enough to explain "why do these timings differ" across environments
    without leaking a hostname into committed baselines or uploaded
    artifacts."""
    return "-".join((platform.system().lower() or "unknown",
                     platform.machine() or "unknown",
                     f"{os.cpu_count() or 0}cpu"))


def provenance() -> dict:
    """Where a measurement came from: backend, jax version, x64 flag,
    machine tag.  Degrades gracefully when jax is absent (trace-only
    consumers)."""
    prov = {"machine": machine_tag(),
            "python": platform.python_version()}
    try:
        import jax

        prov["jax_version"] = jax.__version__
        prov["backend"] = jax.default_backend()
        prov["x64"] = bool(jax.config.jax_enable_x64)
    except Exception:  # noqa: BLE001 — provenance must never sink a trace
        prov["backend"] = None
    return prov


# ---------------------------------------------------------------------------
# JSONL schema validation (shared by tests and the obs-smoke CI leg)
# ---------------------------------------------------------------------------

_REQUIRED = {
    "header": ("schema", "schema_version", "provenance"),
    "span": ("name", "t_us", "dur_us", "depth"),
    "event": ("name", "t_us"),
    "summary": ("spans", "events", "counters", "gauges"),
}


def validate_trace_lines(lines) -> list[str]:
    """All schema violations of a JSONL trace (empty list == valid).

    Checks: every line parses as a JSON object; first record is a
    ``header`` with the known schema; last is a ``summary`` whose span
    count matches; required fields per record type; span timings are
    finite and non-negative."""
    problems: list[str] = []
    records = []
    for i, line in enumerate(lines):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError as e:
            problems.append(f"line {i + 1}: not valid JSON ({e})")
            continue
        if not isinstance(rec, dict):
            problems.append(f"line {i + 1}: not a JSON object")
            continue
        records.append((i + 1, rec))
    if not records:
        problems.append("empty trace: no records")
        return problems
    for ln, rec in records:
        typ = rec.get("type")
        if typ not in _REQUIRED:
            problems.append(f"line {ln}: unknown record type {typ!r}")
            continue
        for field in _REQUIRED[typ]:
            if field not in rec:
                problems.append(f"line {ln}: {typ} record missing "
                                f"{field!r}")
        if typ == "span":
            for field in ("t_us", "dur_us"):
                v = rec.get(field)
                if not isinstance(v, (int, float)) or v < 0 or v != v:
                    problems.append(f"line {ln}: span {field}={v!r} is "
                                    "not a non-negative number")
    first, last = records[0][1], records[-1][1]
    if first.get("type") != "header":
        problems.append("first record is not a header")
    elif first.get("schema") != TRACE_SCHEMA:
        problems.append(f"header schema {first.get('schema')!r} != "
                        f"{TRACE_SCHEMA!r}")
    if last.get("type") != "summary":
        problems.append("last record is not a summary")
    else:
        nspan = sum(1 for _, r in records if r.get("type") == "span")
        if last.get("spans") != nspan:
            problems.append(f"summary claims {last.get('spans')} spans, "
                            f"trace holds {nspan}")
    return problems


def validate_trace_file(path) -> list[str]:
    """:func:`validate_trace_lines` over a file path."""
    try:
        text = pathlib.Path(path).read_text()
    except OSError as e:
        return [f"cannot read trace file {path}: {e}"]
    return validate_trace_lines(text.splitlines())


# ---------------------------------------------------------------------------
# a profiler session around a bench
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def profiling(logdir=None):
    """``jax.profiler.start_trace(logdir)`` .. ``stop_trace()`` around a
    block; a no-op when ``logdir`` is falsy.  The benches pass
    ``$REPRO_PROFILE_DIR`` here, so profiling is one env var away without
    touching bench code; every :func:`span` inside lands in the trace."""
    if not logdir:
        yield None
        return
    import jax.profiler

    jax.profiler.start_trace(str(logdir))
    try:
        yield str(logdir)
    finally:
        jax.profiler.stop_trace()
