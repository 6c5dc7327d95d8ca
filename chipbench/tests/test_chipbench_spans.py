"""CPU tests of the readers of the program's own span totals
(``program_spans.py``): host time per answered solve of the drivers'
preparation and validation."""
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import spec as spec_mod  # noqa: E402

READERS = ("driver.prepare_ms_per_solve.solve",
           "driver.validate_ms_per_solve.solve")


def _run(solves):
    return types.SimpleNamespace(loop={"solves": list(solves)}, trace=None)


def _read(name, run):
    return spec_mod.Spec().metric(name).read(run)


def _totals(monkeypatch, totals):
    from repro.obs import trace

    monkeypatch.setattr(trace, "span_totals", lambda: totals)


def test_readers_divide_span_time_by_the_answered_solves(monkeypatch):
    _totals(monkeypatch, {"solve": {"count": 4, "ns": 900_000_000},
                          "driver.prepare": {"count": 4, "ns": 800_000_000},
                          "driver.validate": {"count": 4,
                                              "ns": 600_000_000}})
    # four answered solves, and one offered that never came back
    run = _run([{"iters": 600}] * 4 + [{}])
    assert _read(READERS[0], run) == pytest.approx(200.0)
    assert _read(READERS[1], run) == pytest.approx(150.0)


def test_a_driver_without_validation_reads_zero(monkeypatch):
    _totals(monkeypatch, {"solve": {"count": 2, "ns": 9_000_000},
                          "driver.prepare": {"count": 2, "ns": 3_000_000}})
    run = _run([{"iters": 100}] * 2)
    assert _read(READERS[0], run) == pytest.approx(1.5)
    assert _read(READERS[1], run) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_no_span_totals_read_nothing(monkeypatch, name):
    run = _run([{"iters": 100}] * 2)
    # a window with no solve span: no profiler session, or no instrumented
    # program
    _totals(monkeypatch, {})
    assert _read(name, run) is None
    _totals(monkeypatch, {"driver.prepare": {"count": 1, "ns": 5}})
    assert _read(name, run) is None
    # no answered solve to divide by
    _totals(monkeypatch, {"solve": {"count": 1, "ns": 5}})
    assert _read(name, _run([{}])) is None
    # a program that keeps no span totals at all
    from repro.obs import trace

    monkeypatch.delattr(trace, "span_totals")
    assert _read(name, run) is None
