"""The one wall-clock measurement helper (DESIGN.md §11).

Every measured-time consumer — the autotune sweeps in
``kernels/autotune.py`` and the bench modules under ``benchmarks/`` (via
the ``benchmarks.timing`` re-export) — times through :func:`measure`, so
warmup handling and the median-of-reps estimator cannot drift apart
between the tuner and the benches that validate its picks.

Methodology: ``warmup`` calls are discarded (they absorb compilation and
first-touch cache effects), then each of ``reps`` repetitions is synced and
timed and the median is returned — the median is robust to the one-sided
noise wall-clock suffers (preemption, clock migration can only add time,
so the mean over-reports).  A repetition is one call, or with ``chain`` a
chain of back-to-back calls ended by one sync, so a sub-millisecond kernel
is timed on the device's clock rather than on its dispatch and sync
(:func:`chain_length` sizes a chain to a wall time).  ``timer`` and
``sync`` are injectable for unit tests (tests/test_timing.py).
"""
from __future__ import annotations

import time

__all__ = ["measure", "chain_length", "median", "stopwatch",
           "Stopwatch"]


class Stopwatch:
    """Monotonic elapsed-µs reader (``time.perf_counter_ns`` based — the
    same clock discipline as :func:`measure`).  The telemetry layer's
    phase timer: ``sw = stopwatch(); ...; sw.us()``."""

    __slots__ = ("_t0",)

    def __init__(self):
        self._t0 = time.perf_counter_ns()

    def us(self) -> float:
        """Microseconds since construction."""
        return (time.perf_counter_ns() - self._t0) / 1e3


def stopwatch() -> Stopwatch:
    """Start a :class:`Stopwatch` now."""
    return Stopwatch()


def median(xs) -> float:
    """Median of a non-empty sequence (upper median for even lengths —
    the conservative choice for one-sided timing noise)."""
    xs = sorted(xs)
    if not xs:
        raise ValueError("median() of empty sequence")
    return xs[len(xs) // 2]


def _default_sync(x):
    import jax
    return jax.block_until_ready(x)


def measure(fn, *args, reps: int = 5, warmup: int = 1, timer=None,
            sync=None, chain: int = 1) -> float:
    """Median wall-clock seconds per call of ``fn(*args)`` over ``reps``
    repetitions, after ``warmup`` discarded synced calls.

    Args:
      fn: callable under test; its (possibly async-dispatched) result is
        passed through ``sync`` so the work is actually finished inside
        the timed region.
      reps: timed repetitions (must be >= 1); the *median* is returned.
      warmup: discarded leading calls (compile + cache warm; may be 0 when
        the callable is already warm).
      timer: monotonic clock, ``time.perf_counter`` by default.
      sync: completion barrier, ``jax.block_until_ready`` by default
        (imported lazily so non-jax callables can use this too).
      chain: calls per repetition, issued back to back with one ``sync``
        on the last result; the repetition's time is divided by ``chain``.
        The device runs the calls in order, so the chain ends when the last
        call does, and dispatch overlaps the device work instead of adding
        to it.
    """
    if reps < 1:
        raise ValueError(f"reps must be >= 1, got {reps}")
    if warmup < 0:
        raise ValueError(f"warmup must be >= 0, got {warmup}")
    if chain < 1:
        raise ValueError(f"chain must be >= 1, got {chain}")
    timer = time.perf_counter if timer is None else timer
    sync = _default_sync if sync is None else sync
    for _ in range(warmup):
        sync(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = timer()
        for _ in range(chain - 1):
            fn(*args)
        sync(fn(*args))
        ts.append((timer() - t0) / chain)
    return median(ts)


def chain_length(fn, *args, seconds: float, warmup: int = 1, timer=None,
                 sync=None, limit: int = 1 << 16) -> int:
    """Calls per chain (:func:`measure`'s ``chain``) for one chain of
    ``fn(*args)`` to last at least ``seconds``: after ``warmup`` synced
    calls, chains of 1, 2, 4, ... calls are timed until one lasts that
    long (at most ``limit`` calls)."""
    timer = time.perf_counter if timer is None else timer
    sync = _default_sync if sync is None else sync
    for _ in range(warmup):
        sync(fn(*args))
    chain = 1
    while chain < limit:
        if chain * measure(fn, *args, reps=1, warmup=0, timer=timer,
                           sync=sync, chain=chain) >= seconds:
            break
        chain *= 2
    return chain
