"""The shared wall-clock helper (DESIGN.md §11.1, kernels/timing.py):
median estimator, warmup discipline, injectable timer/sync, and the
benchmarks re-export staying the same object."""
import pytest

from repro.kernels import timing


# ---------------------------------------------------------------------------
# median
# ---------------------------------------------------------------------------

def test_median_odd_and_even():
    assert timing.median([3.0, 1.0, 2.0]) == 2.0
    # even length: the *upper* median — conservative for one-sided noise
    assert timing.median([4.0, 1.0, 2.0, 3.0]) == 3.0
    assert timing.median([5.0]) == 5.0


def test_median_empty_raises():
    with pytest.raises(ValueError):
        timing.median([])


def test_median_does_not_mutate_input():
    xs = [3.0, 1.0, 2.0]
    timing.median(xs)
    assert xs == [3.0, 1.0, 2.0]


# ---------------------------------------------------------------------------
# measure: a fake monotonic clock scripted per call makes the estimator
# deterministic — intervals are whatever the script says they are.
# ---------------------------------------------------------------------------

class _Clock:
    """timer() returns scripted instants; one tick per call."""

    def __init__(self, instants):
        self.instants = list(instants)

    def __call__(self):
        return self.instants.pop(0)


def test_measure_returns_median_interval():
    calls = []

    def fn():
        calls.append(1)
        return "result"

    synced = []
    # 3 timed reps -> 6 timer() calls; intervals 1.0, 5.0, 2.0 -> median 2.0
    clock = _Clock([0.0, 1.0, 10.0, 15.0, 20.0, 22.0])
    t = timing.measure(fn, reps=3, warmup=2, timer=clock,
                       sync=synced.append)
    assert t == 2.0
    assert len(calls) == 5              # 2 warmup + 3 timed
    assert synced == ["result"] * 5     # every call synced, warmups too


def test_measure_warmup_outside_timed_region():
    # warmup calls must not consume timer ticks: the clock only has
    # exactly enough instants for the timed reps.
    clock = _Clock([0.0, 3.0])
    t = timing.measure(lambda: None, reps=1, warmup=4, timer=clock,
                       sync=lambda x: x)
    assert t == 3.0
    assert clock.instants == []


def test_measure_passes_args_through():
    seen = []
    clock = _Clock([0.0, 1.0])
    timing.measure(lambda a, b: seen.append((a, b)), "x", 7,
                   reps=1, warmup=0, timer=clock, sync=lambda x: x)
    assert seen == [("x", 7)]


def test_measure_validates_reps_and_warmup():
    with pytest.raises(ValueError):
        timing.measure(lambda: None, reps=0)
    with pytest.raises(ValueError):
        timing.measure(lambda: None, warmup=-1)


def test_measure_chain_times_back_to_back_calls_with_one_sync():
    calls, synced = [], []

    def fn():
        calls.append(1)
        return len(calls)

    # 2 reps of a 4-call chain: intervals 8.0 and 12.0 -> 2.0, 3.0 per call
    clock = _Clock([0.0, 8.0, 10.0, 22.0])
    t = timing.measure(fn, reps=2, warmup=0, timer=clock,
                       sync=synced.append, chain=4)
    assert t == 3.0                     # upper median of 2.0 and 3.0
    assert len(calls) == 8
    assert synced == [4, 8]             # one sync per chain, on its last call
    with pytest.raises(ValueError):
        timing.measure(fn, chain=0)


class _CostClock:
    """A clock that each call of ``fn`` advances by ``cost`` seconds."""

    def __init__(self, cost):
        self.now, self.cost = 0.0, cost

    def __call__(self):
        return self.now

    def fn(self):
        self.now += self.cost


def test_chain_length_reaches_the_asked_wall_time():
    clk = _CostClock(0.001)
    # chains of 1, 2, 4, 8, 16 ms fall short of 20 ms; 32 ms does not
    assert timing.chain_length(clk.fn, seconds=0.02, timer=clk,
                               sync=lambda x: x) == 32
    slow = _CostClock(0.05)
    assert timing.chain_length(slow.fn, seconds=0.02, timer=slow,
                               sync=lambda x: x) == 1
    stuck = _CostClock(0.0)
    assert timing.chain_length(stuck.fn, seconds=0.02, timer=stuck,
                               sync=lambda x: x, limit=64) == 64


def test_measure_default_sync_blocks_jax_values():
    import jax.numpy as jnp

    # the lazy jax.block_until_ready default: just exercise the real path
    t = timing.measure(lambda: jnp.arange(4) + 1, reps=1, warmup=1)
    assert t >= 0.0


def test_benchmarks_reexport_is_the_same_object():
    from benchmarks import timing as bench_timing

    assert bench_timing.measure is timing.measure
    assert bench_timing.median is timing.median
