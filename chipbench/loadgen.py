"""The load generator: one window of a traffic mix.

A traffic mix (``traffic/<name>.json``) holds parameters only:

``loop``
    The shape of the offered load, a module ``loops/<loop>.py`` that
    drives the system (``closed``: one caller, back to back).  A new
    shape of arrivals is a new module; a new mix of an existing shape is
    a new data file.
``pool``
    How many distinct right-hand sides the seed makes.
``sample``
    How many answers of the window are checked against the reference,
    drawn from the seed over all answers that completed.
``trace_seconds``
    The length of the traced window of a ``--trace 1`` run.
``solver`` (optional)
    Keys that override the configuration's ``solver`` block.

Further keys are the loop module's own parameters.
"""
from __future__ import annotations

import time

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one use of the run's seed (any integer)."""
    return np.random.default_rng(np.random.SeedSequence([seed % 2**64,
                                                         stream]))


class Reservoir:
    """A uniform sample of ``k`` items from a stream, drawn from the seed
    (Algorithm R): the sample does not depend on timing, only on how
    many items came."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = rng_for(seed, 2)
        self.seen = 0
        self.items: list = []

    def offer(self, item) -> None:
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.items[j] = item
        self.seen += 1


def window(loop, system, pool: list, traffic: dict, seconds: float,
           seed: int, sample: Reservoir, clock=time.perf_counter) -> dict:
    """One window of ``traffic`` through the loop module ``loop``."""
    return loop.drive(system, pool, traffic, seconds, seed, sample, clock)
