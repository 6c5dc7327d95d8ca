"""The system of ``"entry": "case"``: ``NekboneCase.solve`` through the
``core/solvers.py`` registry, one right-hand side per call.

A system module has ``build(cfg, solver, traffic, pool, tols)``, which
returns an object with ``warm()``, ``solve(k) -> (x, iters)`` (``x`` as
the program returned it, in the user's ``(E, n, n, n)`` layout, ready),
``use(pool, tols)`` and ``info() -> dict``.  Nothing here measures.
"""
from __future__ import annotations

import jax

import program


class CaseSystem:
    """``NekboneCase.solve`` on one right-hand side at a time."""

    def __init__(self, cfg: dict, solver: dict, pool: list, tols: list):
        from repro.core import solvers

        self.cfg = cfg
        self.case = program.program_config(cfg, solver).make_case()
        self.use(pool, tols)
        self.route = solvers.route_name(
            self.case, b=1, niter=self.kw[0].get("niter"),
            pc_name=self.case.precond)
        self.pipeline = None

    def use(self, pool: list, tols: list) -> None:
        """Solve these right-hand sides with these absolute tolerances."""
        self.pool = pool
        self.kw = [program.stop_kwargs(self.cfg, t) for t in tols]

    def warm(self) -> None:
        self.solve(0)

    def solve(self, k: int):
        res = self.case.solve(self.pool[k], **self.kw[k])
        jax.block_until_ready(res.x)
        self.pipeline = res.pipeline
        return res.x, int(res.iters_taken)

    def info(self) -> dict:
        return {"entry": "case", "route": self.route,
                "pipeline": self.pipeline, **program.describe(self.case)}


def build(cfg: dict, solver: dict, traffic: dict, pool: list, tols: list):
    return CaseSystem(cfg, solver, pool, tols)
