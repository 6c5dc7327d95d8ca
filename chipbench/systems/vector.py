"""The system of ``"entry": "vector"``: a 3-component field (CEED BP6)
through ``NekboneCase.solve`` as one coupled linear system, one field per
call.

It has the interface of ``systems/case.py``: ``build(cfg, solver,
traffic, pool, tols)`` returns an object with ``warm()``, ``solve(k) ->
(x, iters)`` (``x`` as the program returned it, ``(3, E, n, n, n)``,
ready), ``use(pool, tols)`` and ``info() -> dict``.  ``build`` fails at
once on a program whose configuration has no ``components``, or whose
case routes the field anywhere but the coupled ``vector`` row, instead of
solving something else.  Nothing here measures.
"""
from __future__ import annotations

import dataclasses

import jax

import program


class VectorSystem:
    """``NekboneCase.solve`` on one vector field at a time."""

    def __init__(self, cfg: dict, solver: dict, pool: list, tols: list):
        from repro.core import solvers

        self.cfg = cfg
        self.case = dataclasses.replace(
            program.program_config(cfg, solver),
            components=int(cfg["components"])).make_case()
        self.use(pool, tols)
        self.route = solvers.route_name(
            self.case, b=1, niter=self.kw[0].get("niter"),
            pc_name=self.case.precond)
        if self.route != "vector":
            raise RuntimeError(f"the program routes a {cfg['components']}-"
                               f"component field to {self.route!r}, not "
                               "the coupled 'vector' row")
        self.pipeline = None

    def use(self, pool: list, tols: list) -> None:
        """Solve these fields with these absolute tolerances."""
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
        return {"entry": "vector", "route": self.route,
                "pipeline": self.pipeline,
                "components": self.case.components,
                **program.describe(self.case)}


def build(cfg: dict, solver: dict, traffic: dict, pool: list, tols: list):
    return VectorSystem(cfg, solver, pool, tols)
