"""Whether the timed path's answers are right.

Each sampled answer (the solution ``x`` the timed path returned, in the
user's ``(E, n, n, n)`` layout) is set against the configuration's plain
reference, solving the same right-hand side with the same stopping rule
in float32 at ``highest`` matmul precision.  Three numbers can be compared,
each with its limit from the configuration's ``check`` block:

``x_rel_diff``
    ``max |x - x_ref| / max |x_ref|`` over every node copy, worst sample.
``residual``
    The answer's true relative residual ``|f - A x| / |f|`` (in the norm
    of the preconditioner, as the reference weighs it), in float64 on the
    host, worst sample.
``iters_rel_gap``
    ``|iters - iters_ref| / iters_ref``, worst sample: the iterations the
    program reported against the reference's under the same stopping
    rule.  It holds a solve to its tolerance: one that stops early, on a
    looser rule, takes fewer.

The reference's preconditioner and residual weight come from the
reference module the configuration names (``precond_weight``, ``solver``).
A run is correct when every compared number is at most its limit and no
solve stopped at ``max_iter``.
"""
from __future__ import annotations

import numpy as np


class Checker:
    """The reference solves of one configuration, made once per
    right-hand side of the pool that a sample uses."""

    def __init__(self, ref_module, cfg: dict, solver: dict, pool_unique,
                 rtols: list):
        self.cfg = cfg
        self.box = ref_module.Box(cfg["n"], cfg["grid"])
        self.precond = solver.get("precond")
        self.weight = self.box.precond_weight(self.precond)
        self.stop = cfg["stop"]
        self.pool = pool_unique
        self.rtols = rtols
        self._ref = {}
        self._solvers = {}

    def solve(self, k: int, precision: str = "highest"):
        """Reference (or, at ``high``, control) solution of pool entry
        ``k``: unique-node ``x`` and the iterations it took."""
        if precision not in self._solvers:
            self._solvers[precision] = self.box.solver(
                precision=precision, precond=self.precond)
        solve = self._solvers[precision]
        niter = int(self.stop.get("niter", 0))
        max_iter = int(self.stop.get("max_iter", niter))
        x, it = solve(self.pool[k], float(self.rtols[k]), niter=niter,
                      max_iter=max_iter)
        return x, int(it)

    def reference(self, k: int):
        if k not in self._ref:
            self._ref[k] = self.solve(k)
        return self._ref[k]

    def numbers(self, samples: list,
                names=("x_rel_diff", "residual", "iters_rel_gap")) -> dict:
        """Worst of each named number over ``(k, x, iters)`` samples."""
        import jax.numpy as jnp

        worst = dict.fromkeys(names, 0.0)
        for k, x, iters in samples:
            if "iters_rel_gap" in names:
                _, it_ref = self.reference(k)
                worst["iters_rel_gap"] = max(worst["iters_rel_gap"], abs(
                    iters - it_ref) / max(it_ref, 1))
            if "x_rel_diff" in names:
                xr, _ = self.reference(k)
                diff = jnp.max(jnp.abs(x - self.box.to_elements(xr, jnp)))
                worst["x_rel_diff"] = max(worst["x_rel_diff"], float(
                    diff / jnp.max(jnp.abs(xr))))
            if "residual" in names:
                xu = self.box.from_elements(np.asarray(x, np.float64))
                f = np.asarray(self.pool[k], np.float64)
                worst["residual"] = max(worst["residual"],
                                        self.box.residual64(f, xu,
                                                            self.weight))
        return worst


def verdict(numbers: dict, limits: dict) -> list[dict]:
    """``[{"name", "value", "limit", "ok"}]`` for each limited number."""
    return [{"name": name, "value": numbers[name], "limit": lim,
             "ok": bool(numbers[name] <= lim)}
            for name, lim in limits.items()]
