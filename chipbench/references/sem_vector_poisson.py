"""Plain reference for the vector box SEM Poisson solve (CEED BP6).

Three components share the scalar Poisson operator of ``sem_poisson``
(the module beside this one, which shares no code with the program under
test) and are solved as one linear system: the operator and the Jacobi
preconditioner act on each component, while CG's dots, its residual
measure ``sqrt(r . M^-1 r)`` and its stopping rule run over the whole
field.

Fields carry the component axis first: unique nodes ``(3, Gz, Gy, Gx)``,
element copies ``(3, E, n, n, n)``.  :class:`Box` has the interface the
harness uses of ``sem_poisson.Box`` (``gshape``, ``interior64``,
``to_elements``, ``from_elements``, ``precond_weight``, ``residual64``,
``solver``), each with that axis.
"""
from __future__ import annotations

import functools
import importlib.util
import pathlib

import numpy as np

__all__ = ["COMPONENTS", "Box"]

COMPONENTS = 3


def _scalar_reference():
    path = pathlib.Path(__file__).resolve().with_name("sem_poisson.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_references_sem_poisson_scalar", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_scalar = _scalar_reference()


class Box:
    """``COMPONENTS`` fields under the assembled scalar operator of one box
    configuration (``grid`` is ``(EX, EY, EZ)``, ``n`` GLL points)."""

    def __init__(self, n: int, grid, lengths=(1.0, 1.0, 1.0)):
        self.scalar = _scalar.Box(n, grid, lengths)
        self.n, self.grid, self.nelt = (self.scalar.n, self.scalar.grid,
                                        self.scalar.nelt)
        self.gshape = (COMPONENTS,) + self.scalar.gshape
        self.interior64 = np.broadcast_to(self.scalar.interior64,
                                          self.gshape)

    def _each(self, fn, u, xp):
        return xp.stack([fn(u[j]) for j in range(COMPONENTS)])

    # -- layout ------------------------------------------------------------
    def to_elements(self, u, xp=np):
        """``(..., 3, Gz, Gy, Gx)`` -> ``(..., 3, E, n, n, n)``."""
        return self.scalar.to_elements(u, xp)

    def from_elements(self, v, xp=np):
        """``(3, E, n, n, n)`` -> unique nodes, one copy of each node."""
        return self._each(lambda c: self.scalar.from_elements(c, xp), v, xp)

    # -- operator ----------------------------------------------------------
    def apply64(self, u: np.ndarray) -> np.ndarray:
        """``A u`` of each component on the host in float64."""
        return self._each(self.scalar.apply64, u, np)

    def precond_weight(self, precond: str | None) -> np.ndarray:
        """``M^-1`` on the unique nodes of every component: the scalar
        operator's (ones, or its inverse assembled diagonal for
        ``"jacobi"``)."""
        return np.broadcast_to(self.scalar.precond_weight(precond),
                               self.gshape)

    def residual64(self, f, x, weight=None) -> float:
        """True relative residual ``|f - A x| / |f|`` of the whole field in
        float64, in the norm ``sqrt(r . M^-1 r)`` with ``weight``."""
        f = np.asarray(f, np.float64)
        r = f - self.apply64(np.asarray(x, np.float64))
        wgt = 1.0 if weight is None else weight
        return float(np.sqrt(np.sum(r * wgt * r) / np.sum(f * wgt * f)))

    # -- device solver -----------------------------------------------------
    def solver(self, *, precision: str = "highest",
               precond: str | None = None):
        """A jitted ``solve(f, rtol, niter, max_iter) -> (x, iters)`` on
        ``(3, Gz, Gy, Gx)`` float32 fields: one CG (PCG with ``precond``)
        over the whole field, stopping as ``sem_poisson``'s solver does on
        ``r . z <= rtol^2 * r0 . z0`` summed over the components."""
        import jax
        import jax.numpy as jnp

        sc = self.scalar
        f32 = jnp.float32
        D = jnp.asarray(sc.D64, f32)
        grr, gss, gtt = (jnp.asarray(g, f32) for g in sc.metric64)
        interior = jnp.asarray(sc.interior64, f32)
        invd = jnp.asarray(sc.precond_weight(precond), f32) * interior
        ein = _scalar._einsum(precision)

        def A1(u):
            el = sc.to_elements(u, jnp)
            w = sc._local(el, D, grr, gss, gtt, ein)
            return sc.assemble(w, jnp) * interior

        def A(u):
            return self._each(A1, u, jnp)

        def M(r):
            return r if precond is None else invd * r

        @functools.partial(jax.jit, static_argnames=("niter", "max_iter"))
        def solve(f, rtol, *, niter: int, max_iter: int):
            f = f * interior
            z = M(f)
            rz = jnp.sum(f * z)
            stop = rtol * rtol * rz

            def cond(st):
                k, rz = st[0], st[4]
                go_tol = jnp.logical_and(k < max_iter, rz > stop)
                return jnp.where(rtol > 0, go_tol, k < niter)

            def body(st):
                k, x, r, p, rz = st
                w = A(p)
                alpha = rz / jnp.sum(p * w)
                x = x + alpha * p
                r = r - alpha * w
                z = M(r)
                rz_new = jnp.sum(r * z)
                p = z + (rz_new / rz) * p
                return k + 1, x, r, p, rz_new

            st = (jnp.asarray(0), jnp.zeros_like(f), f, z, rz)
            k, x, *_ = jax.lax.while_loop(cond, body, st)
            return x, k

        return solve
