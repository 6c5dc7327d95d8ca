"""Plain reference for the box SEM Poisson solve: operator, CG and PCG.

A straightforward implementation of what the benchmark's configurations
state, written from the method (Deville, Fischer and Mund, "High-order
methods for incompressible fluid flow", ch. 4; Nekbone's ``ax``): degree
``n - 1`` Lagrange polynomials on Gauss-Lobatto-Legendre (GLL) points,
affine box elements on an ``EX x EY x EZ`` grid of the unit cube,
homogeneous Dirichlet conditions on the whole boundary.  It shares no code
and no data with the program under test.

Vectors live on the globally unique nodes, shape ``(Gz, Gy, Gx)`` with
``G = E_axis * (n - 1) + 1``; boundary nodes hold 0.  The user-facing layout
``(E, n, n, n)`` (element ``e = (ez * EY + ey) * EX + ex``, nodes ``(k, j,
i)`` with ``i`` along x) is reached by :meth:`Box.to_elements`, and the
assembled operator is ``A = R Q^T A_L Q`` (``Q`` gathers the element copies,
``R`` zeroes the boundary).  CG on unique nodes with the plain dot product
is CG on element copies with dots weighted by ``1 / multiplicity``: the
same iterates in exact arithmetic.

Device code runs in float32 with every contraction at the stated matmul
precision (``highest`` for the reference, ``high`` for the control);
:meth:`Box.residual64` evaluates a true residual on the host in float64.
"""
from __future__ import annotations

import functools

import numpy as np

__all__ = ["gll", "Box"]


def gll(n: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """GLL points ``x``, weights ``w`` and differentiation matrix ``D``
    (``D[i, j] = l_j'(x_i)``) of degree ``N = n - 1``, in float64."""
    from numpy.polynomial import legendre as L

    N = n - 1
    cN = np.zeros(N + 1)
    cN[N] = 1.0
    inner = np.sort(L.legroots(L.legder(cN)).real)
    x = np.concatenate([[-1.0], inner, [1.0]])
    pN = L.legval(x, cN)
    w = 2.0 / (N * (N + 1) * pN ** 2)
    D = np.zeros((n, n))
    for i in range(n):
        for j in range(n):
            if i != j:
                D[i, j] = pN[i] / (pN[j] * (x[i] - x[j]))
    D[0, 0] = -N * (N + 1) / 4.0
    D[N, N] = N * (N + 1) / 4.0
    return x, w, D


def _einsum(precision: str):
    """``jnp.einsum`` in float32 at ``highest`` (float32 products), or at
    ``high``: bfloat16 three-pass products (``a_hi b_hi + a_hi b_lo + a_lo
    b_hi``), written out so that every backend computes the same."""
    import jax
    import jax.numpy as jnp

    exact = functools.partial(jnp.einsum,
                              precision=jax.lax.Precision.HIGHEST)
    if precision == "highest":
        return exact
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")

    def split(v):
        hi = v.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (v - hi).astype(jnp.bfloat16).astype(jnp.float32)

    def three_pass(spec, a, b):
        (ah, al), (bh, bl) = split(a), split(b)
        return (exact(spec, ah, bl) + exact(spec, al, bh)) + exact(spec, ah, bh)

    return three_pass


class Box:
    """The assembled Poisson operator of one box configuration.

    ``grid`` is ``(EX, EY, EZ)``; ``n`` the GLL points per direction.
    """

    def __init__(self, n: int, grid, lengths=(1.0, 1.0, 1.0)):
        self.n = int(n)
        self.grid = tuple(int(e) for e in grid)
        ex, ey, ez = self.grid
        self.nelt = ex * ey * ez
        N = self.n - 1
        self.gshape = (ez * N + 1, ey * N + 1, ex * N + 1)
        _, w, D = gll(self.n)
        hx, hy, hz = (lengths[0] / ex, lengths[1] / ey, lengths[2] / ez)
        w3 = w[:, None, None] * w[None, :, None] * w[None, None, :]
        # metric of an affine element: w3 * J * (dr/dx)^2, J = hx hy hz / 8
        self.metric64 = (w3 * hy * hz / (2 * hx), w3 * hx * hz / (2 * hy),
                         w3 * hx * hy / (2 * hz))
        self.D64 = D
        interior = [np.ones(g) for g in self.gshape]
        for a in interior:
            a[0] = a[-1] = 0.0
        self.interior64 = (interior[0][:, None, None]
                           * interior[1][None, :, None]
                           * interior[2][None, None, :])

    # -- layout ------------------------------------------------------------
    def _idx(self, axis_e: int) -> np.ndarray:
        N = self.n - 1
        return (np.arange(axis_e)[:, None] * N
                + np.arange(self.n)[None, :]).reshape(-1)

    def to_elements(self, u, xp=np):
        """Unique-node field ``(..., Gz, Gy, Gx)`` -> element copies
        ``(..., E, n, n, n)``."""
        ex, ey, ez = self.grid
        n = self.n
        lead = u.shape[:-3]
        k = len(lead)
        v = xp.take(u, self._idx(ez), axis=k)
        v = xp.take(v, self._idx(ey), axis=k + 1)
        v = xp.take(v, self._idx(ex), axis=k + 2)
        v = v.reshape(lead + (ez, n, ey, n, ex, n))
        perm = tuple(range(k)) + tuple(k + a for a in (0, 2, 4, 1, 3, 5))
        return xp.transpose(v, perm).reshape(lead + (self.nelt, n, n, n))

    def from_elements(self, v, xp=np):
        """Element copies ``(E, n, n, n)`` -> unique nodes, reading one copy
        of each node (the copies of an assembled field are equal)."""
        ex, ey, ez = self.grid
        n, N = self.n, self.n - 1
        v6 = xp.transpose(v.reshape(ez, ey, ex, n, n, n), (0, 3, 1, 4, 2, 5))

        def pick(a, axis, ne):
            # node g of the axis comes from element min(g // N, ne - 1)
            g = np.arange(ne * N + 1)
            e = np.minimum(g // N, ne - 1)
            flat = e * n + (g - e * N)
            shape = list(a.shape)
            a = a.reshape(shape[:axis] + [ne * n] + shape[axis + 2:])
            return xp.take(a, flat, axis=axis)

        u = pick(v6, 0, ez)
        u = pick(u, 1, ey)
        return pick(u, 2, ex)

    def _scatter_axis(self, v, axis: int, ne: int, xp):
        """Adjoint of the gather along one axis: ``(.., ne, n, ..)`` ->
        ``(.., ne * (n - 1) + 1, ..)``, summing the shared face nodes."""
        n, N = self.n, self.n - 1
        v = xp.moveaxis(v, (axis, axis + 1), (0, 1))
        rest = v.shape[2:]
        body = v[:, :N].reshape((ne * N,) + rest)
        last = v[:, N]
        tail = xp.concatenate(
            [xp.zeros((ne, N - 1) + rest, v.dtype), last[:, None]], axis=1)
        pad = [(0, 0)] * len(rest)
        out = (xp.pad(body, [(0, 1)] + pad)
               + xp.pad(tail.reshape((ne * N,) + rest), [(1, 0)] + pad))
        return xp.moveaxis(out, 0, axis)

    def assemble(self, w, xp=np):
        """``Q^T``: element contributions ``(E, n, n, n)`` summed onto the
        unique nodes."""
        ex, ey, ez = self.grid
        n = self.n
        v = xp.transpose(w.reshape(ez, ey, ex, n, n, n), (0, 3, 1, 4, 2, 5))
        v = self._scatter_axis(v, 0, ez, xp)
        v = self._scatter_axis(v, 1, ey, xp)
        return self._scatter_axis(v, 2, ex, xp)

    # -- operator ----------------------------------------------------------
    @staticmethod
    def _local(u, D, grr, gss, gtt, einsum):
        """``A_L u`` per element: ``D^T G D`` with the diagonal box metric."""
        ur = einsum("ia,ekja->ekji", D, u)
        us = einsum("jb,ekbi->ekji", D, u)
        ut = einsum("kc,ecji->ekji", D, u)
        return (einsum("ai,ekja->ekji", D, grr * ur)
                + einsum("bj,ekbi->ekji", D, gss * us)
                + einsum("ck,ecji->ekji", D, gtt * ut))

    def apply64(self, u: np.ndarray) -> np.ndarray:
        """``A u`` on the host in float64 (unique-node layout)."""
        el = self.to_elements(np.asarray(u, np.float64))
        ein = functools.partial(np.einsum, optimize=True)
        w = self._local(el, self.D64, *self.metric64, einsum=ein)
        return self.assemble(w) * self.interior64

    def diag64(self) -> np.ndarray:
        """Assembled diagonal of ``A`` (1 on the boundary), float64."""
        n = self.n
        D2 = self.D64 ** 2
        grr, gss, gtt = self.metric64
        d = (np.einsum("ai,kja->kji", D2, grr)
             + np.einsum("bj,kbi->kji", D2, gss)
             + np.einsum("ck,cji->kji", D2, gtt))
        d = self.assemble(np.broadcast_to(d, (self.nelt, n, n, n)))
        return np.where(self.interior64 > 0, d, 1.0)

    def precond_weight(self, precond: str | None) -> np.ndarray:
        """``M^-1`` of a diagonal preconditioner on the unique nodes, in
        float64: ones without one, the inverse assembled diagonal for
        ``"jacobi"``.  It weighs the residual norm ``sqrt(r . M^-1 r)``
        that the stopping rule and :meth:`residual64` use."""
        if precond is None:
            return np.ones(self.gshape)
        if precond == "jacobi":
            return 1.0 / self.diag64()
        raise ValueError(f"this reference has no preconditioner "
                         f"{precond!r}")

    def residual64(self, f, x, weight=None) -> float:
        """True relative residual ``|f - A x| / |f|`` in float64, both
        unique-node fields; with ``weight`` (``precond_weight``) the norms
        are ``sqrt(r . M^-1 r)``."""
        f = np.asarray(f, np.float64)
        r = f - self.apply64(x)
        wgt = 1.0 if weight is None else weight
        return float(np.sqrt(np.sum(r * wgt * r) / np.sum(f * wgt * f)))

    # -- device solvers ----------------------------------------------------
    def solver(self, *, precision: str = "highest",
               precond: str | None = None):
        """A jitted ``solve(f, niter, rtol, max_iter) -> (x, iters)`` on
        unique-node float32 fields.

        With ``rtol`` the loop stops before an iteration once
        ``r . z <= rtol^2 * r0 . z0`` (``z = M^-1 r``, ``M`` the
        preconditioner ``precond`` names, ``I`` for ``None``), or at
        ``max_iter``; with ``rtol = 0`` it runs exactly ``niter``
        iterations.
        """
        import jax
        import jax.numpy as jnp

        f32 = jnp.float32
        D = jnp.asarray(self.D64, f32)
        grr, gss, gtt = (jnp.asarray(g, f32) for g in self.metric64)
        interior = jnp.asarray(self.interior64, f32)
        invd = jnp.asarray(self.precond_weight(precond), f32) * interior
        ein = _einsum(precision)

        def A(u):
            el = self.to_elements(u, jnp)
            w = self._local(el, D, grr, gss, gtt, ein)
            return self.assemble(w, jnp) * interior

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
