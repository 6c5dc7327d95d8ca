"""p-multigrid V-cycle preconditioner (DESIGN.md §13).

Polynomial-degree coarsening for the box Poisson operator: the same
element grid is rediscretized at a ladder of GLL orders
``n -> ceil(n/2) -> ... -> 2`` (:func:`repro.core.cost.pmg_degrees` — the
HipBone configuration, Chalmers et al. 2022), each fine level smoothed by
the fused Chebyshev(k) apply kernel on a *per-level* Lanczos interval,
levels coupled by tensor-product GLL interpolation
(:func:`gll_interp_matrix`), and the 2^3 base level solved by a few fixed
CG iterations.

The cycle is symmetric (pre- + post-smoothing with the same polynomial;
the Chebyshev smoother ``S = q_k(A)`` is a polynomial in ``A`` and hence
self-adjoint in the c-weighted inner product, so applying the recurrence
forward is already its own reversal) and the two-level operator

    M = 2S - SAS + (I - SA) P C P^T (I - AS)

is symmetric positive definite whenever ``lambda q_k(lambda) in (0, 2)``
on ``(0, lmax]`` — which the smoothing interval ``[lmax/ratio, lmax]``
guarantees: *below* the interval the error polynomial stays in (0, 1), so
``lambda q_k(lambda) = 1 - p(lambda)`` stays in (0, 1) there too (§13.3).
PCG theory therefore applies, up to the deliberate approximation that the
base solve ``C`` is a *fixed-iteration* CG (ISSUE: "a few fixed CG
iterations on the 2^3 operator") — verified the same way the Chebyshev
preconditioner was: interpret-mode parity vs the XLA reference cycle plus
the iters-to-tol acceptance check (benchmarks/pmg_smoke.py).

Transfer operators: prolongation is the element-local tensor-product
interpolation ``e_f = (J x J x J) e_c`` with ``J[i, c] = l_c(x_f[i])``
the coarse Lagrange cardinals at the fine GLL nodes.  Because both grids
contain the endpoints, the endpoint rows of ``J`` are exact 0/1 —
prolongation maps element-face values to element-face values, so it
preserves continuity and the masked (Dirichlet) subspace *exactly*.
Restriction is the c-weighted adjoint in the duplicated-local
representation:

    r_c = mask_c * gs( J^T (c_f * r_f) )

(the gather-scatter transfers onto the other factor of the c-dot for
continuous fields, DESIGN.md §3.2, making ``<u, P e>_c = <R u, e>_c``).

This module holds the spec, the setup (per-level rediscretization +
interval estimation) and the reference (XLA) cycle; the fused driver
lives in ``core/precond._pcg_pmg`` on top of the Pallas interpolation
kernel (`kernels/nekbone_ax.nekbone_interp_kernel`).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost import (PMG_COARSE_ITERS, PMG_DEFAULT_K,
                             PMG_SMOOTH_RATIO, pmg_degrees)
from repro.core.geom import BoxMesh, box_outer
from repro.core.sem import gll_points_weights

__all__ = ["PMG_DEFAULT_K", "PMG_COARSE_ITERS", "PMG_SMOOTH_RATIO",
           "PMGPrecond", "pmg_degrees", "gll_interp_matrix", "interp3",
           "make_pmg_preconditioner", "level_operator", "pmg_level_pytree",
           "coarse_solve_fixed", "pmg_vcycle_reference"]


# ---------------------------------------------------------------------------
# GLL-to-GLL transfer matrices
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def gll_interp_matrix(n_to: int, n_from: int) -> np.ndarray:
    """``(n_to, n_from)`` Lagrange interpolation between GLL grids, f64.

    ``J[i, c] = l_c(x_to[i])`` with ``l_c`` the cardinal functions of the
    ``n_from``-point GLL grid (barycentric form).  Rows at coinciding
    nodes (always the two endpoints, GLL grids contain ±1) are exact
    0/1 — the structural-preservation property the V-cycle relies on.
    ``gll_interp_matrix(nf, nc)`` prolongs coarse -> fine; its transpose
    is the (unweighted part of the) restriction.
    """
    x_to = np.asarray(gll_points_weights(n_to)[0], np.float64)
    x_from = np.asarray(gll_points_weights(n_from)[0], np.float64)
    diff = x_from[:, None] - x_from[None, :]
    np.fill_diagonal(diff, 1.0)
    wbar = 1.0 / np.prod(diff, axis=1)
    J = np.zeros((n_to, n_from), np.float64)
    for i, xt in enumerate(x_to):
        d = xt - x_from
        hit = np.abs(d) < 1e-13
        if hit.any():
            J[i, int(np.argmax(hit))] = 1.0
        else:
            t = wbar / d
            J[i] = t / t.sum()
    return J


def _interp_axis(u: jnp.ndarray, mt: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Contract ``u``'s ``axis`` with ``mt``'s rows (output dim appended
    last) — the exact ``dot_general`` the Pallas interp kernel issues, so
    an independent XLA reference for the Pallas interp kernel."""
    acc = jnp.float64 if u.dtype == jnp.float64 else jnp.float32
    return jax.lax.dot_general(u, mt, (((axis,), (0,)), ((), ())),
                               preferred_element_type=acc)


def interp3(u: jnp.ndarray, M: jnp.ndarray) -> jnp.ndarray:
    """Apply ``M`` (n_out, n_in) along each local axis of ``(E, n_in^3)``
    fields in natural ``(E, k, j, i)`` shape; returns ``(E, n_out^3)``
    natural.  The dense XLA reference for the Pallas interpolation kernel
    (which contracts layer by layer; the two agree to fp64 round-off)."""
    mt = jnp.asarray(M).T.astype(u.dtype)
    v = _interp_axis(u, mt, 3)                           # (E, k, j, io)
    v = _interp_axis(v, mt, 2).transpose(0, 1, 3, 2)     # (E, k, jo, io)
    v = _interp_axis(v, mt, 1).transpose(0, 3, 1, 2)     # (E, ko, jo, io)
    return v


# ---------------------------------------------------------------------------
# spec + setup
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PMGPrecond:
    """p-multigrid V-cycle preconditioner spec (static, hashable).

    ``ns`` is the degree ladder fine -> coarse (``pmg_degrees(n)``);
    ``intervals`` the per-*smoothed*-level Chebyshev smoothing intervals
    ``(lmax/ratio, lmax)`` from per-level Lanczos estimates (one per
    ``ns[:-1]`` entry); ``k`` the smoother order; ``coarse_iters`` the
    fixed CG iteration count of the 2^3 base solve.
    """

    ns: tuple[int, ...]
    k: int
    intervals: tuple[tuple[float, float], ...]
    coarse_iters: int
    lengths: tuple[float, float, float] = (1.0, 1.0, 1.0)
    name: str = dataclasses.field(default="pmg", init=False)

    def scalars(self, level: int) -> np.ndarray:
        """(k+1, 2) f64 Chebyshev recurrence table for a smoothed level."""
        from repro.core.precond import cheb_scalars

        lmin, lmax = self.intervals[level]
        return cheb_scalars(self.k, lmin, lmax)


@functools.lru_cache(maxsize=64)
def _level_mesh(n: int, grid: tuple[int, int, int],
                lengths: tuple[float, float, float]) -> BoxMesh:
    return BoxMesh(n, grid, lengths)


def level_operator(n: int, grid: tuple[int, int, int],
                   lengths: tuple[float, float, float] = (1.0, 1.0, 1.0)):
    """Rediscretized operator data at GLL order ``n``: ``(D, g, mask, c)``.

    The p-coarse levels are *rediscretizations* (HipBone-style), not
    Galerkin products: the same box at a lower order, so every level is
    exactly the operator the existing kernels already implement.
    """
    mesh = _level_mesh(int(n), tuple(grid), tuple(lengths))
    D = mesh.ops.D
    g = mesh.geometric_factors()
    mask = mesh.dirichlet_mask()
    c = mask / mesh.multiplicity()
    return D, g, mask, c


def make_pmg_preconditioner(*, D, g, grid: tuple[int, int, int],
                            mask=None, c=None, k: int = PMG_DEFAULT_K,
                            lengths: tuple[float, float, float] = (1, 1, 1),
                            coarse_iters: int = PMG_COARSE_ITERS,
                            smooth_ratio: float = PMG_SMOOTH_RATIO,
                            intervals=None) -> PMGPrecond:
    """Build a :class:`PMGPrecond` for the operator ``(D, g)`` on ``grid``.

    Per smoothed level the spectrum top ``lmax`` comes from the same
    weighted-Lanczos estimate the Chebyshev preconditioner uses
    (:func:`repro.core.precond.estimate_interval` — level 0 on the
    caller's operator data, coarser levels on their rediscretizations);
    the smoothing interval is ``[lmax / smooth_ratio, lmax]``: the
    smoother only needs to damp what the next-coarser space cannot
    represent, and clipping the interval bottom keeps the degree-k
    polynomial strong there (§13.3; over-estimating ``lmax`` stays the
    safe direction).  ``intervals`` overrides the estimate (a tuple of
    per-level ``(lmin, lmax)``).
    """
    from repro.core.precond import estimate_interval

    grid = tuple(grid)
    n = int(jnp.asarray(D).shape[-1])
    ns = pmg_degrees(n)
    if len(ns) < 2:
        raise ValueError(f"pmg needs n >= 3 to coarsen, got n = {n}")
    if intervals is not None:
        intervals = tuple((float(a), float(b)) for a, b in intervals)
        if len(intervals) != len(ns) - 1:
            raise ValueError(f"need {len(ns) - 1} per-level intervals for "
                             f"ladder {ns}, got {len(intervals)}")
    else:
        ivs = []
        for lev, nl in enumerate(ns[:-1]):
            if lev == 0 and mask is not None:
                lmax = estimate_interval(D, g, grid, mask, c)[1]
            else:
                Dl, gl, ml, cl = level_operator(nl, grid, lengths)
                lmax = estimate_interval(Dl, gl, grid, ml, cl)[1]
            ivs.append((lmax / float(smooth_ratio), lmax))
        intervals = tuple(ivs)
    return PMGPrecond(ns=ns, k=int(k), intervals=intervals,
                      coarse_iters=int(coarse_iters),
                      lengths=tuple(float(x) for x in lengths))


@functools.lru_cache(maxsize=8)
def pmg_level_pytree(spec: PMGPrecond, grid: tuple[int, int, int],
                     op_name: str, acc_name: str):
    """Per-level jnp arrays for the fused driver, as a (hashably cached)
    pytree ``(coefs, transfers, midops, coarse)``:

    * ``coefs[l]``  — (k+1, 2) Chebyshev table of smoothed level ``l``
      (``acc`` dtype, like the cheb driver's);
    * ``transfers[l]`` — ``J_l = gll_interp_matrix(ns[l], ns[l+1])`` in
      the op-storage dtype (``J_l`` restricts as-is via the interp
      kernel's row contraction; its transpose prolongs);
    * ``midops[l-1]`` for levels ``1..L-2`` — ``(D_l, g3_l, mx, my, mz,
      cx, cy, cz)`` in op-storage / factor form, exactly the operands
      the v2 slab + cheb kernels take;
    * ``coarse`` — ``(D_c, g_c, mask_c, c_c)`` natural-shape f-acc data
      for the shared fixed-CG base solve.
    """
    from repro.kernels import ops as kernel_ops

    op_dtype = jnp.dtype(op_name)
    acc_dtype = jnp.dtype(acc_name)
    ns = spec.ns
    E = grid[0] * grid[1] * grid[2]
    coefs = tuple(jnp.asarray(spec.scalars(lev), acc_dtype)
                  for lev in range(len(ns) - 1))
    transfers = tuple(jnp.asarray(gll_interp_matrix(ns[lev], ns[lev + 1]),
                                  op_dtype)
                      for lev in range(len(ns) - 1))
    midops = []
    for lev in range(1, len(ns) - 1):
        nl = ns[lev]
        Dl, gl, _, _ = level_operator(nl, grid, spec.lengths)
        g3l = kernel_ops.diag_metric(jnp.asarray(gl, op_dtype), E, nl)
        (mxl, myl, mzl), (cxl, cyl, czl) = kernel_ops.slab_axis_factors(
            grid, nl, op_dtype)
        midops.append((jnp.asarray(Dl, op_dtype), g3l,
                       mxl, myl, mzl, cxl, cyl, czl))
    nc = ns[-1]
    Dc, gc, mc, cc = level_operator(nc, grid, spec.lengths)
    coarse = (jnp.asarray(Dc, acc_dtype), jnp.asarray(gc, acc_dtype),
              jnp.asarray(mc, acc_dtype), jnp.asarray(cc, acc_dtype))
    return coefs, transfers, tuple(midops), coarse


# ---------------------------------------------------------------------------
# base solve — shared verbatim by the fused and reference cycles, so the
# interpret-mode parity smoke isolates the Pallas kernels
# ---------------------------------------------------------------------------

def coarse_solve_fixed(r: jnp.ndarray, D: jnp.ndarray, g: jnp.ndarray,
                       grid: tuple[int, int, int], mask: jnp.ndarray,
                       c: jnp.ndarray, *, iters: int) -> jnp.ndarray:
    """``iters`` fixed CG iterations on the rediscretized base operator.

    Plain XLA (``ax_local_fused`` + ``ds_sum_local`` + mask; c-weighted
    dots) from a zero initial guess.  The base system is tiny ((EX-1)
    (EY-1)(EZ-1) interior DOFs at n=2), so CG can converge *exactly*
    within ``iters`` — the zero-guarded alpha/beta turn further
    iterations into no-ops instead of 0/0 NaNs.
    """
    from repro.core.ax import ax_local_fused
    from repro.core.gs import ds_sum_local

    grid = tuple(grid)

    def A(v):
        return ds_sum_local(ax_local_fused(v, D, g), grid) * mask

    def dot(u, v):
        return jnp.sum(u * c * v)

    def safe_div(num, den):
        return jnp.where(den != 0, num / jnp.where(den != 0, den, 1.0), 0.0)

    def body(_, state):
        x, res, p, rtz = state
        w = A(p)
        alpha = safe_div(rtz, dot(p, w))
        x = x + alpha * p
        res = res - alpha * w
        rtz_new = dot(res, res)
        beta = safe_div(rtz_new, rtz)
        p = res + beta * p
        return x, res, p, rtz_new

    x0 = jnp.zeros_like(r)
    x, _, _, _ = jax.lax.fori_loop(0, int(iters), body,
                                   (x0, r, r, dot(r, r)))
    return x


# ---------------------------------------------------------------------------
# reference (XLA) V-cycle — the oracle the fused driver's parity smoke
# compares against, and a drop-in precond= callable for core/cg.py
# ---------------------------------------------------------------------------

def pmg_vcycle_reference(spec: PMGPrecond, *, D, g,
                         grid: tuple[int, int, int], mask, c):
    """Reference symmetric V-cycle ``M(r)`` on natural ``(E, n, n, n)``.

    Level 0 runs on the caller's operator data (``D``/``g``/``mask``/
    ``c`` — the case's own fields); coarser levels on their
    rediscretizations.  Same algebra as ``precond._pcg_pmg``: Chebyshev
    pre-smooth, restrict the residual, recurse, prolong-correct,
    Chebyshev post-smooth; base level via :func:`coarse_solve_fixed`.
    """
    grid = tuple(grid)
    ns = spec.ns
    L = len(ns)
    levels = []
    for lev in range(L):
        if lev == 0:
            levels.append((jnp.asarray(D), jnp.asarray(g),
                           jnp.asarray(mask), jnp.asarray(c)))
        else:
            Dl, gl, ml, cl = level_operator(ns[lev], grid, spec.lengths)
            levels.append((jnp.asarray(Dl), jnp.asarray(gl),
                           jnp.asarray(ml), jnp.asarray(cl)))
    transfers = [jnp.asarray(gll_interp_matrix(ns[lev], ns[lev + 1]))
                 for lev in range(L - 1)]
    coefs = [spec.scalars(lev) for lev in range(L - 1)]

    def apply_a(v, lev):
        from repro.core.ax import ax_local_fused
        from repro.core.gs import ds_sum_local

        Dl, gl, ml, _ = levels[lev]
        return ds_sum_local(ax_local_fused(v, Dl, gl), grid) * ml

    def smooth(r, lev):
        coef = coefs[lev]
        d = coef[0, 0] * r
        z = d
        res = r
        for i in range(1, spec.k + 1):
            res = res - apply_a(d, lev)
            d = coef[i, 0] * d + coef[i, 1] * res
            z = z + d
        return z

    def restrict(res, lev):
        from repro.core.gs import ds_sum_local

        _, _, _, cf = levels[lev]
        mc = levels[lev + 1][2]
        t = interp3(res * cf, transfers[lev].T)        # J^T (c_f r_f)
        return ds_sum_local(t, grid) * mc

    def prolong(e, lev):
        mf = levels[lev][2]
        return interp3(e, transfers[lev]) * mf

    def cycle(r, lev):
        # host-recursion V-cycle: each level is a real host region, so a
        # trace (when on) gets one timed "pmg.vcycle" span per level per
        # application — the fused driver's statically-unrolled ladder
        # only exposes its levels at setup (precond._dispatch).
        from repro.obs import trace as _trace

        with _trace.span("pmg.vcycle", level=lev, n=ns[lev]):
            if lev == L - 1:
                Dc, gc, mc, cc = levels[lev]
                return coarse_solve_fixed(r, Dc, gc, grid, mc, cc,
                                          iters=spec.coarse_iters)
            z = smooth(r, lev)
            z = z + prolong(
                cycle(restrict(r - apply_a(z, lev), lev), lev + 1), lev)
            return z + smooth(r - apply_a(z, lev), lev)

    def M(r):
        return cycle(r, 0)

    return M
