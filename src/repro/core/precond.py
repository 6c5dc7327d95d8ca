"""Preconditioning subsystem for the fused CG pipelines (DESIGN.md §9).

The paper's benchmark protocol runs *unpreconditioned* CG (its §V), and
flags diagonal preconditioning as future work; HipBone (Chalmers et al.,
PAPERS.md) shows the NekBone benchmark generalizes cleanly to
preconditioned solves on GPUs, and the tensor-product kernels this repo
fuses are exactly the building block a polynomial smoother/preconditioner
needs (Świrydowicz et al.).  This module makes preconditioning a
first-class workload layer over every existing pipeline:

* **Jacobi (diagonal) PCG fused into the v2 slab pipeline**
  (:func:`pcg_fused_v2_fixed_iters` with a :class:`JacobiPrecond`): the
  operator diagonal is computed once per case
  (:func:`operator_diagonal`), inverted, and kept slab-resident; the
  solver carries the *preconditioned* residual ``z = D^-1 r`` so the v2
  front-half kernel is reused unchanged (``p = z + beta p`` is its
  direction update) and the merged back-half
  (`kernels/nekbone_ax.nekbone_pcg_update_kernel`) applies ``M^-1``
  in-kernel — PCG costs exactly **one extra stream/iter** (14 vs 13,
  `cost.JACOBI_V2_*`, pinned by the regression gate).

* **Chebyshev polynomial PCG** (:class:`ChebyshevPrecond`):
  ``z = q_k(A) r`` with ``q_k`` the degree-k Chebyshev approximation of
  ``A^-1`` on an interval bracketing the spectrum.  One application is k
  chained assembled operator applications — the v3 matrix-powers
  structure — so the apply kernel
  (`kernels/nekbone_ax.nekbone_cheb_apply_kernel`) reuses the §8 halo
  machinery (k ghost slabs per side, `sstep_extend_field` windows) to
  evaluate the whole polynomial in **one slab residency**: r + 3 metric
  diagonals in, z out (18 streams/iter total, `cost.CHEB_V2_*`; the win
  is the iteration count).  The interval comes from
  :func:`estimate_interval` — a weighted-Lanczos eigenvalue estimate
  that extends ``cg_sstep.estimate_theta``'s one-sided power iteration
  to both ends of the spectrum.

* **Tolerance-driven fused solves** (:func:`cg_fused_tol`): the same
  per-iteration bodies under a ``lax.while_loop`` with
  :func:`repro.core.cg.cg`'s stopping rule (`|rtz| <= tol**2`, checked
  *before* each iteration), for the unpreconditioned v2 pipeline and
  both PCG variants.  The iteration body is shared with the
  fixed-iteration drivers (``cg_fused._v2_iter`` and the `_pcg_*` cores
  below run with a ``tol2 = -1`` sentinel), so the tolerance-driven
  trajectory reproduces the fixed-iteration trajectory as a prefix *by
  construction*.  The s-step driver gets the same semantics per cycle
  (``cg_sstep_fixed_iters(tol=...)``) with the stopping point resolved
  to iteration granularity through the f64 Gram recurrence.

Preconditions are the v2 pipeline's (structured axis-aligned box,
assembled+masked ``b``); the ``precision`` policy (DESIGN.md §7)
composes unchanged — the carried ``z`` streams at storage width and both
reduction partials see the *stored* vector; the operator diagonal and
the Chebyshev windows are operator data (``op_storage`` dtype).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.gs as gs_mod
from repro.core import pmg as _pmg
from repro.core.cg import CGResult, SolveResult
from repro.core.cg_fused import (_check_box_fields, _v2_iter, initial_rtz,
                                 v2_operands)
from repro.core.cost import CHEB_DEFAULT_K, PMG_DEFAULT_K
from repro.core.geom import box_axis_factors, box_outer
from repro.core.precision import resolve_policy
from repro.kernels import autotune as _autotune
from repro.kernels import nekbone_ax as _ax
from repro.obs import trace as _trace

__all__ = ["CHEB_DEFAULT_K", "PMG_DEFAULT_K", "JacobiPrecond",
           "ChebyshevPrecond", "PMGPrecond",
           "make_preconditioner", "operator_diagonal", "estimate_interval",
           "cheb_scalars", "chebyshev_preconditioner",
           "pcg_fused_v2_fixed_iters", "cg_fused_tol"]

# re-exported so every preconditioner spec is importable from one place
# (the pmg module owns the V-cycle setup/reference; the fused driver
# lives here, next to its cheb/jacobi siblings).
PMGPrecond = _pmg.PMGPrecond


# ---------------------------------------------------------------------------
# operator diagonal (Jacobi)
# ---------------------------------------------------------------------------

def operator_diagonal(D: jnp.ndarray, g: jnp.ndarray, grid, mask) -> jnp.ndarray:
    """diag(A) of the assembled, masked SEM Poisson operator, structurally.

    For the tensor-product operator ``w = D^T G D u`` the element-local
    diagonal is three small contractions of ``D ∘ D`` against the metric
    diagonal; assembly (gather-scatter) then sums coincident copies.
    Masked rows are set to 1 (identity-like — they carry no residual), so
    the inverse never divides by zero.

    Args:
      D: (n, n); g: (E, 6, n, n, n) metric or its (E, 3, ...) diagonal;
      grid: element grid; mask: (E, n, n, n) Dirichlet mask.
    """
    g = jnp.asarray(g)
    if g.shape[1] == 6:
        grr, gss, gtt = g[:, 0], g[:, 3], g[:, 5]
    elif g.shape[1] == 3:
        grr, gss, gtt = g[:, 0], g[:, 1], g[:, 2]
    else:
        raise ValueError(f"metric must have 3 or 6 components, got {g.shape}")
    D2 = D * D  # (a, b): D[a,b]^2
    dr = jnp.einsum("li,ekjl->ekji", D2, grr)
    ds = jnp.einsum("lj,ekli->ekji", D2, gss)
    dt = jnp.einsum("lk,elji->ekji", D2, gtt)
    diag = gs_mod.ds_sum_local(dr + ds + dt, tuple(grid))
    return jnp.where(jnp.asarray(mask) > 0, diag, 1.0)


# ---------------------------------------------------------------------------
# Chebyshev recurrence scalars and the reference (XLA) applier
# ---------------------------------------------------------------------------

def cheb_scalars(k: int, lmin: float, lmax: float) -> np.ndarray:
    """Chebyshev-semi-iteration recurrence scalars for ``q_k(A) ≈ A^-1``.

    The incremental-residual form (Saad, *Iterative Methods*, Alg. 12.1,
    started from ``x0 = 0``) applied for ``k`` operator applications:

        d = coef[0,0] * r;  z = d;  res = r
        for i in 1..k:
            res -= A d
            d    = coef[i,0] * d + coef[i,1] * res
            z   += d

    yields the degree-k polynomial whose error ``1 - λ q_k(λ)`` is the
    scaled-and-shifted Chebyshev polynomial minimizing the max over
    ``[lmin, lmax]``.  On that interval ``λ q_k(λ) ∈ (0, 2)``, so ``q_k``
    is positive there — ``M^-1 = q_k(A)`` is SPD whenever the interval
    covers the spectrum (over-estimating ``lmax`` is the safe direction;
    under-estimating ``lmin`` only costs effectiveness, §9.3).

    Returns an (k+1, 2) float64 array: row 0 = (1/θ, 0) with
    ``θ = (lmax+lmin)/2``; row i = (ρ_i ρ_{i-1}, 2 ρ_i / δ) with
    ``δ = (lmax-lmin)/2``, ``σ1 = θ/δ``, ``ρ_0 = 1/σ1``,
    ``ρ_i = 1/(2σ1 - ρ_{i-1})``.
    """
    if k < 1:
        raise ValueError(f"Chebyshev order must be >= 1, got {k}")
    lmin = float(lmin)
    lmax = float(lmax)
    if not (0.0 < lmin < lmax) or not np.isfinite(lmax):
        raise ValueError(f"need 0 < lmin < lmax, got [{lmin}, {lmax}]")
    theta = 0.5 * (lmax + lmin)
    delta = 0.5 * (lmax - lmin)
    sigma1 = theta / delta
    rho_prev = 1.0 / sigma1
    coef = np.zeros((k + 1, 2), np.float64)
    coef[0, 0] = 1.0 / theta
    for i in range(1, k + 1):
        rho = 1.0 / (2.0 * sigma1 - rho_prev)
        coef[i, 0] = rho * rho_prev
        coef[i, 1] = 2.0 * rho / delta
        rho_prev = rho
    return coef


def chebyshev_preconditioner(A, k: int, lmin: float, lmax: float):
    """Reference (XLA-composed) Chebyshev applier ``M(r) = q_k(A) r``.

    The oracle the fused kernel's parity tests compare against, and a
    drop-in ``precond=`` callable for :func:`repro.core.cg.cg` /
    ``cg_fixed_iters`` on any operator ``A`` (not just the box).
    """
    coef = cheb_scalars(k, lmin, lmax)

    def M(r):
        d = coef[0, 0] * r
        z = d
        res = r
        for i in range(1, k + 1):
            res = res - A(d)
            d = coef[i, 0] * d + coef[i, 1] * res
            z = z + d
        return z

    return M


# ---------------------------------------------------------------------------
# spectrum interval estimate: weighted Lanczos (extends estimate_theta)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("grid", "iters"))
def _lanczos_tridiag(D, g, mask, c, *, grid: tuple[int, int, int],
                     iters: int):
    """``iters`` steps of Lanczos on the assembled masked operator.

    Runs in the c-weighted inner product (the one ``A`` is self-adjoint
    in on continuous fields — the same identity the fused pap partial
    rests on, DESIGN.md §3.2); the start vector is one operator
    application of the deterministic ramp ``cg_sstep._theta_power_iter``
    uses, which makes it continuous (gs output) and drops any component
    outside range(A).  Returns the tridiagonal entries
    ``(alphas[iters], betas[iters])`` — no reorthogonalization (the
    extreme Ritz values converge first, which is all the interval
    needs).
    """
    from repro.core.ax import ax_local_fused

    tiny = jnp.asarray(np.finfo(np.float32).tiny, mask.dtype)

    def A(v):
        return gs_mod.ds_sum_local(ax_local_fused(v, D, g), grid) * mask

    def dot(u, v):
        return jnp.sum(u * c * v)

    v0 = A(jnp.linspace(1.0, 2.0, mask.size).reshape(mask.shape)
           .astype(mask.dtype) * mask)
    q = v0 / jnp.maximum(jnp.sqrt(jnp.abs(dot(v0, v0))), tiny)

    def body(j, carry):
        q_prev, q, beta, alphas, betas = carry
        w = A(q)
        alpha = dot(w, q)
        w = w - alpha * q - beta * q_prev
        beta_new = jnp.sqrt(jnp.abs(dot(w, w)))
        q_new = w / jnp.maximum(beta_new, tiny)
        alphas = alphas.at[j].set(alpha)
        betas = betas.at[j].set(beta_new)
        return q, q_new, beta_new, alphas, betas

    zeros = jnp.zeros((iters,), mask.dtype)
    _, _, _, alphas, betas = jax.lax.fori_loop(
        0, iters, body, (jnp.zeros_like(q), q, jnp.zeros((), mask.dtype),
                         zeros, zeros))
    return alphas, betas


def estimate_interval(D: jnp.ndarray, g: jnp.ndarray,
                      grid: tuple[int, int, int], mask: jnp.ndarray,
                      c: jnp.ndarray | None = None,
                      iters: int = 16) -> tuple[float, float]:
    """Lanczos estimate of ``[λmin, λmax]`` for the Chebyshev interval.

    Extends ``cg_sstep.estimate_theta`` (a one-sided power iteration on
    ``‖A‖``) to both ends of the spectrum: the tridiagonal Ritz values of
    a short weighted-Lanczos run bracket the extreme eigenvalues from
    inside, so the returned interval applies safety factors in the
    *safe* directions — λmax is inflated (the SPD-critical end: the
    Chebyshev error polynomial is only bounded inside the interval's
    right edge) and λmin deflated (under-shooting it merely weakens the
    polynomial, §9.3).  A one-time setup cost per case, like theta.

    Returns a ``(lmin, lmax)`` float pair, guaranteed
    ``0 < lmin < lmax`` (degenerate estimates fall back to
    ``lmax / 100``).
    """
    grid = tuple(grid)
    if c is None:
        (mxf, myf, mzf), (cxf, cyf, czf) = box_axis_factors(grid,
                                                            mask.shape[-1])
        c = box_outer(czf, cyf, cxf).reshape(mask.shape)
    alphas, betas = _lanczos_tridiag(jnp.asarray(D), jnp.asarray(g),
                                     jnp.asarray(mask),
                                     jnp.asarray(c, mask.dtype),
                                     grid=grid, iters=int(iters))
    alphas = np.asarray(alphas, np.float64)
    betas = np.asarray(betas, np.float64)
    # truncate at Krylov breakdown (beta ~ 0): later entries are noise.
    scale = max(np.abs(alphas).max(), 1.0)
    good = np.nonzero(betas < 1e-12 * scale)[0]
    m = int(good[0]) + 1 if good.size else alphas.size
    T = np.diag(alphas[:m])
    if m > 1:
        off = betas[:m - 1]
        T += np.diag(off, 1) + np.diag(off, -1)
    ritz = np.linalg.eigvalsh(T)
    lmax = float(ritz[-1]) * 1.05
    lmin = float(ritz[0]) * 0.9
    if not np.isfinite(lmax) or lmax <= 0.0:
        return 0.01, 1.0
    if not np.isfinite(lmin) or lmin <= 0.0 or lmin >= lmax:
        lmin = lmax / 100.0
    return lmin, lmax


# ---------------------------------------------------------------------------
# preconditioner specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class JacobiPrecond:
    """Diagonal preconditioner: slab-resident assembled ``1/diag(A)``."""

    invdiag: jnp.ndarray                 # (E, n, n, n), 1 at masked rows
    name: str = dataclasses.field(default="jacobi", init=False)


@dataclasses.dataclass(frozen=True)
class ChebyshevPrecond:
    """Chebyshev polynomial preconditioner of order ``k`` on an interval."""

    k: int
    lmin: float
    lmax: float
    name: str = dataclasses.field(default="cheb", init=False)

    def scalars(self) -> np.ndarray:
        """The (k+1, 2) f64 recurrence-scalar table (:func:`cheb_scalars`)."""
        return cheb_scalars(self.k, self.lmin, self.lmax)


def make_preconditioner(name: str, *, D: jnp.ndarray, g: jnp.ndarray,
                        grid: tuple[int, int, int],
                        mask: jnp.ndarray | None = None,
                        c: jnp.ndarray | None = None,
                        k: int = CHEB_DEFAULT_K,
                        interval: tuple[float, float] | None = None,
                        lengths: tuple[float, float, float] = (1.0, 1.0,
                                                               1.0)):
    """Build a preconditioner spec from its registry name.

    Args:
      name: ``"jacobi"``; ``"cheb"``/``"chebyshev"`` (optionally with a
            trailing order, e.g. ``"cheb2"`` — overrides ``k``); or
            ``"pmg"`` (optionally with a smoother order, ``"pmg[cheb2]"``)
            — the p-multigrid V-cycle (DESIGN.md §13).
      D/g/grid: the operator's defining data, as the fused drivers take.
      mask/c: structural fields (rebuilt from the box factors if omitted).
      k: Chebyshev order (default :data:`CHEB_DEFAULT_K`; the pmg
         smoother has its own default, :data:`CHEB_DEFAULT_K` does not
         leak into it).
      interval: Chebyshev ``(lmin, lmax)`` override (default: the
            :func:`estimate_interval` Lanczos estimate — a one-time setup
            cost per case).
      lengths: physical box extents — pmg only (its coarse levels are
            rediscretizations of the same box, so they must know it).
    """
    grid = tuple(grid)
    if mask is None:
        n = jnp.asarray(D).shape[-1]
        (mxf, myf, mzf), _ = box_axis_factors(grid, n)
        mask = box_outer(mzf, myf, mxf).reshape(-1, n, n, n)
        mask = jnp.asarray(mask, jnp.asarray(g).dtype)
    key = str(name).lower()
    if key == "jacobi":
        diag = operator_diagonal(jnp.asarray(D), g, grid, mask)
        return JacobiPrecond(invdiag=1.0 / diag)
    if key.startswith("pmg"):
        suffix = key.removeprefix("pmg")
        kk = PMG_DEFAULT_K
        if suffix:
            inner = suffix.removeprefix("[cheb").removesuffix("]")
            if (suffix == f"[cheb{inner}]" and inner.isdigit()
                    and int(inner) >= 1):
                kk = int(inner)
            else:
                raise ValueError(f"unknown preconditioner {name!r}; the "
                                 "pmg spellings are 'pmg' and "
                                 "'pmg[cheb<k>]'")
        return _pmg.make_pmg_preconditioner(D=D, g=g, grid=grid, mask=mask,
                                            c=c, k=kk, lengths=lengths)
    if key.startswith("cheb"):
        suffix = key.removeprefix("chebyshev").removeprefix("cheb")
        if suffix:
            k = int(suffix)
        if interval is None:
            interval = estimate_interval(D, g, grid, mask, c)
        return ChebyshevPrecond(k=int(k), lmin=float(interval[0]),
                                lmax=float(interval[1]))
    raise ValueError(f"unknown preconditioner {name!r}; expected 'jacobi', "
                     "'cheb[<k>]', 'pmg', or 'pmg[cheb<k>]'")


# ---------------------------------------------------------------------------
# jitted solver cores.  All three share the stopping rule of core/cg.cg —
# the while_loop runs while  k < max_iter  AND  |rtz| > tol2 — and the
# fixed-iteration entry points reuse them with the sentinel tol2 = -1
# (never satisfied, so exactly max_iter iterations run and the trajectory
# is the tol-driven one's continuation — the prefix property).
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n", "grid", "max_iter", "sz",
                                             "interpret", "acc_name",
                                             "x_name", "grid_order"))
def _cg_v2_tol(b, D, Dt, g3, mx, my, mz, cx, cy, cz, tol2, *, n: int,
               grid: tuple[int, int, int], max_iter: int, sz: int,
               interpret: bool, acc_name: str, x_name: str,
               grid_order: str = "parallel") -> CGResult:
    acc = jnp.dtype(acc_name)
    x_dtype = jnp.dtype(x_name)
    b2, g3, _ = v2_operands(b, g3, cz, cy, cx, acc)
    rtz0 = initial_rtz(b, cz, cy, cx, acc)
    hist0 = jnp.full((max_iter + 1,), jnp.nan, dtype=acc)
    tol2 = jnp.asarray(tol2, acc)

    def cond(state):
        _, _, _, rtz, _, _, kk = state
        return jnp.logical_and(kk < max_iter, jnp.abs(rtz) > tol2)

    def body(state):
        x2, r2, p2, rtz, beta, hist, kk = state
        hist = hist.at[kk].set(jnp.sqrt(jnp.abs(rtz)))
        x2, r2, p2, rtz_new, beta = _v2_iter(
            x2, r2, p2, rtz, beta, D=D, Dt=Dt, g3=g3, mx=mx, my=my, mz=mz,
            cx=cx, cy=cy, cz=cz, n=n, grid=grid, sz=sz, interpret=interpret,
            acc_name=acc_name, grid_order=grid_order)
        return x2, r2, p2, rtz_new, beta, hist, kk + 1

    state = (jnp.zeros(b2.shape, x_dtype), b2, jnp.zeros_like(b2), rtz0,
             jnp.zeros((), acc), hist0, jnp.asarray(0))
    x2, r2, p2, rtz, beta, hist, kk = jax.lax.while_loop(cond, body, state)
    hist = hist.at[kk].set(jnp.sqrt(jnp.abs(rtz)))
    return CGResult(x=_ax.from_lanes(x2, n).reshape(b.shape), iters=kk,
                    rnorm=hist[kk], rnorm_history=hist)


@functools.partial(jax.jit, static_argnames=("n", "grid", "max_iter", "sz",
                                             "interpret", "acc_name",
                                             "x_name", "grid_order"))
def _pcg_jacobi(b, invd, D, Dt, g3, mx, my, mz, cx, cy, cz, tol2, *, n: int,
                grid: tuple[int, int, int], max_iter: int, sz: int,
                interpret: bool, acc_name: str, x_name: str,
                grid_order: str = "parallel") -> CGResult:
    """Fused Jacobi-PCG core: v2 slab front-half + PCG update back-half.

    The loop state carries ``z = invdiag * r`` instead of ``r``
    (DESIGN.md §9.2): the slab kernel's merged direction update
    ``p = z + beta p`` and its pap partial are then exactly PCG's, and
    only the update kernel needs the extra ``invdiag`` stream (14
    streams/iter).  ``rtz = r·c·z`` drives alpha/beta and the stopping
    rule (as in :func:`repro.core.cg.cg`); the history records the
    reconstructed ``sqrt(r·c·r)``, directly comparable to
    unpreconditioned CG's.
    """
    acc = jnp.dtype(acc_name)
    x_dtype = jnp.dtype(x_name)
    b2, g3, c2 = v2_operands(b, g3, cz, cy, cx, acc)
    invd2 = _ax.to_lanes(invd, n)
    b_acc = b2.astype(acc)
    # z0 rounded through storage — the slab kernel reads the stored z
    # (§7 rule 1's analog for the carried vector).
    z0 = (invd2.astype(acc) * b_acc).astype(b.dtype)
    rtz0 = jnp.sum(b_acc * c2 * z0.astype(acc))
    rcr0 = jnp.sum(b_acc * c2 * b_acc)
    hist0 = jnp.full((max_iter + 1,), jnp.nan, dtype=acc) \
        .at[0].set(jnp.sqrt(jnp.abs(rcr0)))
    tol2 = jnp.asarray(tol2, acc)

    def cond(state):
        _, _, _, rtz, _, _, kk = state
        return jnp.logical_and(kk < max_iter, jnp.abs(rtz) > tol2)

    def body(state):
        x2, z2, p2, rtz, beta, hist, kk = state
        p2, w2, bot, top, pap_b = _ax.nekbone_ax_slab_pallas(
            p2, z2, D, Dt, g3, mx, my, mz, beta.reshape(1, 1),
            n=n, grid=grid, sz=sz, interpret=interpret, acc_dtype=acc_name,
            grid_order=grid_order)
        alpha = rtz / jnp.sum(pap_b)
        addb, addt = _ax.shift_planes(bot, top)
        x2, z2, rtz_b, rcr_b = _ax.nekbone_pcg_update_pallas(
            x2, p2, z2, w2, addb, addt, alpha.reshape(1, 1), invd2,
            cx, cy, cz, n=n, grid=grid, sz=sz, interpret=interpret,
            acc_dtype=acc_name)
        rtz_new = jnp.sum(rtz_b)
        beta = rtz_new / rtz
        hist = hist.at[kk + 1].set(jnp.sqrt(jnp.abs(jnp.sum(rcr_b))))
        return x2, z2, p2, rtz_new, beta, hist, kk + 1

    state = (jnp.zeros(b2.shape, x_dtype), z0, jnp.zeros_like(z0), rtz0,
             jnp.zeros((), acc), hist0, jnp.asarray(0))
    x2, z2, p2, rtz, beta, hist, kk = jax.lax.while_loop(cond, body, state)
    return CGResult(x=_ax.from_lanes(x2, n).reshape(b.shape), iters=kk,
                    rnorm=hist[kk], rnorm_history=hist)


@functools.partial(jax.jit, static_argnames=("n", "grid", "max_iter", "sz",
                                             "sz_c", "k", "interpret",
                                             "acc_name", "x_name",
                                             "grid_order"))
def _pcg_cheb(b, D, Dt, g3, mx, my, mz, cx, cy, cz, coef, tol2, *, n: int,
              grid: tuple[int, int, int], max_iter: int, sz: int, sz_c: int,
              k: int, interpret: bool, acc_name: str, x_name: str,
              grid_order: str = "parallel") -> CGResult:
    """Fused Chebyshev-PCG core: cheb apply + v2 slab + v2 update.

    Per iteration: the halo'd Chebyshev kernel evaluates
    ``z = q_k(A) r`` and the ``rtz = r·c·z`` partial in one slab
    residency (it runs at the *end* of the body, on the freshly updated
    residual, so the while_loop's stopping rule sees the same rtz
    :func:`repro.core.cg.cg` checks); the unmodified v2 slab and update
    kernels then run the direction update / operator / axpys — 13 + 5 =
    18 streams/iter (DESIGN.md §9.3), the win being the iteration count.
    """
    acc = jnp.dtype(acc_name)
    x_dtype = jnp.dtype(x_name)
    b2, g3, c2 = v2_operands(b, g3, cz, cy, cx, acc)
    rcr0 = jnp.sum(b2.astype(acc) * c2 * b2.astype(acc))
    # halo'd operator windows for the cheb kernel, built once per solve
    # (loop-invariant); the per-iteration residual window gather below is
    # part of the halo side channel (§8.2's honesty note).
    gext = _ax.sstep_extend_field(g3, grid, sz_c, k)
    mzext = _ax.sstep_extend_zfactor(mz, sz_c, k)

    def cheb(r2):
        rext = _ax.sstep_extend_field(r2, grid, sz_c, k)
        z2, rtz_b = _ax.nekbone_cheb_apply_pallas(
            rext, D, Dt, gext, mx, my, mzext, cx, cy, cz, coef,
            n=n, grid=grid, sz=sz_c, k=k, interpret=interpret,
            acc_dtype=acc_name, grid_order=grid_order)
        return z2, jnp.sum(rtz_b)

    z0, rtz0 = cheb(b2)
    hist0 = jnp.full((max_iter + 1,), jnp.nan, dtype=acc) \
        .at[0].set(jnp.sqrt(jnp.abs(rcr0)))
    tol2 = jnp.asarray(tol2, acc)

    def cond(state):
        _, _, _, _, rtz, _, _, kk = state
        return jnp.logical_and(kk < max_iter, jnp.abs(rtz) > tol2)

    def body(state):
        x2, r2, z2, p2, rtz, rtz_prev, hist, kk = state
        beta = rtz / rtz_prev            # rtz_prev = 1 at k=0: p0 = 0
        p2, w2, bot, top, pap_b = _ax.nekbone_ax_slab_pallas(
            p2, z2, D, Dt, g3, mx, my, mz, beta.reshape(1, 1),
            n=n, grid=grid, sz=sz, interpret=interpret, acc_dtype=acc_name,
            grid_order=grid_order)
        alpha = rtz / jnp.sum(pap_b)
        addb, addt = _ax.shift_planes(bot, top)
        x2, r2, rcr_b = _ax.nekbone_cg_update_pallas(
            x2, p2, r2, w2, addb, addt, alpha.reshape(1, 1), cx, cy, cz,
            n=n, grid=grid, sz=sz, interpret=interpret, acc_dtype=acc_name)
        hist = hist.at[kk + 1].set(jnp.sqrt(jnp.abs(jnp.sum(rcr_b))))
        z2, rtz_new = cheb(r2)
        return x2, r2, z2, p2, rtz_new, rtz, hist, kk + 1

    state = (jnp.zeros(b2.shape, x_dtype), b2, z0, jnp.zeros_like(b2),
             rtz0, jnp.ones((), acc), hist0, jnp.asarray(0))
    x2, r2, z2, p2, rtz, rtz_prev, hist, kk = jax.lax.while_loop(cond, body,
                                                                 state)
    return CGResult(x=_ax.from_lanes(x2, n).reshape(b.shape), iters=kk,
                    rnorm=hist[kk], rnorm_history=hist)


@functools.partial(jax.jit, static_argnames=("n", "grid", "max_iter", "sz",
                                             "ns", "szs", "cheb_szs", "k",
                                             "coarse_iters", "interpret",
                                             "acc_name", "x_name",
                                             "grid_order"))
def _pcg_pmg(b, D, Dt, g3, mx, my, mz, cx, cy, cz, levels, tol2, *, n: int,
             grid: tuple[int, int, int], max_iter: int, sz: int,
             ns: tuple[int, ...], szs: tuple[int, ...],
             cheb_szs: tuple[int, ...], k: int, coarse_iters: int,
             interpret: bool, acc_name: str, x_name: str,
             grid_order: str = "parallel") -> CGResult:
    """Fused p-multigrid PCG core (DESIGN.md §13).

    The :func:`_pcg_cheb` loop with the single polynomial apply replaced
    by a symmetric V-cycle over the degree ladder ``ns``: per smoothed
    level a Chebyshev(k) pre-smooth (the fused apply kernel on that
    level's rediscretized operator), an explicit residual via the v2 slab
    kernel (beta=0, planes stitched host-side), the c-weighted-adjoint
    restriction (c-multiply -> Pallas interp -> gather-scatter -> mask),
    recursion, tensor-product prolongation + masked correction, a second
    residual and a Chebyshev post-smooth — then ``rtz = r·c·z`` host-side
    in the accumulation dtype.  The recursion is a *static* Python unroll
    (the ladder is a static argname), so every level's kernels trace at
    their own ``n_l``/slab split (``szs``/``cheb_szs``, autotuned under
    per-level ``pmg:<level>`` keys).

    ``levels`` is the :func:`repro.core.pmg.pmg_level_pytree` operand
    pytree; level 0 runs on the caller's operator data (the same
    ``D``/``g3``/factor operands the unpreconditioned pipeline uses), and
    the base level is the shared fixed-CG solve
    (:func:`repro.core.pmg.coarse_solve_fixed` — shared with the XLA
    reference cycle so interpret-mode parity isolates the kernels).
    """
    E = b.shape[0]
    acc = jnp.dtype(acc_name)
    x_dtype = jnp.dtype(x_name)
    # every level runs in the kernels' (n_l, n_l^2, E) layout; operands
    # are converted once here (the base solve converts its small vectors)
    b2, g3, c2 = v2_operands(b, g3, cz, cy, cx, acc)
    rcr0 = jnp.sum(b2.astype(acc) * c2 * b2.astype(acc))
    coefs, transfers, midops, coarse = levels
    L = len(ns)
    # per-smoothed-level kernel operands, fine -> coarsest smoothed
    lops = [(D, Dt, g3, mx, my, mz, cx, cy, cz)]
    for lev, (Dl, g3l, mxl, myl, mzl, cxl, cyl, czl) in enumerate(midops,
                                                                   1):
        lops.append((Dl, Dl.T, _ax.metric_lanes(g3l, ns[lev]), mxl, myl,
                     mzl, cxl, cyl, czl))
    # loop-invariant per-level windows and full structural fields
    gexts, mzexts, mask2s, c2s = [], [], [], []
    for lev in range(L - 1):
        _, _, g3l, mxl, myl, mzl, cxl, cyl, czl = lops[lev]
        nl = ns[lev]
        gexts.append(_ax.sstep_extend_field(g3l, grid, cheb_szs[lev], k))
        mzexts.append(_ax.sstep_extend_zfactor(mzl, cheb_szs[lev], k))
        mask2s.append(_ax.to_lanes(
            box_outer(mzl, myl, mxl).reshape(E, nl ** 3), nl))
        c2s.append(_ax.to_lanes(
            box_outer(czl, cyl, cxl).reshape(E, nl ** 3), nl).astype(acc))
    Dc, gc, maskc, cc = coarse
    nc = ns[-1]
    mask2s.append(_ax.to_lanes(maskc.reshape(E, nc ** 3), nc))

    def smooth(r2l, lev):
        Dl, Dtl, _, mxl, myl, _, cxl, cyl, czl = lops[lev]
        rext = _ax.sstep_extend_field(r2l, grid, cheb_szs[lev], k)
        z2l, _ = _ax.nekbone_cheb_apply_pallas(
            rext, Dl, Dtl, gexts[lev], mxl, myl, mzexts[lev],
            cxl, cyl, czl, coefs[lev], n=ns[lev], grid=grid,
            sz=cheb_szs[lev], k=k, interpret=interpret, acc_dtype=acc_name,
            grid_order=grid_order)
        return z2l

    def apply_a(z2l, lev):
        nl, szl = ns[lev], szs[lev]
        Dl, Dtl, g3l, mxl, myl, mzl, *_ = lops[lev]
        _, w2, bot, top, _ = _ax.nekbone_ax_slab_pallas(
            jnp.zeros_like(z2l), z2l, Dl, Dtl, g3l, mxl, myl, mzl,
            jnp.zeros((1, 1), acc), n=nl, grid=grid, sz=szl,
            interpret=interpret, acc_dtype=acc_name, grid_order=grid_order)
        return _ax.stitch_planes(w2, bot, top, grid, szl)

    def restrict(res2, lev):
        ncl = ns[lev + 1]
        t2 = (res2.astype(acc) * c2s[lev]).astype(res2.dtype)
        rc2 = _ax.nekbone_interp_pallas(
            t2, transfers[lev], nin=ns[lev], nout=ncl, grid=grid,
            sz=szs[lev], interpret=interpret, acc_dtype=acc_name)
        rc2 = gs_mod.ds_sum_local(rc2, grid, lanes=True)
        return rc2 * mask2s[lev + 1].astype(rc2.dtype)

    def prolong(ec2, lev):
        return _ax.nekbone_interp_pallas(
            ec2, jnp.swapaxes(transfers[lev], 0, 1), nin=ns[lev + 1],
            nout=ns[lev], grid=grid, sz=szs[lev], interpret=interpret,
            acc_dtype=acc_name)

    def vcycle_level(r2l, lev):
        if lev == L - 1:
            e4 = _pmg.coarse_solve_fixed(
                _ax.from_lanes(r2l, nc).reshape(E, nc, nc, nc).astype(acc),
                Dc, gc, grid, maskc, cc, iters=coarse_iters)
            return _ax.to_lanes(e4.reshape(E, nc ** 3), nc).astype(b.dtype)
        z2l = smooth(r2l, lev)
        res = (r2l.astype(acc) - apply_a(z2l, lev).astype(acc)) \
            .astype(r2l.dtype)
        ec = vcycle_level(restrict(res, lev), lev + 1)
        z2l = (z2l.astype(acc) + prolong(ec, lev).astype(acc)
               * mask2s[lev].astype(acc)).astype(r2l.dtype)
        res = (r2l.astype(acc) - apply_a(z2l, lev).astype(acc)) \
            .astype(r2l.dtype)
        return (z2l.astype(acc) + smooth(res, lev).astype(acc)) \
            .astype(r2l.dtype)

    def vcycle(r2):
        z2 = vcycle_level(r2, 0)
        return z2, jnp.sum(r2.astype(acc) * c2 * z2.astype(acc))

    z0, rtz0 = vcycle(b2)
    hist0 = jnp.full((max_iter + 1,), jnp.nan, dtype=acc) \
        .at[0].set(jnp.sqrt(jnp.abs(rcr0)))
    tol2 = jnp.asarray(tol2, acc)

    def cond(state):
        _, _, _, _, rtz, _, _, kk = state
        return jnp.logical_and(kk < max_iter, jnp.abs(rtz) > tol2)

    def body(state):
        x2, r2, z2, p2, rtz, rtz_prev, hist, kk = state
        beta = rtz / rtz_prev            # rtz_prev = 1 at k=0: p0 = 0
        p2, w2, bot, top, pap_b = _ax.nekbone_ax_slab_pallas(
            p2, z2, D, Dt, g3, mx, my, mz, beta.reshape(1, 1),
            n=n, grid=grid, sz=sz, interpret=interpret, acc_dtype=acc_name,
            grid_order=grid_order)
        alpha = rtz / jnp.sum(pap_b)
        addb, addt = _ax.shift_planes(bot, top)
        x2, r2, rcr_b = _ax.nekbone_cg_update_pallas(
            x2, p2, r2, w2, addb, addt, alpha.reshape(1, 1), cx, cy, cz,
            n=n, grid=grid, sz=sz, interpret=interpret, acc_dtype=acc_name)
        hist = hist.at[kk + 1].set(jnp.sqrt(jnp.abs(jnp.sum(rcr_b))))
        z2, rtz_new = vcycle(r2)
        return x2, r2, z2, p2, rtz_new, rtz, hist, kk + 1

    state = (jnp.zeros(b2.shape, x_dtype), b2, z0, jnp.zeros_like(b2),
             rtz0, jnp.ones((), acc), hist0, jnp.asarray(0))
    x2, r2, z2, p2, rtz, rtz_prev, hist, kk = jax.lax.while_loop(cond, body,
                                                                 state)
    return CGResult(x=_ax.from_lanes(x2, n).reshape(b.shape), iters=kk,
                    rnorm=hist[kk], rnorm_history=hist)


# ---------------------------------------------------------------------------
# public drivers
# ---------------------------------------------------------------------------

def _prepare(b, D, g, grid, mask, c, sz, interpret, precision, precond,
             grid_order=None):
    """Shared operand preparation for the fused v2-family drivers."""
    from repro.kernels import ops as kernel_ops

    policy = resolve_policy(precision, b.dtype)
    b = jnp.asarray(b, policy.storage_dtype)
    E = b.shape[0]
    n = b.shape[-1]
    grid = tuple(grid)
    if interpret is None:
        interpret = kernel_ops.default_interpret()
    # only Jacobi changes the slab kernels' working set (the update
    # kernel holds the diagonal block); Chebyshev runs the unmodified
    # v2 kernels — its own apply kernel has the "cheb" kind — so it
    # shares the plain pick rather than re-measuring.
    jac = (isinstance(precond, JacobiPrecond)
           or (isinstance(precond, str) and precond == "jacobi"))
    sz, grid_order = _autotune.slab_config(
        "slab", grid, n, b.dtype, acc_dtype=policy.accum,
        precond="jacobi" if jac else None, sz=sz, grid_order=grid_order)
    _check_box_fields(grid, n, mask, c)
    (mx, my, mz), (cx, cy, cz) = kernel_ops.slab_axis_factors(grid, n,
                                                              b.dtype)
    D_op = jnp.asarray(D, policy.op_storage_dtype)
    g3 = kernel_ops.diag_metric(jnp.asarray(g, policy.op_storage_dtype),
                                E, n)
    return (policy, b, n, grid, sz, grid_order, interpret,
            (mx, my, mz), (cx, cy, cz), D_op, g3)


def _resolve_precond(precond, *, D, g, grid, mask, c):
    if precond is None or isinstance(precond, (JacobiPrecond,
                                               ChebyshevPrecond,
                                               _pmg.PMGPrecond)):
        return precond
    return make_preconditioner(str(precond), D=D, g=g, grid=grid,
                               mask=mask, c=c)


def _dispatch(b, precond, tol2, max_iter, *, policy, n, grid, sz, interpret,
              m_factors, c_factors, D_op, g3,
              grid_order: str, cheb_sz: int | None = None) -> CGResult:
    mx, my, mz = m_factors
    cx, cy, cz = c_factors
    common = dict(n=n, grid=grid, max_iter=max_iter, sz=sz,
                  interpret=interpret, acc_name=policy.accum,
                  x_name=policy.x_storage_dtype.name,
                  grid_order=grid_order)
    if precond is None:
        return _cg_v2_tol(b, D_op, D_op.T, g3, mx, my, mz, cx, cy, cz,
                          tol2, **common)
    if isinstance(precond, JacobiPrecond):
        invd = jnp.asarray(precond.invdiag, policy.op_storage_dtype) \
            .reshape(b.shape[0], n ** 3)
        return _pcg_jacobi(b, invd, D_op, D_op.T, g3, mx, my, mz,
                           cx, cy, cz, tol2, **common)
    if isinstance(precond, ChebyshevPrecond):
        sz_c, _ = _autotune.slab_config(
            "cheb", grid, n, b.dtype, acc_dtype=policy.accum,
            depth=precond.k, sz=cheb_sz, grid_order="parallel")
        coef = jnp.asarray(precond.scalars(), policy.accum_dtype)
        return _pcg_cheb(b, D_op, D_op.T, g3, mx, my, mz, cx, cy, cz,
                         coef, tol2, sz_c=sz_c, k=precond.k, **common)
    if isinstance(precond, _pmg.PMGPrecond):
        ns_t = precond.ns
        # per-level slab splits: the Az/interp kernels at each degree get
        # their own ``pmg:<level>`` autotune key; the level-0 smoother may
        # reuse the caller's cheb_sz pin (the paper-case workloads pin it).
        # The per-level host work (autotune picks) is the V-cycle's host
        # boundary — the jitted driver unrolls the ladder statically, so
        # these "pmg.vcycle.level" spans are where the per-level structure
        # is visible to a trace (DESIGN.md §14.2).
        szs = []
        cheb_szs = []
        for lev in range(len(ns_t) - 1):
            with _trace.span("pmg.vcycle.level", level=lev, n=ns_t[lev],
                             k=precond.k):
                szs.append(_autotune.slab_config(
                    "slab", grid, ns_t[lev], b.dtype,
                    acc_dtype=policy.accum, precond=f"pmg:{lev}",
                    grid_order="parallel")[0])
                cheb_szs.append(_autotune.slab_config(
                    "cheb", grid, ns_t[lev], b.dtype,
                    acc_dtype=policy.accum, depth=precond.k,
                    sz=cheb_sz if lev == 0 else None,
                    grid_order="parallel")[0])
        szs, cheb_szs = tuple(szs), tuple(cheb_szs)
        levels = _pmg.pmg_level_pytree(precond, grid,
                                       policy.op_storage_dtype.name,
                                       policy.accum)
        with _trace.span("pmg.dispatch", levels=len(ns_t),
                         coarse_n=ns_t[-1]):
            return _pcg_pmg(b, D_op, D_op.T, g3, mx, my, mz, cx, cy, cz,
                            levels, tol2, ns=ns_t, szs=szs,
                            cheb_szs=cheb_szs, k=precond.k,
                            coarse_iters=precond.coarse_iters, **common)
    raise TypeError(f"unsupported preconditioner {precond!r}")


def pcg_fused_v2_fixed_iters(b: jnp.ndarray, *, D: jnp.ndarray,
                             g: jnp.ndarray, grid: tuple[int, int, int],
                             niter: int, precond,
                             mask: jnp.ndarray | None = None,
                             c: jnp.ndarray | None = None,
                             sz: int | None = None,
                             cheb_sz: int | None = None,
                             grid_order: str | None = None,
                             interpret: bool | None = None,
                             precision=None) -> CGResult:
    """Fixed-iteration *preconditioned* CG through the fused v2 pipeline.

    The PCG sibling of :func:`repro.core.cg_fused.cg_fused_v2_fixed_iters`
    (same arguments and preconditions), with ``precond`` a
    :class:`JacobiPrecond`, a :class:`ChebyshevPrecond`, or a registry
    name (``"jacobi"`` / ``"cheb[<k>]"`` — built via
    :func:`make_preconditioner`, which costs a one-time diagonal / Lanczos
    setup).  ``precond=None`` degenerates to the unpreconditioned v2
    driver.

    Matches ``cg_fixed_iters(A, b, precond=M, dot=weighted)`` to
    round-off of the policy's storage dtype; the residual-norm history
    records ``sqrt(r·c·r)`` exactly like unpreconditioned CG, so
    preconditioned and plain trajectories are directly comparable.
    ``sz`` pins the v2 kernels' slab split and ``cheb_sz`` the Chebyshev
    apply kernel's (defaults: autotuned — deeper polynomials want larger
    ``cheb_sz``, the halo is ``8k/sz`` streams, cost.cheb_halo_streams).
    """
    with _trace.span("driver.prepare"):
        (policy, b, n, grid, sz, grid_order, interpret, m_factors,
         c_factors, D_op, g3) = _prepare(b, D, g, grid, mask, c, sz,
                                         interpret, precision, precond,
                                         grid_order)
        # specs built by name use the caller's (full-precision) operator
        # data; the drivers cast the resulting fields to the policy's
        # op-storage.
        precond = _resolve_precond(precond, D=D, g=g, grid=grid, mask=mask,
                                   c=c)
    # tol2 = -1 sentinel: |rtz| > -1 always holds, so exactly ``niter``
    # iterations run — the tol-driven path's trajectory continued.
    return SolveResult.from_cg(
        _dispatch(b, precond, -1.0, niter, policy=policy, n=n, grid=grid,
                  sz=sz, interpret=interpret, m_factors=m_factors,
                  c_factors=c_factors, D_op=D_op, g3=g3, cheb_sz=cheb_sz,
                  grid_order=grid_order),
        pipeline="fused_v2", precond=getattr(precond, "name", None))


def cg_fused_tol(b: jnp.ndarray, *, D: jnp.ndarray, g: jnp.ndarray,
                 grid: tuple[int, int, int], tol: float = 1e-8,
                 max_iter: int = 100, precond=None,
                 mask: jnp.ndarray | None = None,
                 c: jnp.ndarray | None = None, sz: int | None = None,
                 cheb_sz: int | None = None,
                 grid_order: str | None = None,
                 interpret: bool | None = None, precision=None) -> CGResult:
    """Tolerance-driven fused-v2 (P)CG: solve to ``tol``, not 100 iters.

    The ``lax.while_loop`` sibling of the fixed-iteration drivers, with
    :func:`repro.core.cg.cg`'s stopping rule: iterate while
    ``k < max_iter`` and ``|rtz| > tol**2`` (``rtz = r·c·z``; ``= r·c·r``
    unpreconditioned), checking *before* each iteration.  The bodies are
    the fixed-iteration bodies, so the returned ``rnorm_history`` is a
    prefix of the fixed-iteration trajectory (NaN-padded to
    ``max_iter + 1`` like :func:`repro.core.cg.cg`) and ``iters`` is the
    count actually run.

    Args are :func:`pcg_fused_v2_fixed_iters`'s with ``tol``/``max_iter``
    replacing ``niter``; ``precond=None`` runs the plain v2 pipeline.
    """
    with _trace.span("driver.prepare"):
        (policy, b, n, grid, sz, grid_order, interpret, m_factors,
         c_factors, D_op, g3) = _prepare(b, D, g, grid, mask, c, sz,
                                         interpret, precision, precond,
                                         grid_order)
        precond = _resolve_precond(precond, D=D, g=g, grid=grid, mask=mask,
                                   c=c)
    return SolveResult.from_cg(
        _dispatch(b, precond, float(tol) ** 2, max_iter, policy=policy,
                  n=n, grid=grid, sz=sz, interpret=interpret,
                  m_factors=m_factors, c_factors=c_factors, D_op=D_op,
                  g3=g3, cheb_sz=cheb_sz, grid_order=grid_order),
        pipeline="fused_v2", precond=getattr(precond, "name", None))
