"""Communication-avoiding (s-step) CG on the v3 matrix-powers pipeline.

The v2 pipeline (core/cg_fused.py, DESIGN.md §3.4) fixed the per-iteration
stream count at 13; what every iteration still re-reads is the *operator
data* — the 3 metric diagonals, D, the mask factors — plus two scalar
(alpha/beta) host round-trips per iteration.  s-step CG amortizes both by
restructuring s iterations into one **cycle** (DESIGN.md §8):

1. **matrix-powers kernel** (`kernels/nekbone_ax.nekbone_ax_powers_kernel`)
   — evaluates the scaled Krylov basis ``V = [p, A'p, .., A'^s p, r, A'r,
   .., A'^{s-1} r]`` (``A' = A/theta``) in a single slab residency: metric,
   D, and mask factors are loaded once per s operator applications, and the
   ``(2s+1)^2`` Gram block ``G = V^T C V`` is reduced in-kernel.
2. **host recurrence** (this module, :func:`sstep_recurrence`) — the s-step
   coefficient updates run on the ``(2s+1)``-vector *coordinates*: every
   alpha/beta of the cycle is a pair of O(s^2) quadratic forms in ``G``,
   solved in float64 regardless of the device or the ``jax_enable_x64``
   flag (numpy on host — "Gram/recurrence always wide", the §7 policy
   extended).  One device->host sync per cycle replaces the 2-per-iteration
   scalar round-trips of v1/v2.
3. **multi-axpy update kernel** (`nekbone_sstep_update_kernel`) — applies
   the whole s-step of x/r/p updates in one pass over the basis and emits
   the post-cycle ``r·c·r`` partial over the *stored* residual.

Stream budget per cycle: 5 reads + (2s-1) basis writes (powers kernel),
(2s+2) reads + 3 writes (update kernel) = ``4s + 9`` streams per s
iterations (`cost.sstep_streams`) — exactly the v2 budget at s=1, 6.25
streams/iteration at the default s=4.  The matrix-powers halo (s ghost
slabs per block side) is the side channel: ``10/sz`` stream-equivalents
per iteration (`cost.sstep_halo_streams`), <= 9 effective streams at
(s, sz) = (4, 4).

Stability: the monomial basis conditions the Gram block like
``kappa(A)^{2s}``; the theta scaling (a one-time power-iteration estimate
of ||A||) keeps basis norms O(1) but not the angles, so parity with
``cg_fixed_iters`` degrades as s grows — s <= 4 holds fp64 round-off
parity on the paper-grid cases (tests/test_cg_sstep.py), larger s needs a
Newton/Chebyshev basis (out of scope, DESIGN.md §8 documents the limit).

Preconditions are the v2 pipeline's: assembled+masked ``b``, the
structured axis-aligned box (diagonal metric, factorizable mask),
fixed-iteration unpreconditioned solves.  The ``precision`` policy
(DESIGN.md §7) composes unchanged: basis vectors stream in the storage
dtype (rounded through storage *inside* the kernel chain, so Gram and
stored basis describe the same vectors), contractions and Gram partials
accumulate wide, and :func:`repro.core.cg_fused.cg_ir_fixed_iters`
accepts ``variant="sstep"`` to run s-step sweeps inside iterative
refinement.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.gs as gs_mod
from repro.core.cg import CGResult, SolveResult
from repro.core.geom import box_axis_factors, box_outer
from repro.core.precision import resolve_policy
from repro.kernels import autotune as _autotune
from repro.kernels import nekbone_ax as _ax

__all__ = ["cg_sstep_fixed_iters", "sstep_recurrence", "cycle_coefficients",
           "estimate_theta", "sstep_cycle_traceables"]


def sstep_recurrence(G: np.ndarray, s: int, m: int, theta: float):
    """Run m (<= s) CG iterations on s-step basis coordinates, in float64.

    With ``V = [p, A'p, .., A'^s p, r, A'r, .., A'^{s-1} r]`` and
    ``A V = theta * V T`` (``T`` the block shift), the CG two-term
    recurrence closes on coefficient vectors:

        rtz_j   = b_j' G b_j
        alpha_j = rtz_j / (a_j' G (theta T a_j))
        e_{j+1} = e_j + alpha_j a_j            (x - x0 coordinates)
        b_{j+1} = b_j - alpha_j theta T a_j    (r coordinates)
        beta_j  = rtz_{j+1} / rtz_j
        a_{j+1} = b_{j+1} + beta_j a_j         (p coordinates)

    The degree argument keeps T total: p_j involves powers <= j of p and
    <= j-1 of r, so ``T a_j`` for j <= s-1 never needs the truncated
    columns.  Everything is float64 numpy — the Gram/recurrence stays wide
    whatever the device precision.

    Args:
      G: (2s+1, 2s+1) assembled Gram matrix ``V^T C V``.
      s: basis powers; m: iterations to advance (final cycle may be short).
      theta: the basis scale (``A' = A/theta``).

    Returns ``(e, b, a, rtz_hist)`` — the three coefficient vectors after
    m steps and the list of the m start-of-iteration ``rtz`` values.
    """
    K = 2 * s + 1
    G = np.asarray(G, np.float64).reshape(K, K)
    G = 0.5 * (G + G.T)                  # kernel partials are symmetric
    T = np.zeros((K, K))
    for j in range(s):
        T[j + 1, j] = theta              # A (A'^j p) = theta A'^{j+1} p
    for j in range(s - 1):
        T[s + 2 + j, s + 1 + j] = theta
    a = np.zeros(K)
    a[0] = 1.0                           # p
    b = np.zeros(K)
    b[s + 1] = 1.0                       # r
    e = np.zeros(K)
    rtz_hist = []
    rtz = float(b @ G @ b)
    for _ in range(m):
        rtz_hist.append(rtz)
        Ta = T @ a
        alpha = rtz / float(a @ G @ Ta)
        e = e + alpha * a
        b = b - alpha * Ta
        rtz_new = float(b @ G @ b)
        beta = rtz_new / rtz
        a = b + beta * a
        rtz = rtz_new
    return e, b, a, rtz_hist


def cycle_coefficients(G: np.ndarray, s: int, m: int, theta: float,
                       tol2: float | None = None):
    """One cycle's recurrence + in-cycle tolerance resolution, shared by the
    single-device driver below and the sharded one
    (:func:`repro.distributed.sstep.cg_sstep_sharded_fixed_iters`).

    Runs :func:`sstep_recurrence` for ``m`` steps; with ``tol2`` set,
    applies :func:`repro.core.cg.cg`'s stopping rule at *iteration*
    granularity — stop before the first iteration whose start-of-iteration
    ``rtz`` is ``<= tol2`` — by re-running the O(s^2) f64 recurrence for
    the shorter count, so the update kernel applies exactly the iterations
    taken.

    Returns ``(coef, rtzs, m)``: the stacked f64 ``(3, 2s+1)`` coefficient
    block (x/r/p rows — the update kernel's layout), the ``m``
    start-of-iteration rtz values actually run, and the resolved step
    count (``m == 0`` means the tolerance was already met at cycle start
    and nothing should be applied).
    """
    e_c, b_c, a_c, rtzs = sstep_recurrence(G, s, m, theta)
    if tol2 is not None:
        stop = next((j for j, v in enumerate(rtzs) if abs(v) <= tol2), None)
        if stop is not None:
            if stop == 0:
                return None, [], 0
            e_c, b_c, a_c, rtzs = sstep_recurrence(G, s, stop, theta)
            m = stop
    return np.stack([e_c, b_c, a_c]), rtzs, m


@functools.partial(jax.jit, static_argnames=("grid", "iters"))
def _theta_power_iter(D, g, mask, *, grid: tuple[int, int, int],
                      iters: int):
    """Whole power iteration in one jitted program (one host sync).

    Module-level so the jit cache is shared across solves — a per-call
    closure would re-trace every time.  Degenerate inputs (zero/non-finite
    operator norms) carry the previous theta forward; the caller maps a
    non-finite final value to 1.0.
    """
    from repro.core.ax import ax_local_fused

    tiny = jnp.asarray(np.finfo(np.float64).tiny, mask.dtype)
    v0 = jnp.linspace(1.0, 2.0, mask.size).reshape(mask.shape) \
        .astype(mask.dtype) * mask

    def body(_, carry):
        v, theta = carry
        w = gs_mod.ds_sum_local(ax_local_fused(v, D, g), grid) * mask
        nrm = jnp.max(jnp.abs(w))
        ok = jnp.isfinite(nrm) & (nrm > 0)
        theta = jnp.where(ok, nrm / jnp.maximum(jnp.max(jnp.abs(v)), tiny),
                          theta)
        v = jnp.where(ok, w / jnp.where(ok, nrm, 1.0), v)
        return v, theta

    _, theta = jax.lax.fori_loop(
        0, iters, body, (v0, jnp.ones((), mask.dtype)))
    return theta


def estimate_theta(D: jnp.ndarray, g: jnp.ndarray,
                   grid: tuple[int, int, int], mask: jnp.ndarray,
                   iters: int = 8) -> float:
    """Power-iteration estimate of ||A|| for the basis scale.

    Any fixed positive theta leaves the recurrence *exact* (it is a
    diagonal rescale of the basis, accounted for in T); a ||A||-sized one
    keeps the monomial basis norms O(1) so the f64 Gram stays conditioned.
    A handful of deterministic power iterations on the assembled masked
    operator suffice — a one-time setup cost per solve (pass ``theta=`` to
    :func:`cg_sstep_fixed_iters` to amortize it across solves).
    """
    theta = float(_theta_power_iter(jnp.asarray(D), jnp.asarray(g),
                                    jnp.asarray(mask), grid=tuple(grid),
                                    iters=iters))
    if not np.isfinite(theta) or theta <= 0.0:
        return 1.0
    return theta


@functools.partial(jax.jit, static_argnames=("n", "grid", "sz", "s",
                                             "interpret", "acc_name",
                                             "grid_order"))
def _powers_call(p2, r2, D, Dt, gext, mx, my, mzext, cx, cy, cz, inv_theta,
                 *, n: int, grid: tuple[int, int, int], sz: int, s: int,
                 interpret: bool, acc_name: str,
                 grid_order: str = "parallel"):
    """Halo-window gather + the matrix-powers pallas_call, one cycle, on
    kernel-layout vectors ``(n, n^2, E)``."""
    pext = _ax.sstep_extend_field(p2, grid, sz, s)
    rext = _ax.sstep_extend_field(r2, grid, sz, s)
    return _ax.nekbone_ax_powers_pallas(
        pext, rext, D, Dt, gext, mx, my, mzext, cx, cy, cz, inv_theta,
        n=n, grid=grid, sz=sz, s=s, interpret=interpret, acc_dtype=acc_name,
        grid_order=grid_order)


def sstep_cycle_traceables(D: jnp.ndarray, g: jnp.ndarray,
                           grid: tuple[int, int, int], *, s: int = 4,
                           sz: int = 4, precision=None):
    """One s-step cycle's two launches as traceable closures + arg specs.

    Replicates exactly the operand prep of :func:`cg_sstep_fixed_iters`
    (operator dtypes, halo'd metric window, extended z factors) and
    returns ``((powers_fn, powers_args), (update_fn, update_args))``
    where the args are :class:`jax.ShapeDtypeStruct` specs for the
    per-cycle vector operands.  ``jax.make_jaxpr(fn)(*args)`` then yields
    the same program the driver launches once per cycle — the
    measurement surface :mod:`repro.obs.drift` charges against the
    ``cost.py`` books without running a solve.
    """
    from repro.kernels import ops as kernel_ops

    grid = tuple(grid)
    n = int(jnp.asarray(D).shape[0])
    E = int(np.prod(grid))
    policy = resolve_policy(precision, jnp.asarray(D).dtype)
    (mx, my, mz), (cx, cy, cz) = kernel_ops.slab_axis_factors(
        grid, n, policy.storage_dtype)
    D_op = jnp.asarray(D, policy.op_storage_dtype)
    g3 = _ax.metric_lanes(kernel_ops.diag_metric(
        jnp.asarray(g, policy.op_storage_dtype), E, n), n)
    gext = _ax.sstep_extend_field(g3, grid, sz, s)
    mzext = _ax.sstep_extend_zfactor(mz, sz, s)
    inv_theta = jnp.full((1, 1), 1.0, policy.accum_dtype)

    def powers_fn(p2, r2):
        return _powers_call(p2, r2, D_op, D_op.T, gext, mx, my, mzext,
                            cx, cy, cz, inv_theta, n=n, grid=grid, sz=sz,
                            s=s, interpret=True, acc_name=policy.accum)

    def update_fn(x2, p2, r2, basis, coef):
        return _ax.nekbone_sstep_update_pallas(
            x2, p2, r2, basis, coef, cx, cy, cz, n=n, grid=grid, sz=sz,
            s=s, interpret=True, acc_dtype=policy.accum)

    lanes = (n, n * n, E)
    field = jax.ShapeDtypeStruct(lanes, policy.storage_dtype)
    xf = jax.ShapeDtypeStruct(lanes, policy.x_storage_dtype)
    basis = jax.ShapeDtypeStruct((2 * s - 1,) + lanes, policy.storage_dtype)
    coef = jax.ShapeDtypeStruct((3, 2 * s + 1), policy.accum_dtype)
    return ((powers_fn, (field, field)),
            (update_fn, (xf, field, field, basis, coef)))


def cg_sstep_fixed_iters(b: jnp.ndarray, *, D: jnp.ndarray, g: jnp.ndarray,
                         grid: tuple[int, int, int], niter: int, s: int = 4,
                         mask: jnp.ndarray | None = None,
                         c: jnp.ndarray | None = None,
                         sz: int | None = None, theta: float | None = None,
                         grid_order: str | None = None,
                         tol: float | None = None,
                         interpret: bool | None = None,
                         precision=None) -> CGResult:
    """Fixed-iteration s-step CG through the v3 matrix-powers pipeline.

    Args:
      b:     (E, n, n, n) assembled, masked right-hand side; elements
             z-major over ``grid``.
      D:     (n, n) derivative matrix.
      g:     (E, 6, n, n, n) axis-aligned metric, or pre-packed diagonal.
      grid:  element grid (EX, EY, EZ).
      niter: total CG iterations (any value — the final cycle runs the
             remainder ``niter % s`` recurrence steps on a full basis).
             With ``tol`` set this is the *ceiling* (``max_iter``).
      s:     iterations per cycle (s >= 1; s=1 degenerates to the v2
             stream budget, s=4 is the tuned default — DESIGN.md §8).
      mask/c: optional structural fields, validated like the v2 path.
      sz, grid_order: slab split and the powers kernel's grid iteration
             order (default: `kernels/autotune.slab_config`, kind
             ``"sstep"``, keyed by s).
      theta: basis scale override (default: power-iteration ||A|| estimate).
      tol:   optional tolerance for early exit (DESIGN.md §9.4): stop, as
             :func:`repro.core.cg.cg` does, *before* the first iteration
             whose start-of-iteration ``rtz = r·c·r`` is ``<= tol**2``.
             The cycle's rtz values are the f64 Gram quadratic forms, so
             the stopping point is resolved to *iteration* granularity:
             the recurrence is re-run for the shorter step count and the
             update kernel applies exactly the iterations taken.  The
             returned ``iters`` is the count actually run.
      interpret: force Pallas interpret mode (default: off-TPU detection).
      precision: policy name / policy / ``None`` (DESIGN.md §7) — basis
             and vectors stream in the storage dtype, Gram partials in the
             accum dtype, the recurrence always in host float64.

    Returns a :class:`repro.core.cg.CGResult` whose ``rnorm_history``
    matches ``cg_fixed_iters`` to round-off for small s (the in-cycle
    entries are the f64 Gram quadratic forms ``sqrt(b_j' G b_j)``; the
    final entry is the update kernel's stored-residual reduction).  With
    ``tol``, the history holds the ``iters + 1`` entries actually
    produced — a prefix of the fixed-iteration trajectory.
    """
    from repro.core.cg_fused import _check_box_fields
    from repro.kernels import ops as kernel_ops

    if s < 1:
        raise ValueError(f"s-step CG needs s >= 1, got {s}")
    policy = resolve_policy(precision, b.dtype)
    b = jnp.asarray(b, policy.storage_dtype)
    E = b.shape[0]
    n = b.shape[-1]
    grid = tuple(grid)
    ex, ey, ez = grid
    if interpret is None:
        interpret = kernel_ops.default_interpret()
    sz, grid_order = _autotune.slab_config(
        "sstep", grid, n, b.dtype, acc_dtype=policy.accum, depth=s, sz=sz,
        grid_order=grid_order)

    _check_box_fields(grid, n, mask, c)
    (mx, my, mz), (cx, cy, cz) = kernel_ops.slab_axis_factors(grid, n,
                                                              b.dtype)
    n3 = n ** 3
    acc = policy.accum_dtype
    x_dtype = policy.x_storage_dtype
    # operator data in the policy's op-storage dtype (refined policies keep
    # it wide, DESIGN.md §7); the halo'd metric windows are built once per
    # solve — the per-cycle kernel reads are what the cost model charges.
    # The cycles run in the kernels' (n, n^2, E) layout: b and the metric
    # are converted once here, x once at the end.
    D_op = jnp.asarray(D, policy.op_storage_dtype)
    g3 = _ax.metric_lanes(kernel_ops.diag_metric(
        jnp.asarray(g, policy.op_storage_dtype), E, n), n)
    gext = _ax.sstep_extend_field(g3, grid, sz, s)
    mzext = _ax.sstep_extend_zfactor(mz, sz, s)
    if theta is None:
        if mask is None:
            mask = box_outer(
                *reversed(box_axis_factors(grid, n)[0])).reshape(b.shape)
        theta = estimate_theta(jnp.asarray(D, b.dtype),
                               jnp.asarray(g, b.dtype), grid,
                               jnp.asarray(mask, b.dtype))
    inv_theta = jnp.full((1, 1), 1.0 / theta, acc)

    tol2 = None if tol is None else float(tol) ** 2
    r2 = p2 = _ax.to_lanes(b.reshape(E, n3), n)
    x2 = jnp.zeros(r2.shape, x_dtype)
    hist: list[float] = []
    rcr_last = None
    it = 0
    from repro.obs import trace as _trace

    while it < niter:
        # per-cycle tolerance check on the previous update kernel's stored-
        # residual reduction — the same quantity the next cycle's Gram
        # would report as its start-of-iteration rtz, one powers launch
        # earlier (DESIGN.md §9.4).
        if tol2 is not None and rcr_last is not None \
                and abs(float(rcr_last)) <= tol2:
            break
        m = min(s, niter - it)
        with _trace.span("sstep.cycle", it=it, s=s):
            with _trace.span("sstep.powers"):
                basis, gram_b = _powers_call(
                    p2, r2, D_op, D_op.T, gext, mx, my, mzext, cx, cy,
                    cz, inv_theta, n=n, grid=grid, sz=sz, s=s,
                    interpret=interpret, acc_name=policy.accum,
                    grid_order=grid_order)
            # the policy's gram dtype is always float64
            # (PrecisionPolicy.gram); cycle_coefficients resolves the
            # in-cycle stop (run only the iterations whose start rtz is
            # still above tol^2 — exactly cg()'s while_loop semantics).
            G = np.asarray(jnp.sum(gram_b, axis=0), np.dtype(policy.gram))
            coef_np, rtzs, m = cycle_coefficients(G, s, m, theta, tol2)
            if m == 0:
                break
            hist.extend(np.sqrt(np.abs(v)) for v in rtzs)
            coef = jnp.asarray(coef_np, acc)
            with _trace.span("sstep.update"):
                x2, r2, p2, rcr_b = _ax.nekbone_sstep_update_pallas(
                    x2, p2, r2, basis, coef, cx, cy, cz, n=n, grid=grid,
                    sz=sz, s=s, interpret=interpret,
                    acc_dtype=policy.accum)
            rcr_last = jnp.sum(rcr_b)
        it += m
        if tol2 is not None and m < s:
            break
    if rcr_last is None:                  # niter == 0 (or tol met at start)
        c2 = _ax.to_lanes(box_outer(cz, cy, cx).reshape(E, n3),
                          n).astype(acc)
        rcr_last = jnp.sum(r2.astype(acc) * c2 * r2.astype(acc))
    hist.append(float(np.sqrt(abs(float(rcr_last)))))
    hist_arr = jnp.asarray(np.asarray(hist, np.float64), acc)
    return SolveResult.from_cg(
        CGResult(x=_ax.from_lanes(x2, n).reshape(b.shape),
                 iters=jnp.asarray(it),
                 rnorm=hist_arr[-1], rnorm_history=hist_arr),
        pipeline="sstep_v3")
