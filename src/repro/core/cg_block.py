"""Multi-RHS (block) CG through the batched v2 slab pipeline (DESIGN.md §12).

The serving-side amortization axis: one operator, b right-hand sides.  Each
iteration runs the two batched slab kernels
(:func:`repro.kernels.nekbone_ax.nekbone_ax_slab_block_pallas` /
``nekbone_cg_update_block_pallas``), which load the operator residents —
D, D^T, the 3 metric diagonals, the per-axis mask/weight factors — once per
slab residency and reuse them across the batch, so the shared operator
streams are divided by b while the per-RHS vector streams stay put
(:func:`repro.core.cost.multi_rhs_streams`).

The CG scalar recurrences stay *independent per RHS*: rtz/alpha/beta travel
as length-b vectors (one lane per RHS), the pap/rcr kernel partials come
back as (nblk, b) and are reduced per lane.  The per-RHS arithmetic is the
single-RHS v2 arithmetic operation for operation — at ``b = 1`` the fixed-
iteration driver is fp64-bitwise identical to
:func:`repro.core.cg_fused.cg_fused_v2_fixed_iters` (pinned by
tests/test_cg_block.py).

Both drivers accept ``B`` of shape (b, E, n, n, n) — or (E, n, n, n),
treated as ``b = 1`` — and return a :class:`repro.core.cg.SolveResult`
with per-RHS ``history`` (b, niter+1), ``rnorm``, and ``achieved_rtol``.

**Coupled mode** (:func:`cg_block_coupled_tol`,
:func:`cg_block_coupled_fixed_iters`): the b lanes are the components of
*one* vector field under one scalar operator (CEED BP6), solved as one
Krylov system.  The same block kernels run — with the batched Jacobi
back-half (``nekbone_pcg_update_block_pallas``) when preconditioned — but
the pap and rtz partials are summed over the lanes, so one alpha and one
beta go to every lane, and the stopping rule and residual history are the
whole field's.  At ``b = 1`` the coupled Jacobi trajectory is
:func:`repro.core.precond.cg_fused_tol`'s bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.cg import CGResult, SolveResult
from repro.core.cg_fused import _check_box_fields, initial_rtz
from repro.core.geom import box_outer
from repro.core.precision import resolve_policy
from repro.kernels import autotune as _autotune
from repro.kernels import nekbone_ax as _ax

__all__ = ["cg_block_fixed_iters", "cg_block_tol", "cg_block_coupled_tol",
           "cg_block_coupled_fixed_iters"]


def _block_iter(x3, r3, p3, rtz, beta, *, D, Dt, g3, mx, my, mz, cx, cy, cz,
                n: int, grid: tuple[int, int, int], sz: int,
                interpret: bool, acc_name: str,
                grid_order: str = "parallel"):
    """One full batched v2 CG iteration (both block kernels + stitch).

    The multi-RHS sibling of :func:`repro.core.cg_fused._v2_iter`:
    identical structure with a leading RHS axis on the kernel-layout
    fields ``(b, n, n^2, E)`` and planes, and per-lane scalar recurrences
    (``rtz``/``beta``: (b,)).  Returns ``(x3, r3, p3, rtz_new, beta_new)``.
    """
    nrhs = p3.shape[0]
    p3, w3, bot, top, pap_b = _ax.nekbone_ax_slab_block_pallas(
        p3, r3, D, Dt, g3, mx, my, mz, beta.reshape(1, nrhs),
        n=n, grid=grid, sz=sz, interpret=interpret, acc_dtype=acc_name,
        grid_order=grid_order)
    pap = jnp.sum(pap_b, axis=0)
    alpha = rtz / pap
    # cross-block stitch operands, shifted along the block axis per RHS.
    addb, addt = _ax.shift_planes(bot, top)
    x3, r3, rcr_b = _ax.nekbone_cg_update_block_pallas(
        x3, p3, r3, w3, addb, addt, alpha.reshape(1, nrhs), cx, cy, cz,
        n=n, grid=grid, sz=sz, interpret=interpret, acc_dtype=acc_name)
    rtz_new = jnp.sum(rcr_b, axis=0)
    beta = rtz_new / rtz
    return x3, r3, p3, rtz_new, beta


def _block_init(B, g3, cx, cy, cz, *, n, acc, x_name):
    """Shared set-up: the kernel-layout RHS batch ``(b, n, n^2, E)`` and
    metric (converted once per solve), and per-RHS rtz0 (one
    single-RHS-shaped reduction per lane, so the b=1 arithmetic is exactly
    ``_cg_fused_v2``'s).  Returns ``(state, g3)``."""
    nrhs, E = B.shape[0], B.shape[1]
    B2 = _ax.to_lanes(B.reshape(nrhs, E, n ** 3), n)
    rtz0 = jnp.stack([initial_rtz(B[j].reshape(E, n, n, n), cz, cy, cx, acc)
                      for j in range(nrhs)])
    state = (jnp.zeros(B2.shape, jnp.dtype(x_name)), B2,
             jnp.zeros_like(B2), rtz0, jnp.zeros((nrhs,), acc))
    return state, _ax.metric_lanes(g3, n)


@functools.partial(jax.jit, static_argnames=("n", "grid", "niter", "sz",
                                             "interpret", "acc_name",
                                             "x_name", "grid_order"))
def _cg_block(B, D, Dt, g3, mx, my, mz, cx, cy, cz, *, n: int,
              grid: tuple[int, int, int], niter: int, sz: int,
              interpret: bool, acc_name: str, x_name: str,
              grid_order: str = "parallel") -> CGResult:
    nrhs = B.shape[0]
    acc = jnp.dtype(acc_name)
    (x3, r3, p3, rtz0, beta0), g3 = _block_init(
        B, g3, cx, cy, cz, n=n, acc=acc, x_name=x_name)

    def body(k, state):
        x3, r3, p3, rtz, beta, hist = state
        hist = hist.at[:, k].set(jnp.sqrt(jnp.abs(rtz)))
        x3, r3, p3, rtz_new, beta = _block_iter(
            x3, r3, p3, rtz, beta, D=D, Dt=Dt, g3=g3, mx=mx, my=my, mz=mz,
            cx=cx, cy=cy, cz=cz, n=n, grid=grid, sz=sz, interpret=interpret,
            acc_name=acc_name, grid_order=grid_order)
        return x3, r3, p3, rtz_new, beta, hist

    hist0 = jnp.full((nrhs, niter + 1), jnp.nan, dtype=acc)
    state = (x3, r3, p3, rtz0, beta0, hist0)
    x3, r3, p3, rtz_last, beta, hist = jax.lax.fori_loop(0, niter, body,
                                                         state)
    hist = hist.at[:, niter].set(jnp.sqrt(jnp.abs(rtz_last)))
    return CGResult(x=_ax.from_lanes(x3, n), iters=jnp.asarray(niter),
                    rnorm=hist[:, niter], rnorm_history=hist)


@functools.partial(jax.jit, static_argnames=("n", "grid", "max_iter", "sz",
                                             "interpret", "acc_name",
                                             "x_name", "grid_order"))
def _cg_block_tol(B, D, Dt, g3, mx, my, mz, cx, cy, cz, tol2, *, n: int,
                  grid: tuple[int, int, int], max_iter: int, sz: int,
                  interpret: bool, acc_name: str, x_name: str,
                  grid_order: str = "parallel") -> CGResult:
    nrhs = B.shape[0]
    acc = jnp.dtype(acc_name)
    (x3, r3, p3, rtz0, beta0), g3 = _block_init(
        B, g3, cx, cy, cz, n=n, acc=acc, x_name=x_name)
    tol2 = jnp.asarray(tol2, acc)

    # cg()'s stopping rule per RHS, jointly: iterate while any RHS is
    # still above tol (converged lanes keep iterating — harmless, their
    # recurrences stay finite — so the batch exits together and every
    # lane's trajectory is a prefix of its fixed-iteration one).
    def cond(state):
        _, _, _, rtz, _, _, kk = state
        return jnp.logical_and(kk < max_iter,
                               jnp.any(jnp.abs(rtz) > tol2))

    def body(state):
        x3, r3, p3, rtz, beta, hist, kk = state
        hist = hist.at[:, kk].set(jnp.sqrt(jnp.abs(rtz)))
        x3, r3, p3, rtz_new, beta = _block_iter(
            x3, r3, p3, rtz, beta, D=D, Dt=Dt, g3=g3, mx=mx, my=my, mz=mz,
            cx=cx, cy=cy, cz=cz, n=n, grid=grid, sz=sz, interpret=interpret,
            acc_name=acc_name, grid_order=grid_order)
        return x3, r3, p3, rtz_new, beta, hist, kk + 1

    hist0 = jnp.full((nrhs, max_iter + 1), jnp.nan, dtype=acc)
    state = (x3, r3, p3, rtz0, beta0, hist0, jnp.asarray(0))
    x3, r3, p3, rtz, beta, hist, kk = jax.lax.while_loop(cond, body, state)
    hist = hist.at[:, kk].set(jnp.sqrt(jnp.abs(rtz)))
    return CGResult(x=_ax.from_lanes(x3, n), iters=kk, rnorm=hist[:, kk],
                    rnorm_history=hist)


@functools.partial(jax.jit, static_argnames=("n", "grid", "max_iter", "sz",
                                             "interpret", "acc_name",
                                             "x_name", "grid_order"))
def _cg_block_coupled(B, invd, D, Dt, g3, mx, my, mz, cx, cy, cz, tol2, *,
                      n: int, grid: tuple[int, int, int], max_iter: int,
                      sz: int, interpret: bool, acc_name: str, x_name: str,
                      grid_order: str = "parallel") -> CGResult:
    """Coupled block (P)CG core: one Krylov solve over all b lanes.

    ``invd`` is the shared Jacobi diagonal ``(E, n^3)``, or ``None`` for
    plain CG.  Preconditioned, the loop carries ``z = invd * r`` as
    :func:`repro.core.precond._pcg_jacobi` does; alpha is
    ``sum_j rtz_j / sum_j pap_j`` and beta ``sum_j rtz_j' / sum_j rtz_j``.
    Each lane's partials are formed exactly as the single-RHS core forms
    them, so ``b = 1`` reproduces ``_pcg_jacobi`` (and, unpreconditioned,
    ``_cg_v2_tol``) bit for bit.  ``tol2 = -1`` runs exactly ``max_iter``
    iterations (the fixed-iteration twin).  ``rnorm_history`` holds the
    whole field's ``sqrt(r·c·r)``.
    """
    nrhs, E = B.shape[0], B.shape[1]
    n3 = n ** 3
    acc = jnp.dtype(acc_name)
    b2 = _ax.to_lanes(B.reshape(nrhs, E, n3), n)
    g3 = _ax.metric_lanes(g3, n)
    c2 = _ax.to_lanes(box_outer(cz, cy, cx).reshape(E, n3).astype(acc), n)
    b_acc = b2.astype(acc)

    def lanes_total(parts):
        return sum(parts[1:], parts[0])

    if invd is None:
        z0 = b2
        rtz0 = lanes_total([initial_rtz(B[j], cz, cy, cx, acc)
                            for j in range(nrhs)])
        rcr0 = rtz0
    else:
        invd2 = _ax.to_lanes(invd, n)
        z0 = (invd2.astype(acc) * b_acc).astype(B.dtype)
        rtz0 = lanes_total([jnp.sum(b_acc[j] * c2 * z0[j].astype(acc))
                            for j in range(nrhs)])
        rcr0 = lanes_total([jnp.sum(b_acc[j] * c2 * b_acc[j])
                            for j in range(nrhs)])
    hist0 = jnp.full((max_iter + 1,), jnp.nan, dtype=acc) \
        .at[0].set(jnp.sqrt(jnp.abs(rcr0)))
    tol2 = jnp.asarray(tol2, acc)

    def cond(state):
        _, _, _, rtz, _, _, kk = state
        return jnp.logical_and(kk < max_iter, jnp.abs(rtz) > tol2)

    def body(state):
        x3, z3, p3, rtz, beta, hist, kk = state
        p3, w3, bot, top, pap_b = _ax.nekbone_ax_slab_block_pallas(
            p3, z3, D, Dt, g3, mx, my, mz,
            jnp.broadcast_to(beta, (1, nrhs)), n=n, grid=grid, sz=sz,
            interpret=interpret, acc_dtype=acc_name, grid_order=grid_order)
        alpha = jnp.broadcast_to(rtz / jnp.sum(pap_b), (1, nrhs))
        addb, addt = _ax.shift_planes(bot, top)
        if invd is None:
            x3, z3, rcr_b = _ax.nekbone_cg_update_block_pallas(
                x3, p3, z3, w3, addb, addt, alpha, cx, cy, cz, n=n,
                grid=grid, sz=sz, interpret=interpret, acc_dtype=acc_name)
            rtz_b = rcr_b
        else:
            x3, z3, rtz_b, rcr_b = _ax.nekbone_pcg_update_block_pallas(
                x3, p3, z3, w3, addb, addt, alpha, invd2, cx, cy, cz, n=n,
                grid=grid, sz=sz, interpret=interpret, acc_dtype=acc_name)
        rtz_new = jnp.sum(rtz_b)
        beta = rtz_new / rtz
        hist = hist.at[kk + 1].set(jnp.sqrt(jnp.abs(jnp.sum(rcr_b))))
        return x3, z3, p3, rtz_new, beta, hist, kk + 1

    state = (jnp.zeros(b2.shape, jnp.dtype(x_name)), z0, jnp.zeros_like(z0),
             rtz0, jnp.zeros((), acc), hist0, jnp.asarray(0))
    x3, z3, p3, rtz, beta, hist, kk = jax.lax.while_loop(cond, body, state)
    return CGResult(x=_ax.from_lanes(x3, n).reshape(B.shape), iters=kk,
                    rnorm=hist[kk], rnorm_history=hist)


def _prepare_block(B, D, g, grid, mask, c, sz, grid_order, interpret,
                   precision, precond=None):
    """Shared public-driver setup: batch-axis lift, precision policy,
    autotuned (sz, grid_order) at this RHS count (keyed on the Jacobi
    update kernel when ``precond`` is ``"jacobi"``), box-field
    validation, factor/operator preparation."""
    from repro.kernels import ops as kernel_ops

    B = jnp.asarray(B)
    if B.ndim == 4:
        B = B[None]
    if B.ndim != 5:
        raise ValueError(
            f"cg_block expects (b, E, n, n, n) or (E, n, n, n); "
            f"got shape {B.shape}")
    policy = resolve_policy(precision, B.dtype)
    B = jnp.asarray(B, policy.storage_dtype)
    nrhs, E = B.shape[0], B.shape[1]
    n = B.shape[-1]
    grid = tuple(grid)
    if interpret is None:
        interpret = kernel_ops.default_interpret()
    sz, grid_order = _autotune.slab_config(
        "slab", grid, n, B.dtype, acc_dtype=policy.accum, precond=precond,
        nrhs=nrhs, sz=sz, grid_order=grid_order)

    _check_box_fields(grid, n, mask, c)
    (mx, my, mz), (cx, cy, cz) = kernel_ops.slab_axis_factors(grid, n,
                                                             B.dtype)
    D_op = jnp.asarray(D, policy.op_storage_dtype)
    g3 = kernel_ops.diag_metric(
        jnp.asarray(g, policy.op_storage_dtype), E, n)
    return (policy, B, n, grid, sz, grid_order, interpret,
            (mx, my, mz), (cx, cy, cz), D_op, g3)


def cg_block_fixed_iters(B: jnp.ndarray, *, D: jnp.ndarray, g: jnp.ndarray,
                         grid: tuple[int, int, int], niter: int,
                         mask: jnp.ndarray | None = None,
                         c: jnp.ndarray | None = None,
                         sz: int | None = None,
                         grid_order: str | None = None,
                         interpret: bool | None = None,
                         precision=None) -> SolveResult:
    """Fixed-iteration multi-RHS CG through the batched v2 kernels.

    Args:
      B:     (b, E, n, n, n) assembled, masked right-hand sides — or a
             single (E, n, n, n) RHS, solved as ``b = 1``; elements
             z-major over ``grid``.
      D, g, grid, niter, mask, c, sz, grid_order, interpret,
      precision: exactly :func:`repro.core.cg_fused.cg_fused_v2_fixed_iters`
             (the autotuned slab config additionally keys on b — the RHS
             batch scales the VMEM footprint).

    Returns a :class:`SolveResult` with per-RHS ``history`` (b, niter+1),
    ``rnorm`` and ``achieved_rtol`` (b,).  At ``b = 1`` the trajectory is
    fp64-bitwise identical to the single-RHS v2 driver.
    """
    (policy, B, n, grid, sz, grid_order, interpret,
     (mx, my, mz), (cx, cy, cz), D_op, g3) = _prepare_block(
        B, D, g, grid, mask, c, sz, grid_order, interpret, precision)
    nrhs = B.shape[0]
    # tracing: the batched solve is one jitted program; the host
    # boundary is this dispatch, recorded as a single span when on.
    from repro.obs import trace as _trace

    with _trace.span("block.dispatch", b=nrhs, coupled=False, niter=niter):
        res = _cg_block(B.reshape(nrhs, B.shape[1], n ** 3), D_op,
                        D_op.T, g3, mx, my, mz, cx, cy, cz, n=n,
                        grid=grid, niter=niter, sz=sz,
                        interpret=interpret, acc_name=policy.accum,
                        x_name=policy.x_storage_dtype.name,
                        grid_order=grid_order)
    return SolveResult.from_cg(
        res._replace(x=res.x.reshape(B.shape)),
        pipeline=f"fused_v2_rhs{nrhs}")


def cg_block_tol(B: jnp.ndarray, *, D: jnp.ndarray, g: jnp.ndarray,
                 grid: tuple[int, int, int], tol: float = 1e-8,
                 max_iter: int = 100,
                 mask: jnp.ndarray | None = None,
                 c: jnp.ndarray | None = None,
                 sz: int | None = None,
                 grid_order: str | None = None,
                 interpret: bool | None = None,
                 precision=None) -> SolveResult:
    """Tolerance-driven multi-RHS CG: iterate until *every* RHS meets
    :func:`repro.core.cg.cg`'s stopping rule (``|rtz| > tol**2`` checked
    before each iteration) or ``max_iter``.

    Converged lanes keep iterating until the whole batch is done — the
    per-RHS histories are prefixes of the fixed-iteration trajectories,
    NaN-padded to ``max_iter + 1``; ``iters`` is the joint count run.
    """
    (policy, B, n, grid, sz, grid_order, interpret,
     (mx, my, mz), (cx, cy, cz), D_op, g3) = _prepare_block(
        B, D, g, grid, mask, c, sz, grid_order, interpret, precision)
    nrhs = B.shape[0]
    from repro.obs import trace as _trace

    with _trace.span("block.dispatch", b=nrhs, coupled=False, tol=tol):
        res = _cg_block_tol(B.reshape(nrhs, B.shape[1], n ** 3), D_op,
                            D_op.T, g3, mx, my, mz, cx, cy, cz,
                            float(tol) ** 2, n=n, grid=grid,
                            max_iter=max_iter, sz=sz, interpret=interpret,
                            acc_name=policy.accum,
                            x_name=policy.x_storage_dtype.name,
                            grid_order=grid_order)
    return SolveResult.from_cg(
        res._replace(x=res.x.reshape(B.shape)),
        pipeline=f"fused_v2_rhs{nrhs}")


def _coupled(B, tol2, max_iter, *, D, g, grid, precond, mask, c, sz,
             grid_order, interpret, precision) -> SolveResult:
    """Prepare (``driver.prepare``) and dispatch (``block.dispatch``) one
    coupled block solve; ``precond`` is ``None``, ``"jacobi"`` or a
    :class:`repro.core.precond.JacobiPrecond`."""
    from repro.core import precond as precond_mod
    from repro.obs import trace as _trace

    if precond is not None and not isinstance(precond,
                                              precond_mod.JacobiPrecond) \
            and precond != "jacobi":
        raise ValueError(f"the coupled block driver runs plain CG or "
                         f"Jacobi PCG; got precond={precond!r}")
    with _trace.span("driver.prepare"):
        (policy, B, n, grid, sz, grid_order, interpret,
         (mx, my, mz), (cx, cy, cz), D_op, g3) = _prepare_block(
            B, D, g, grid, mask, c, sz, grid_order, interpret, precision,
            precond=None if precond is None else "jacobi")
        invd = None
        if precond is not None:
            if not isinstance(precond, precond_mod.JacobiPrecond):
                precond = precond_mod.make_preconditioner(
                    "jacobi", D=D, g=g, grid=grid, mask=mask, c=c)
            invd = jnp.asarray(precond.invdiag, policy.op_storage_dtype) \
                .reshape(B.shape[1], n ** 3)
    nrhs = B.shape[0]
    with _trace.span("block.dispatch", b=nrhs, coupled=True):
        res = _cg_block_coupled(
            B, invd, D_op, D_op.T, g3, mx, my, mz, cx, cy, cz, tol2, n=n,
            grid=grid, max_iter=max_iter, sz=sz, interpret=interpret,
            acc_name=policy.accum, x_name=policy.x_storage_dtype.name,
            grid_order=grid_order)
    return SolveResult.from_cg(
        res, pipeline=f"fused_v2_rhs{nrhs}",
        precond=None if precond is None else "jacobi")


def cg_block_coupled_tol(B: jnp.ndarray, *, D: jnp.ndarray, g: jnp.ndarray,
                         grid: tuple[int, int, int], tol: float = 1e-8,
                         max_iter: int = 100, precond=None,
                         mask: jnp.ndarray | None = None,
                         c: jnp.ndarray | None = None,
                         sz: int | None = None,
                         grid_order: str | None = None,
                         interpret: bool | None = None,
                         precision=None) -> SolveResult:
    """Tolerance-driven coupled block (P)CG: the b lanes of ``B``
    (b, E, n, n, n) are the components of one field, solved as one system.

    Iterates while ``k < max_iter`` and ``|sum_j rtz_j| > tol**2``
    (``rtz = r·c·z`` over the whole field; ``r·c·r`` unpreconditioned),
    checked before each iteration, as :func:`repro.core.cg.cg` does.
    ``precond`` is ``None`` or Jacobi (``"jacobi"`` or a
    :class:`repro.core.precond.JacobiPrecond` of the scalar operator,
    shared by every component).  Other arguments as :func:`cg_block_tol`.
    Returns one ``iters``, ``rnorm`` and ``history`` for the whole field.
    """
    return _coupled(B, float(tol) ** 2, max_iter, D=D, g=g, grid=grid,
                    precond=precond, mask=mask, c=c, sz=sz,
                    grid_order=grid_order, interpret=interpret,
                    precision=precision)


def cg_block_coupled_fixed_iters(B: jnp.ndarray, *, D: jnp.ndarray,
                                 g: jnp.ndarray, grid: tuple[int, int, int],
                                 niter: int, precond=None,
                                 mask: jnp.ndarray | None = None,
                                 c: jnp.ndarray | None = None,
                                 sz: int | None = None,
                                 grid_order: str | None = None,
                                 interpret: bool | None = None,
                                 precision=None) -> SolveResult:
    """Exactly ``niter`` iterations of :func:`cg_block_coupled_tol`'s body
    (the ``tol2 = -1`` sentinel), so a tolerance-driven trajectory is a
    prefix of this one."""
    return _coupled(B, -1.0, niter, D=D, g=g, grid=grid, precond=precond,
                    mask=mask, c=c, sz=sz, grid_order=grid_order,
                    interpret=interpret, precision=precision)
