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
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.cg import CGResult, SolveResult
from repro.core.cg_fused import _check_box_fields, initial_rtz
from repro.core.precision import resolve_policy
from repro.kernels import autotune as _autotune
from repro.kernels import nekbone_ax as _ax

__all__ = ["cg_block_fixed_iters", "cg_block_tol"]


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


def _prepare_block(B, D, g, grid, mask, c, sz, grid_order, interpret,
                   precision):
    """Shared public-driver setup: batch-axis lift, precision policy,
    autotuned (sz, grid_order) at this RHS count, box-field
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
    if sz is None and grid_order is None:
        sz, grid_order = _autotune.pick_slab_config(
            grid, n, B.dtype, acc_dtype=policy.accum, nrhs=nrhs)
    elif sz is None:
        sz = _autotune.pick_slab_sz(grid, n, B.dtype,
                                    acc_dtype=policy.accum, nrhs=nrhs)
    grid_order = "parallel" if grid_order is None else grid_order

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

    with _trace.span("block.dispatch", b=nrhs, niter=niter):
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

    with _trace.span("block.dispatch", b=nrhs, tol=tol):
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
