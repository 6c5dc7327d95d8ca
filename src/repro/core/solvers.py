"""The solve-driver registry: pipeline × precond × tol × multi-RHS routing.

Before this module the routing lived as branching inlined in
``NekboneCase.solve``; now it is one table (DESIGN.md §12).  A *route* is
a named row of :data:`REGISTRY`; :func:`route_name` is the pure function
(case, request) -> row, and :func:`solve_case` executes it.  The
top-level facade :func:`repro.solve` and the solver service
(launch/solver_service.py) both dispatch through here, so there is
exactly one place where "which driver runs this request" is decided.

Routes (every driver returns :class:`repro.core.cg.SolveResult`):

=================  ======================================================
``block``          multi-RHS batched v2 (core/cg_block.py) — b > 1, or
                   an explicitly batched RHS, unpreconditioned
``block_loop``     b > 1 with a preconditioner or a non-v2 pipeline:
                   per-RHS solves through this table, stacked
``vector``         a vector case (``components`` > 1) on the v2 pipeline,
                   plain or Jacobi: the coupled block driver
                   (cg_block_coupled_*), one Krylov solve over the field
``ir``             refined-precision fixed-iters (cg_ir_fixed_iters)
``sstep``          v3 matrix-powers cycles (cg_sstep_fixed_iters;
                   tol-driven via the per-cycle host sync)
``v2``             fused v2 fixed-iters, plain or fused PCG
``v2_tol``         tolerance-driven fused v2 (P)CG (cg_fused_tol)
``v1``             fused v1 fixed-iters
``reference``      XLA reference CG (cg / cg_fixed_iters), optional
                   reference preconditioner; coupled over the components
                   of a vector case
=================  ======================================================

A vector case (``NekboneCase.components`` = 3, CEED BP6) takes one
``(3, E, n, n, n)`` field per solve: one linear system whose dots,
residual measure and stopping rule run over the whole field.  A batch of
vector fields is refused.
"""
from __future__ import annotations

import warnings
from typing import Callable

import jax.numpy as jnp

import repro.core.cg as cg_mod
import repro.core.cg_fused as cg_fused_mod
from repro.core.cg import SolveResult

__all__ = ["REGISTRY", "route_name", "solve_case", "solve"]

# one-time flag for the documented b>1 s-step fallback warning below
# (tests reset it to re-assert the warning fires).
_SSTEP_BLOCK_WARNED = False


# ---------------------------------------------------------------------------
# drivers — uniform signature: (case, f, *, b, niter, tol, max_iter,
# pc_name) -> SolveResult.  ``pc_name`` is the already-resolved registry
# preconditioner name (None = unpreconditioned).  The v2-family routes pass
# ``case.box_fields()`` (checked once per case) as ``g`` and no mask/c, so
# the drivers' per-call field checks have nothing left to check.
# ---------------------------------------------------------------------------

def _drive_block(case, f, *, b, niter, tol, max_iter, pc_name):
    from repro.core.cg_block import cg_block_fixed_iters, cg_block_tol

    if niter is not None:
        return cg_block_fixed_iters(
            f, D=case.D, g=case.box_fields(), grid=case.grid, niter=niter,
            precision=case.precision)
    return cg_block_tol(
        f, D=case.D, g=case.box_fields(), grid=case.grid, tol=tol,
        max_iter=max_iter, precision=case.precision)


def _drive_block_loop(case, f, *, b, niter, tol, max_iter, pc_name):
    """Per-RHS fallback for batched requests outside the block kernels'
    coverage (preconditioned, refined, or non-v2 pipelines): each RHS
    routes through the registry independently and the results stack."""
    parts = [_solve_resolved(case, f[j], b=1, niter=niter, tol=tol,
                             max_iter=max_iter, pc_name=pc_name)
             for j in range(f.shape[0])]
    return SolveResult(
        x=jnp.stack([p.x for p in parts]),
        history=jnp.stack([p.history for p in parts]),
        iters_taken=jnp.stack([p.iters_taken for p in parts]),
        achieved_rtol=jnp.stack([p.achieved_rtol for p in parts]),
        rnorm=jnp.stack([p.rnorm for p in parts]),
        pipeline=parts[0].pipeline, precond=parts[0].precond)


def _drive_vector(case, f, *, b, niter, tol, max_iter, pc_name):
    from repro.core.cg_block import (cg_block_coupled_fixed_iters,
                                     cg_block_coupled_tol)

    spec = case.precond_spec(pc_name) if pc_name else None
    if niter is not None:
        return cg_block_coupled_fixed_iters(
            f, D=case.D, g=case.box_fields(), grid=case.grid, niter=niter,
            precond=spec, precision=case.precision)
    return cg_block_coupled_tol(
        f, D=case.D, g=case.box_fields(), grid=case.grid, tol=tol,
        max_iter=max_iter, precond=spec, precision=case.precision)


def _drive_ir(case, f, *, b, niter, tol, max_iter, pc_name):
    variant = {"pallas_fused_cg_v2": "v2",
               "pallas_sstep_v3": "sstep"}.get(case.ax_impl, "v1")
    if variant == "v2":
        # the outer residual applies the packed diagonal and rebuilds the
        # box mask/c from their factors.
        return cg_fused_mod.cg_ir_fixed_iters(
            f, D=case.D, g=case.box_fields(), grid=case.grid, niter=niter,
            precision=case.precision, variant=variant)
    return cg_fused_mod.cg_ir_fixed_iters(
        f, D=case.D, g=case.g, grid=case.grid, niter=niter,
        precision=case.precision, mask=case.mask, c=case.c,
        variant=variant, s=case.s)


def _drive_sstep(case, f, *, b, niter, tol, max_iter, pc_name):
    from repro.core.cg_sstep import cg_sstep_fixed_iters, estimate_theta

    # the basis scale depends only on the case's operator — estimate once
    # per case, not once per solve.
    theta = getattr(case, "_sstep_theta", None)
    if theta is None:
        theta = estimate_theta(case.D, case.g, case.grid, case.mask)
        case._sstep_theta = theta
    g3 = case.box_fields()
    if niter is not None:
        return cg_sstep_fixed_iters(
            f, D=case.D, g=g3, grid=case.grid, niter=niter, s=case.s,
            theta=theta, precision=case.precision)
    # tolerance-driven: the per-cycle host sync checks the stored-residual
    # reduction and the f64 Gram recurrence resolves the stopping point to
    # iteration granularity (DESIGN.md §9.4).
    return cg_sstep_fixed_iters(
        f, D=case.D, g=g3, grid=case.grid, niter=max_iter, s=case.s,
        theta=theta, tol=tol, precision=case.precision)


def _drive_v2(case, f, *, b, niter, tol, max_iter, pc_name):
    from repro.core import precond as precond_mod

    spec = case.precond_spec(pc_name) if pc_name else None
    if spec is None:
        return cg_fused_mod.cg_fused_v2_fixed_iters(
            f, D=case.D, g=case.box_fields(), grid=case.grid, niter=niter,
            precision=case.precision)
    return precond_mod.pcg_fused_v2_fixed_iters(
        f, D=case.D, g=case.box_fields(), grid=case.grid, niter=niter,
        precond=spec, precision=case.precision)


def _drive_v2_tol(case, f, *, b, niter, tol, max_iter, pc_name):
    from repro.core import precond as precond_mod

    spec = case.precond_spec(pc_name) if pc_name else None
    return precond_mod.cg_fused_tol(
        f, D=case.D, g=case.box_fields(), grid=case.grid, tol=tol,
        max_iter=max_iter, precond=spec, precision=case.precision)


def _drive_v1(case, f, *, b, niter, tol, max_iter, pc_name):
    return cg_fused_mod.cg_fused_fixed_iters(
        f, D=case.D, g=case.g, mask=case.mask, c=case.c, grid=case.grid,
        niter=niter, precision=case.precision)


def _drive_reference(case, f, *, b, niter, tol, max_iter, pc_name):
    A = case.ax_full
    M = case._reference_preconditioner(pc_name)
    if case.components > 1:
        # one system over the components: the operator and the
        # preconditioner act per component, the dots over the whole field.
        A = _per_component(A)
        M = None if M is None else _per_component(M)
    if niter is not None:
        return cg_mod.cg_fixed_iters(A, f, niter=niter, dot=case.dot(),
                                     precond=M)
    return cg_mod.cg(A, f, tol=tol, max_iter=max_iter, dot=case.dot(),
                     precond=M)


def _per_component(op: Callable) -> Callable:
    return lambda u: jnp.stack([op(u[j]) for j in range(u.shape[0])])


REGISTRY: dict[str, Callable] = {
    "block": _drive_block,
    "block_loop": _drive_block_loop,
    "vector": _drive_vector,
    "ir": _drive_ir,
    "sstep": _drive_sstep,
    "v2": _drive_v2,
    "v2_tol": _drive_v2_tol,
    "v1": _drive_v1,
    "reference": _drive_reference,
}


def route_name(case, *, b: int = 1, niter: int | None = None,
               pc_name: str | None = None) -> str:
    """Which :data:`REGISTRY` row serves this request — the routing that
    used to live as branching in ``NekboneCase.solve``, as one pure
    function."""
    fused = case.ax_impl in ("pallas_fused_cg", "pallas_fused_cg_v2",
                             "pallas_sstep_v3")
    refined = False
    if fused and case.precision is not None:
        from repro.core.precision import resolve_policy

        refined = resolve_policy(case.precision).refine
    fused_v2_family = case.ax_impl in ("pallas_fused_cg_v2",
                                       "pallas_sstep_v3")
    if case.components > 1:
        if b > 1:
            raise ValueError(_VECTOR_BATCH)
        if case.ax_impl == "pallas_fused_cg_v2" and not refined \
                and pc_name in (None, "jacobi"):
            return "vector"
        return "reference"
    if b > 1:
        # the batched kernels are the (unpreconditioned, non-refined) v2
        # pipeline; everything else solves per RHS through this table.
        if pc_name is None and not refined and (
                fused_v2_family or case.ax_impl == "pallas_fused_cg"):
            if case.ax_impl == "pallas_sstep_v3":
                # explicit, documented fallback: there is no batched
                # matrix-powers kernel — a b>1 s-step case runs the
                # multi-RHS *v2* block pipeline instead (same answer,
                # the v2 byte books).  Warn once per process so the
                # substitution is visible without spamming sweeps.
                global _SSTEP_BLOCK_WARNED
                if not _SSTEP_BLOCK_WARNED:
                    _SSTEP_BLOCK_WARNED = True
                    warnings.warn(
                        "b>1 on ax_impl='pallas_sstep_v3': no batched "
                        "s-step kernel exists; routing through the "
                        "multi-RHS v2 block pipeline (fused_v2_rhs<b>). "
                        "Set ax_impl='pallas_fused_cg_v2' to silence.",
                        UserWarning, stacklevel=3)
            return "block"
        return "block_loop"
    if refined and niter is not None and pc_name is None:
        return "ir"
    if case.ax_impl == "pallas_sstep_v3" and pc_name is None \
            and not refined:
        return "sstep"
    if case.ax_impl == "pallas_fused_cg_v2" and not refined:
        return "v2" if niter is not None else "v2_tol"
    if case.ax_impl == "pallas_fused_cg" and niter is not None \
            and pc_name is None and not refined:
        return "v1"
    return "reference"


_VECTOR_BATCH = ("a vector case solves one (components, E, n, n, n) field "
                 "per call; batches of vector fields are not supported")


def solve_case(case, f: jnp.ndarray, *, b: int | None = None,
               niter: int | None = None, tol: float = 1e-8,
               max_iter: int = 1000,
               precond: str | None = None) -> SolveResult:
    """Route one solve request through the registry.

    ``b`` is the RHS batch: ``None`` infers it from ``f``'s shape (a
    leading axis ahead of (E, n, n, n) is a batch), 1 forces a single-RHS
    solve, > 1 requires ``f`` of shape (b, E, n, n, n).  ``precond``
    accepts the registry names (resolved by
    :meth:`NekboneCase._precond_name`; the removed booleans raise
    ``TypeError`` there).
    """
    pc_name = case._precond_name(precond)
    f = jnp.asarray(f)
    comps = case.components
    if comps > 1:
        if b not in (None, 1) or f.ndim > 5:
            raise ValueError(_VECTOR_BATCH)
        want = (comps,) + tuple(case.mask.shape)
        if f.shape != want:
            raise ValueError(f"a {comps}-component case needs a field of "
                             f"shape {want}; got {f.shape}")
        b = 1
    batched = f.ndim == 5 and comps == 1
    if b is None:
        b = f.shape[0] if batched else 1
    if batched and f.shape[0] != b:
        raise ValueError(f"b={b} but rhs has leading batch {f.shape[0]}")
    if b > 1 and not batched:
        raise ValueError(f"b={b} needs a (b, E, n, n, n) rhs; "
                         f"got {f.shape}")
    f_in = f[0] if (batched and b == 1) else f
    from repro.obs import trace as _trace

    route = route_name(case, b=b, niter=niter, pc_name=pc_name)
    kw = dict(b=b, niter=niter, tol=tol, max_iter=max_iter, pc_name=pc_name)
    with _trace.span("solve", route=route, b=b, components=comps,
                     niter=niter, precond=pc_name,
                     ax_impl=getattr(case, "ax_impl", None)):
        if _trace.active() is None:    # no recorder: the plain dispatch
            res = REGISTRY[route](case, f_in, **kw)
        else:
            res = _recorded_solve(case, f_in, route, **kw)
    # a batched rhs always comes back batched, even at b=1 through a
    # single-RHS route (callers index res.x[j] uniformly).
    if batched and b == 1 and res.x.ndim == 4:
        res = SolveResult(x=res.x[None], history=res.history[None],
                          iters_taken=res.iters_taken[None],
                          achieved_rtol=res.achieved_rtol[None],
                          rnorm=res.rnorm[None], pipeline=res.pipeline,
                          precond=res.precond, telemetry=res.telemetry)
    return res


def _solve_resolved(case, f, *, b, niter, tol, max_iter, pc_name):
    name = route_name(case, b=b, niter=niter, pc_name=pc_name)
    return REGISTRY[name](case, f, b=b, niter=niter, tol=tol,
                          max_iter=max_iter, pc_name=pc_name)


def _recorded_solve(case, f, route, *, b, niter, tol, max_iter, pc_name):
    """The dispatch under a recorder: the same registry call (so the
    solve output is bitwise identical), with a
    :class:`~repro.obs.metrics.SolveTelemetry` attached to the result's
    non-pytree ``telemetry`` field.  The ``block_until_ready`` and the
    iters/rtol device reads in ``capture_solve`` are syncs the path
    without a recorder never pays."""
    import dataclasses

    import jax

    from repro.kernels import autotune as _autotune
    from repro.kernels.timing import stopwatch
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as _trace

    at0 = _autotune.cache_stats()
    sw = stopwatch()
    res = REGISTRY[route](case, f, b=b, niter=niter, tol=tol,
                          max_iter=max_iter, pc_name=pc_name)
    jax.block_until_ready(res.x)
    wall = sw.us()
    at1 = _autotune.cache_stats()
    _trace.count("solves")
    tel = obs_metrics.capture_solve(
        res, route=route, b=b, niter=niter,
        tol=None if niter is not None else tol, wall_us=wall,
        phases={"dispatch": round(wall, 3)},
        autotune={k: at1[k] - at0.get(k, 0) for k in at1})
    return dataclasses.replace(res, telemetry=tel)


def solve(case_or_config, f: jnp.ndarray | None = None, *,
          b: int | None = None, niter: int | None = None,
          tol: float | None = None, max_iter: int = 1000,
          precond: str | None = None) -> SolveResult:
    """Top-level solve facade (re-exported as ``repro.solve``).

    Args:
      case_or_config: a :class:`repro.core.nekbone.NekboneCase`, a
          :class:`repro.configs.nekbone.NekboneConfig` (instantiated via
          ``make_case()``), or an int — a paper-grid element count
          (``repro.configs.nekbone.PAPER_CASES`` key).
      f: right-hand side(s), (E, n, n, n) or (b, E, n, n, n).  ``None``
          solves the case's manufactured problem (replicated to ``b``).
      b: RHS batch; default: inferred from ``f`` (or the case's ``b``).
      niter: fixed iteration count; ``None`` = tolerance-driven.
      tol: stopping tolerance for the tol-driven mode (default 1e-8);
          ignored when ``niter`` is given.
      precond: registry preconditioner name; ``None`` inherits the case.

    Returns a :class:`SolveResult`.
    """
    case = case_or_config
    if isinstance(case, int):
        from repro.configs.nekbone import PAPER_CASES

        case = PAPER_CASES[case]
    if hasattr(case, "make_case"):          # NekboneConfig
        case = case.make_case()
    if b is None and f is None:
        b = getattr(case, "b", 1)
    if f is None:
        _, f1 = case.manufactured()
        if case.components > 1:
            f1 = jnp.stack([f1] * case.components)
        f = f1 if (b is None or b == 1) else jnp.stack([f1] * b)
    return solve_case(case, f, b=b, niter=niter,
                      tol=1e-8 if tol is None else tol,
                      max_iter=max_iter, precond=precond)
