"""Step-fused conjugate gradients: the whole iteration in Pallas kernels.

``cg_fixed_iters`` (core/cg.py) composes the operator and the three inner
products from separate XLA ops; per iteration the vectors ``p``, ``w``,
``r``, ``c`` are re-read from HBM for every reduction the paper's Eq. 2
charges for.  This module runs the iteration the way the cost model wants it
counted (DESIGN.md §3), at two fusion levels:

**v1** (:func:`cg_fused_fixed_iters`, DESIGN.md §3.3): one multi-output
Pallas kernel computes the masked local operator and the ``p·c·Ap`` partial
in the same VMEM residency; the direct-stiffness summation and the vector
updates remain XLA passes.  The ``r·c·r`` reduction is *carried* through the
loop state (it equals the previous iteration's post-update reduction), so
the kernel never re-reads ``r``/``c`` — 17 streams/iteration against
Eq. 2's 30.

**v2** (:func:`cg_fused_v2_fixed_iters`, DESIGN.md §3.4): zero standalone
full-field XLA passes.  The grid marches whole z-slabs, so the x/y
direct-stiffness summation and the intra-block z interfaces are summed on
the VMEM-resident kernel output; the two cross-block boundary planes travel
as O(E n^2) side outputs and are stitched in VMEM by a second, merged
vector-update kernel that also performs both axpys and the post-update
``r·c·r`` partial.  The ``p = r + beta p`` update folds into the next
iteration's operator kernel (beta enters as a scalar operand), and the
structured box's mask / inner-product weight are rebuilt in-kernel from
per-axis factors while the axis-aligned metric collapses to its diagonal —
13 streams/iteration.

**sharded** (:func:`cg_fused_sharded_fixed_iters`): the v1 pipeline per
shard inside ``shard_map``, with ``ds_sum_sharded`` exchanging the
cross-shard z-planes and the inner-product partials ``psum``-reduced.

All variants are *algebraically identical* to
:func:`repro.core.cg.cg_fixed_iters` with ``M = I``; the inner products are
summed in a different association (per-block then tree), so histories agree
to dtype round-off, which the fp64-interpret parity tests pin down
(tests/test_cg_fused.py, tests/test_cg_fused_v2.py).

**mixed precision** (DESIGN.md §7): every entry point takes a
``precision`` policy (:mod:`repro.core.precision`) splitting the *storage*
dtype — what ``x``/``r``/``p``/``w`` and the metric occupy in HBM, hence
what every stream above is billed in — from the *accumulation* dtype the
kernels upcast to for the contractions and the ``p·c·Ap`` / ``r·c·r``
partials.  bf16 storage halves f32's bytes/iteration; the stalled bf16
residual floor is recovered by :func:`cg_ir_fixed_iters`, which wraps the
low-precision inner solve in an iterative-refinement outer loop whose
residuals are formed in the caller's (high) precision.

Preconditions: ``b`` must be assembled ("continuous": coincident copies
equal — manufactured right-hand sides are) and masked; unpreconditioned CG
only (Nekbone's benchmark configuration and the paper's §V protocol).  The
v2 path additionally requires the structured axis-aligned box fields
(diagonal metric, factorizable mask — what ``BoxMesh`` produces).
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
from repro.obs import trace as _trace

__all__ = ["cg_fused_fixed_iters", "cg_fused_v2_fixed_iters",
           "cg_fused_sharded_fixed_iters", "cg_ir_fixed_iters"]


# ---------------------------------------------------------------------------
# v1: fused operator+pap kernel, XLA assembly and vector pass
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n", "grid", "niter", "block_e",
                                             "interpret", "acc_name",
                                             "x_name"))
def _cg_fused(b, D, Dt, g2, mask2, c, *, n: int,
              grid: tuple[int, int, int], niter: int, block_e: int,
              interpret: bool, acc_name: str, x_name: str) -> CGResult:
    E = b.shape[0]
    n3 = n ** 3
    # inner products, alpha/beta, and the residual history live in the
    # policy's accumulation dtype; the fori_loop carries r/p in the storage
    # dtype (= b.dtype) and x in the policy's (possibly wider) x-storage
    # dtype, so the HBM residency is exactly what Eq. 2 bills.
    acc = jnp.dtype(acc_name)
    x_dtype = jnp.dtype(x_name)
    # the loop runs in the kernels' (n, n^2, E) layout: operands are
    # converted once here, the solution once at the end.
    bl = _ax.to_lanes(b.reshape(E, n3), n)
    g = _ax.metric_lanes(g2, n)
    mask = _ax.to_lanes(mask2, n)
    c_acc = _ax.to_lanes(c.reshape(E, n3), n).astype(acc)
    # r·c·r is carried through the loop: each iteration's post-update
    # reduction (fused by XLA with the axpys that produce r) is next
    # iteration's rtz, so the kernel needs no r/c operands (DESIGN.md §3.3).
    rtz0 = jnp.sum(bl.astype(acc) * c_acc * bl.astype(acc))

    def body(k, state):
        x, r, p, rtz, hist = state
        hist = hist.at[k].set(jnp.sqrt(jnp.abs(rtz)))
        w, pap_b = _ax.nekbone_ax_pap_pallas(
            p, D, Dt, g, mask, n=n, block_e=block_e, interpret=interpret,
            acc_dtype=acc_name)
        pap = jnp.sum(pap_b)            # tree-reduce the per-block partials
        # mask commutes with gs (coincident copies share their mask value),
        # so the kernel's masked output assembles directly.
        w = gs_mod.ds_sum_local(w, grid, lanes=True)
        alpha = rtz / pap
        # axpys evaluated in acc, stored (the loop carry) in storage dtype;
        # for the f32/f64 policies this is bit-identical to pre-policy code.
        x = (x.astype(acc) + alpha * p.astype(acc)).astype(x_dtype)
        r = (r.astype(acc) - alpha * w.astype(acc)).astype(b.dtype)
        # fused by XLA with the axpy above; carried as the next rtz.  The
        # reduction sees the *stored* r so the carried scalar matches the
        # residual the next iteration's kernel actually reads.
        rtz_new = jnp.sum(r.astype(acc) * c_acc * r.astype(acc))
        beta = rtz_new / rtz
        p = (r.astype(acc) + beta * p.astype(acc)).astype(b.dtype)
        return x, r, p, rtz_new, hist

    x = jnp.zeros(bl.shape, x_dtype)
    hist0 = jnp.full((niter + 1,), jnp.nan, dtype=acc)
    state = (x, bl, bl, rtz0, hist0)
    x, r, p, rtz_last, hist = jax.lax.fori_loop(0, niter, body, state)
    hist = hist.at[niter].set(jnp.sqrt(jnp.abs(rtz_last)))
    return CGResult(x=_ax.from_lanes(x, n).reshape(b.shape),
                    iters=jnp.asarray(niter), rnorm=hist[niter],
                    rnorm_history=hist)


def cg_fused_fixed_iters(b: jnp.ndarray, *, D: jnp.ndarray, g: jnp.ndarray,
                         mask: jnp.ndarray, c: jnp.ndarray,
                         grid: tuple[int, int, int], niter: int,
                         block_e: int | None = None,
                         interpret: bool | None = None,
                         precision=None) -> CGResult:
    """Fixed-iteration CG through the fused-iteration Pallas pipeline (v1).

    Args:
      b:     (E, n, n, n) assembled, masked right-hand side.
      D:     (n, n) derivative matrix.
      g:     (E, 6, n, n, n) metric fields.
      mask:  (E, n, n, n) Dirichlet mask (0/1 valued).
      c:     (E, n, n, n) inner-product weight (mask / multiplicity).
      grid:  element grid (EX, EY, EZ) with EX*EY*EZ == E.
      niter: iteration count (the paper runs 100).
      block_e: elements per VMEM block; default: autotuned divisor of E
               (kernels/autotune.py).
      interpret: force Pallas interpret mode (default: off-TPU detection).
      precision: policy name / :class:`~repro.core.precision.PrecisionPolicy`
               / ``None`` (infer from ``b.dtype``): operands are cast to the
               storage dtype, kernels accumulate in the accum dtype
               (DESIGN.md §7).

    Returns a :class:`repro.core.cg.CGResult` whose ``rnorm_history`` matches
    ``cg_fixed_iters`` to round-off (of the policy's storage dtype).
    """
    from repro.kernels import ops as kernel_ops

    with _trace.span("driver.prepare"):
        policy = resolve_policy(precision, b.dtype)
        b = jnp.asarray(b, policy.storage_dtype)
        E = b.shape[0]
        n = b.shape[-1]
        if interpret is None:
            interpret = kernel_ops.default_interpret()
        if block_e is None:
            block_e = _autotune.pick_block_e(E, n, b.dtype,
                                             acc_dtype=policy.accum)
        while E % block_e:
            block_e //= 2              # fused path avoids padding: divisor
        block_e = max(block_e, 1)

        n3 = n ** 3
        # operator data (D, metric) in the policy's op-storage dtype:
        # refined policies keep it wide — rounding A itself floors the
        # refinement.
        D = jnp.asarray(D, policy.op_storage_dtype)
        g2 = jnp.asarray(g, policy.op_storage_dtype).reshape(E, 6, n3)
        mask2 = jnp.asarray(mask, b.dtype).reshape(E, n3)
        c = jnp.asarray(c, b.dtype)
    return SolveResult.from_cg(
        _cg_fused(b, D, D.T, g2, mask2, c, n=n, grid=tuple(grid),
                  niter=niter, block_e=block_e, interpret=interpret,
                  acc_name=policy.accum,
                  x_name=policy.x_storage_dtype.name),
        pipeline="fused_v1")


# ---------------------------------------------------------------------------
# v2: slab gather-scatter + merged vector-update kernel
# ---------------------------------------------------------------------------

def _check_box_fields(grid, n, mask, c) -> None:
    """Verify caller-supplied mask/c match the structural box fields.

    The v2 kernels *rebuild* both from per-axis factors
    (``geom.box_axis_factors``), so silently accepting a different mask or
    weight would compute a different problem.  Skipped under tracing
    (concrete mesh fields are checked at build time).  A direct driver call
    that passes the fields pays this host work (the ``driver.validate``
    span) on every call; the case routes (core/solvers.py) check once per
    case through ``NekboneCase.box_fields`` and pass ``mask=c=None``, which
    opens no span.
    """
    if mask is None and c is None:
        return
    with _trace.span("driver.validate"):
        (mx, my, mz), (cx, cy, cz) = box_axis_factors(grid, n)
        for name, field, want in (
                ("mask", mask, box_outer(mz, my, mx).reshape(-1, n, n, n)),
                ("c", c, box_outer(cz, cy, cx).reshape(-1, n, n, n))):
            if field is None:
                continue
            try:
                got = np.asarray(field, np.float64)
            except jax.errors.TracerArrayConversionError:
                continue
            if got.shape != want.shape or not np.array_equal(got, want):
                raise ValueError(
                    f"pallas_fused_cg_v2 requires the structured box {name}"
                    " (per-axis factorizable); supplied field differs")


def _v2_iter(x2, r2, p2, rtz, beta, *, D, Dt, g3, mx, my, mz, cx, cy, cz,
             n: int, grid: tuple[int, int, int], sz: int, interpret: bool,
             acc_name: str, grid_order: str = "parallel"):
    """One full v2 CG iteration (both slab kernels + the plane stitch) on
    kernel-layout state (``(n, n^2, E)`` vectors, ``(3, n, n^2, E)``
    metric).

    Shared by the fixed-iteration driver below and the tolerance-driven
    driver (:func:`repro.core.precond.cg_fused_tol`), so the tol-driven
    trajectory is the fixed-iteration trajectory *by construction* — the
    acceptance property the tests pin.  Returns
    ``(x2, r2, p2, rtz_new, beta_new)``.
    """
    # front half: p = r + beta p, masked Ax, pap partial, in-block
    # assembly; boundary planes leave as (nblk, n^2, EX*EY) side outputs.
    p2, w2, bot, top, pap_b = _ax.nekbone_ax_slab_pallas(
        p2, r2, D, Dt, g3, mx, my, mz, beta.reshape(1, 1),
        n=n, grid=grid, sz=sz, interpret=interpret, acc_dtype=acc_name,
        grid_order=grid_order)
    pap = jnp.sum(pap_b)
    alpha = rtz / pap
    # cross-block stitch operands: each block receives its neighbours'
    # boundary planes (zeros at the global ends) — O(E n^2) traffic.
    addb, addt = _ax.shift_planes(bot, top)
    # back half: stitch w in VMEM, both axpys, post-update r·c·r.
    x2, r2, rcr_b = _ax.nekbone_cg_update_pallas(
        x2, p2, r2, w2, addb, addt, alpha.reshape(1, 1), cx, cy, cz,
        n=n, grid=grid, sz=sz, interpret=interpret, acc_dtype=acc_name)
    rtz_new = jnp.sum(rcr_b)
    beta = rtz_new / rtz
    return x2, r2, p2, rtz_new, beta


def v2_operands(b, g3, cz, cy, cx, acc):
    """Kernel-layout operands of a v2-family solve, built once per solve:
    ``(b, g3, c)`` — the right-hand side ``(n, n^2, E)``, the metric
    diagonal ``(3, n, n^2, E)`` and the inner-product weight in ``acc``
    (rebuilt from the per-axis factors, an XLA constant)."""
    E, n = b.shape[0], b.shape[-1]
    n3 = n ** 3
    c2 = box_outer(cz, cy, cx).reshape(E, n3).astype(acc)
    return (_ax.to_lanes(b.reshape(E, n3), n), _ax.metric_lanes(g3, n),
            _ax.to_lanes(c2, n))


def initial_rtz(b, cz, cy, cx, acc):
    """``b·c·b`` of one natural ``(E, n, n, n)`` right-hand side, summed in
    the element-major order every single- and multi-RHS v2 driver shares
    (so a batch lane starts bitwise where its single solve does)."""
    E, n = b.shape[0], b.shape[-1]
    b2 = b.reshape(E, n ** 3).astype(acc)
    c2 = box_outer(cz, cy, cx).reshape(E, n ** 3).astype(acc)
    return jnp.sum(b2 * c2 * b2)


@functools.partial(jax.jit, static_argnames=("n", "grid", "niter", "sz",
                                             "interpret", "acc_name",
                                             "x_name", "grid_order"))
def _cg_fused_v2(b, D, Dt, g3, mx, my, mz, cx, cy, cz, *, n: int,
                 grid: tuple[int, int, int], niter: int, sz: int,
                 interpret: bool, acc_name: str, x_name: str,
                 grid_order: str = "parallel") -> CGResult:
    acc = jnp.dtype(acc_name)
    x_dtype = jnp.dtype(x_name)
    b2, g3, _ = v2_operands(b, g3, cz, cy, cx, acc)
    rtz0 = initial_rtz(b, cz, cy, cx, acc)

    def body(state):
        x2, r2, p2, rtz, beta, hist, k = state
        hist = hist.at[k].set(jnp.sqrt(jnp.abs(rtz)))
        x2, r2, p2, rtz_new, beta = _v2_iter(
            x2, r2, p2, rtz, beta, D=D, Dt=Dt, g3=g3, mx=mx, my=my, mz=mz,
            cx=cx, cy=cy, cz=cz, n=n, grid=grid, sz=sz, interpret=interpret,
            acc_name=acc_name, grid_order=grid_order)
        return x2, r2, p2, rtz_new, beta, hist, k + 1

    hist0 = jnp.full((niter + 1,), jnp.nan, dtype=acc)
    state = (jnp.zeros(b2.shape, x_dtype), b2, jnp.zeros_like(b2), rtz0,
             jnp.zeros((), acc), hist0)
    # a while loop with the tolerance driver's body (core/precond.
    # cg_fused_tol), compiled alike, so both trajectories stay bitwise
    # identical.
    x2, r2, p2, rtz_last, beta, hist, _ = jax.lax.while_loop(
        lambda st: st[-1] < niter, body, state + (jnp.asarray(0),))
    hist = hist.at[niter].set(jnp.sqrt(jnp.abs(rtz_last)))
    return CGResult(x=_ax.from_lanes(x2, n).reshape(b.shape),
                    iters=jnp.asarray(niter), rnorm=hist[niter],
                    rnorm_history=hist)


def cg_fused_v2_fixed_iters(b: jnp.ndarray, *, D: jnp.ndarray,
                            g: jnp.ndarray, grid: tuple[int, int, int],
                            niter: int, mask: jnp.ndarray | None = None,
                            c: jnp.ndarray | None = None,
                            sz: int | None = None,
                            grid_order: str | None = None,
                            interpret: bool | None = None,
                            precision=None) -> CGResult:
    """Fixed-iteration CG, whole iteration in two Pallas kernels (v2).

    Args:
      b:     (E, n, n, n) assembled, masked right-hand side; elements
             z-major over ``grid``.
      D:     (n, n) derivative matrix.
      g:     (E, 6, n, n, n) metric (off-diagonals must be zero — the
             axis-aligned box), or pre-packed (E, 3, n, n, n) diagonal.
      grid:  element grid (EX, EY, EZ).
      niter: iteration count.
      mask/c: optional — the kernels rebuild both from per-axis factors;
             when passed (concrete) they are validated against the
             structural fields and otherwise unused.
      sz, grid_order: slab split and the slab kernel's grid iteration
             order (default: kernels/autotune.slab_config).
      interpret: force Pallas interpret mode (default: off-TPU detection).
      precision: policy name / policy / ``None`` (infer from ``b.dtype``):
             b and the metric are cast to the storage dtype, both kernels
             accumulate in the accum dtype (DESIGN.md §7).

    Returns a :class:`repro.core.cg.CGResult` whose ``rnorm_history``
    matches ``cg_fixed_iters`` to round-off (of the storage dtype).
    """
    from repro.kernels import ops as kernel_ops

    with _trace.span("driver.prepare"):
        policy = resolve_policy(precision, b.dtype)
        b = jnp.asarray(b, policy.storage_dtype)
        E = b.shape[0]
        n = b.shape[-1]
        grid = tuple(grid)
        if interpret is None:
            interpret = kernel_ops.default_interpret()
        sz, grid_order = _autotune.slab_config(
            "slab", grid, n, b.dtype, acc_dtype=policy.accum, sz=sz,
            grid_order=grid_order)

        _check_box_fields(grid, n, mask, c)
        (mx, my, mz), (cx, cy, cz) = kernel_ops.slab_axis_factors(
            grid, n, b.dtype)
        # operator data (D, metric) in the policy's op-storage dtype:
        # refined policies keep it wide — rounding A itself floors the
        # refinement.
        D = jnp.asarray(D, policy.op_storage_dtype)
        g3 = kernel_ops.diag_metric(
            jnp.asarray(g, policy.op_storage_dtype), E, n)
    return SolveResult.from_cg(
        _cg_fused_v2(b, D, D.T, g3, mx, my, mz, cx, cy, cz, n=n,
                     grid=grid, niter=niter, sz=sz, interpret=interpret,
                     acc_name=policy.accum,
                     x_name=policy.x_storage_dtype.name,
                     grid_order=grid_order),
        pipeline="fused_v2")


# ---------------------------------------------------------------------------
# sharded: the fused pipeline per shard inside shard_map
# ---------------------------------------------------------------------------

def cg_fused_sharded_fixed_iters(b: jnp.ndarray, *, D: jnp.ndarray,
                                 g: jnp.ndarray, mask: jnp.ndarray,
                                 c: jnp.ndarray,
                                 grid_local: tuple[int, int, int],
                                 axis_names, niter: int,
                                 block_e: int | None = None,
                                 interpret: bool | None = None,
                                 precision=None) -> CGResult:
    """Fused-iteration CG with elements sharded along z, for ``shard_map``.

    Per shard and iteration: the fused operator+pap kernel on the local
    element block, ``ds_sum_sharded`` (core/gs.py) for the assembly — its
    ``halo_exchange_z`` ppermutes the cross-shard interface planes — and the
    XLA vector pass.  The two inner products are global: per-block kernel
    partials are summed locally, then ``psum``-reduced over ``axis_names``,
    so every shard sees identical ``alpha``/``beta`` and the iteration is
    SPMD-uniform.

    Args are the shard-local blocks (``b``: (E_local, n, n, n) etc.);
    ``grid_local`` is the local element grid (EX, EY, EZ_local).  The rtz
    carry matches :func:`cg_fused_fixed_iters`, as does the ``precision``
    policy treatment (storage-dtype shards, accum-dtype scalars — the psum
    partials travel in the accum dtype, so cross-shard reductions never
    round to storage).
    """
    from repro.kernels import ops as kernel_ops

    policy = resolve_policy(precision, b.dtype)
    b = jnp.asarray(b, policy.storage_dtype)
    E = b.shape[0]
    n = b.shape[-1]
    axis_names = tuple(axis_names)
    if interpret is None:
        interpret = kernel_ops.default_interpret()
    if block_e is None:
        block_e = _autotune.pick_block_e(E, n, b.dtype,
                                         acc_dtype=policy.accum)
    while E % block_e:
        block_e //= 2
    block_e = max(block_e, 1)

    n3 = n ** 3
    D = jnp.asarray(D, policy.op_storage_dtype)
    Dt = D.T
    # the loop runs in the kernels' (n, n^2, E_local) layout
    g6 = _ax.metric_lanes(
        jnp.asarray(g, policy.op_storage_dtype).reshape(E, 6, n3), n)
    mask_l = _ax.to_lanes(jnp.asarray(mask, b.dtype).reshape(E, n3), n)
    acc = policy.accum_dtype
    x_dtype = policy.x_storage_dtype
    c_acc = _ax.to_lanes(jnp.asarray(c, b.dtype).reshape(E, n3),
                         n).astype(acc)
    bl = _ax.to_lanes(b.reshape(E, n3), n)

    def gsum(v):
        return jax.lax.psum(v, axis_names)

    rtz0 = gsum(jnp.sum(bl.astype(acc) * c_acc * bl.astype(acc)))

    def body(k, state):
        x, r, p, rtz, hist = state
        hist = hist.at[k].set(jnp.sqrt(jnp.abs(rtz)))
        w, pap_b = _ax.nekbone_ax_pap_pallas(
            p, D, Dt, g6, mask_l, n=n, block_e=block_e, interpret=interpret,
            acc_dtype=policy.accum)
        pap = gsum(jnp.sum(pap_b))
        w = gs_mod.ds_sum_sharded(w, grid_local, axis_names, lanes=True)
        alpha = rtz / pap
        x = (x.astype(acc) + alpha * p.astype(acc)).astype(x_dtype)
        r = (r.astype(acc) - alpha * w.astype(acc)).astype(b.dtype)
        rtz_new = gsum(jnp.sum(r.astype(acc) * c_acc * r.astype(acc)))
        beta = rtz_new / rtz
        p = (r.astype(acc) + beta * p.astype(acc)).astype(b.dtype)
        return x, r, p, rtz_new, hist

    x = jnp.zeros(bl.shape, x_dtype)
    hist0 = jnp.full((niter + 1,), jnp.nan, dtype=acc)
    state = (x, bl, bl, rtz0, hist0)
    x, r, p, rtz_last, hist = jax.lax.fori_loop(0, niter, body, state)
    hist = hist.at[niter].set(jnp.sqrt(jnp.abs(rtz_last)))
    return SolveResult.from_cg(
        CGResult(x=_ax.from_lanes(x, n).reshape(b.shape),
                 iters=jnp.asarray(niter), rnorm=hist[niter],
                 rnorm_history=hist),
        pipeline="fused_v1_sharded")


# ---------------------------------------------------------------------------
# iterative refinement: low-precision fused inner solves, high-precision
# residuals (DESIGN.md §7)
# ---------------------------------------------------------------------------

def cg_ir_fixed_iters(b: jnp.ndarray, *, D: jnp.ndarray, g: jnp.ndarray,
                      grid: tuple[int, int, int], niter: int = 100,
                      precision="bf16_ir", outer_iters: int | None = None,
                      inner_iters: int | None = None,
                      mask: jnp.ndarray | None = None,
                      c: jnp.ndarray | None = None, variant: str = "v2",
                      sz: int | None = None, block_e: int | None = None,
                      s: int = 4,
                      interpret: bool | None = None) -> CGResult:
    """Mixed-precision CG: fused low-precision inner solves wrapped in an
    iterative-refinement outer loop (DESIGN.md §7).

    Low-precision storage stalls plain CG at the storage dtype's round-off
    floor (bf16: ~4e-3 relative).  This driver recovers the high-precision
    floor while keeping every *inner* iteration at the policy's
    bf16/f32-priced streams:

        r_k = b - A x_k                    (caller precision — ``b.dtype``)
        e_k ≈ solve(A e = r_k / s_k)       (fused pipeline, storage dtype,
                                            ``inner_iters`` iterations)
        x_{k+1} = x_k + s_k e_k            (caller precision)

    with ``s_k = max|r_k|`` so each scaled inner problem spends the narrow
    mantissa on the digits that are still wrong — per sweep the residual
    drops by what an ``inner_iters``-iteration CG achieves, floored near
    storage eps, and the floors *compound* across sweeps.  The outer
    residual/axpy pass costs ~14 caller-precision streams amortized over
    ``inner_iters`` fused iterations (``cost.ir_overhead_streams``).

    Each sweep is a *restart* — it discards the Krylov space — so the
    inner solves must run long enough to get past the residual-norm
    transient (CG minimizes the A-norm of the error; on stiff SEM cases
    the residual norm first *rises* for tens of iterations).  The default
    therefore runs full-length sweeps: ``inner_iters = niter`` per sweep,
    a few sweeps (bf16 stalls ~1e-2 relative per sweep on the paper case,
    so 3 sweeps pass fp64's 100-iteration floor; see
    tests/test_precision.py).

    Args:
      b:       (E, n, n, n) assembled, masked right-hand side, in the
               precision the refined residuals should reach (f64 under
               ``JAX_ENABLE_X64`` — the oracle; f32 on TPU).
      D, g, grid: as :func:`cg_fused_v2_fixed_iters` (the packed
               ``(E, 3, n, n, n)`` diagonal only with ``variant="v2"``).
      niter:   inner iterations per refinement sweep (the paper's fixed-
               iteration protocol runs 100).
      precision: refinement policy (default ``bf16_ir``); the policy's
               storage dtype prices the inner iterations.
      outer_iters: refinement sweeps (default: 3 for sub-f32 storage,
               2 otherwise).
      inner_iters: override the per-sweep inner count (default ``niter``).
      mask/c:  optional structural fields; rebuilt from the box's per-axis
               factors when omitted (the v2 inner solves then have no
               field to check).
      variant: inner pipeline — ``"v2"`` (two slab kernels), ``"v1"``, or
               ``"sstep"`` (the v3 s-step matrix-powers pipeline,
               core/cg_sstep.py — its f64 Gram recurrence composes with
               refinement unchanged: the basis streams at the policy's
               storage width, the outer residuals stay in ``b.dtype``).
      sz / block_e / s / interpret: forwarded to the inner pipeline.

    Returns a :class:`repro.core.cg.CGResult`: ``x`` in ``b.dtype``,
    ``rnorm_history`` holding the ``outer_iters + 1`` *outer* weighted
    residual norms (``sqrt(r·c·r)`` in ``b.dtype`` — directly comparable to
    ``cg_fixed_iters``'s history), ``iters`` the total inner count.
    """
    from repro.core.ax import ax_local_fused

    policy = resolve_policy(precision, b.dtype)
    hi = b.dtype
    grid = tuple(grid)
    n = b.shape[-1]
    if outer_iters is None:
        # bf16 sweeps contract fast early (rhs rounding + the bf16
        # r-recursion drift dominate, ~1e-1..1e-2 each) then slow to the
        # restarted-Krylov tail rate; five compound past the fp64
        # 100-iteration floor on the paper's E=1024/n=10 case.  f32
        # sweeps stall ~1e-6: two reach the f64 round-off region.
        outer_iters = 5 if policy.storage_dtype.itemsize < 4 else 2
    if inner_iters is None:
        inner_iters = niter

    mask_in, c_in = mask, c
    if mask is None or c is None:
        (mxf, myf, mzf), (cxf, cyf, czf) = box_axis_factors(grid, n)
        if mask is None:
            mask = box_outer(mzf, myf, mxf).reshape(b.shape)
        if c is None:
            c = box_outer(czf, cyf, cxf).reshape(b.shape)
    mask_hi = jnp.asarray(mask, hi)
    c_hi = jnp.asarray(c, hi)
    D_hi = jnp.asarray(D, hi)
    g_hi = jnp.asarray(g, hi)

    @jax.jit
    def refresh(x):
        """High-precision residual and its weighted norm (one ax_full)."""
        w = gs_mod.ds_sum_local(ax_local_fused(x, D_hi, g_hi), grid)
        r = b - w * mask_hi
        return r, jnp.sqrt(jnp.abs(jnp.sum(r * c_hi * r)))

    theta = None
    if variant == "sstep":
        from repro.core.cg_sstep import estimate_theta

        # theta depends only on (D, g, grid, mask) — estimate once here,
        # not once per refinement sweep inside cg_sstep_fixed_iters.
        theta = estimate_theta(D_hi, g_hi, grid, mask_hi)

    def inner(r_scaled):
        if variant == "sstep":
            from repro.core.cg_sstep import cg_sstep_fixed_iters

            return cg_sstep_fixed_iters(
                r_scaled, D=D, g=g, grid=grid, niter=inner_iters, s=s,
                mask=mask, c=c, sz=sz, theta=theta, interpret=interpret,
                precision=policy)
        if variant == "v2":
            # forward the caller's mask/c so the v2 path *validates* them
            # against the structural box fields — the outer refresh uses
            # them, and a silent mismatch would refine toward a different
            # operator than the inner pipeline solves.
            return cg_fused_v2_fixed_iters(
                r_scaled, D=D, g=g, grid=grid, niter=inner_iters,
                mask=mask_in, c=c_in, sz=sz, interpret=interpret,
                precision=policy)
        return cg_fused_fixed_iters(
            r_scaled, D=D, g=g, mask=mask, c=c, grid=grid,
            niter=inner_iters, block_e=block_e, interpret=interpret,
            precision=policy)

    x = jnp.zeros_like(b)
    r = b
    norms = [jnp.sqrt(jnp.abs(jnp.sum(b * c_hi * b)))]
    # tracing: a timed "ir.sweep" span per refinement when on.
    from repro.obs import trace as _trace

    for sweep in range(outer_iters):
        with _trace.span("ir.sweep", sweep=sweep, variant=variant,
                         inner_iters=inner_iters):
            # inf-norm scaling: the downcast spends the narrow mantissa
            # on the digits that are still wrong, not on the
            # already-converged scale.
            scale = jnp.max(jnp.abs(r))
            scale = jnp.where(scale > 0, scale, jnp.ones((), hi))
            e = inner((r / scale).astype(hi)).x
            x = x + scale * e.astype(hi)
            r, rn = refresh(x)
            norms.append(rn)
    hist = jnp.stack(norms)
    return SolveResult.from_cg(
        CGResult(x=x, iters=jnp.asarray(outer_iters * inner_iters),
                 rnorm=hist[-1], rnorm_history=hist),
        pipeline="ir")
