"""Sharded Jacobi / Chebyshev PCG on the s-step halo machinery.

The two fused PCG pipelines (core/precond.py, DESIGN.md §9) distribute
over the same 1-D z-slab mesh as the sharded s-step driver
(:mod:`repro.distributed.sstep`), with per-iteration communication:

* **Jacobi** — the v2 slab front-half and the merged PCG update are
  shard-local; the cross-shard interface is exactly the inter-*block*
  plane stitch the single-device driver already performs, so the shard
  boundary costs one plane exchange (2 ``ppermute``\\ s) per iteration:
  the previous shard's top plane becomes the first block's ``addb``, the
  next shard's bottom plane the last block's ``addt``, and the global
  domain ends keep the zero planes (``gs.halo_exchange_z`` delivers
  zeros there).  Two stacked psums carry the scalars (``pap``;
  ``rtz``/``rcr`` ride one psum together).

* **Chebyshev** — ``z = q_k(A) r`` is the v3 matrix-powers structure, so
  its k-deep halo is the *same window logic* as s-step's s-deep one: the
  shard exchanges k ghost slabs of the residual (one
  ``halo_exchange_z``), feeds them to
  :func:`repro.kernels.nekbone_ax.sstep_extend_field` as the
  ``below``/``above`` padding, and the apply kernel runs unchanged on
  the local grid.  The loop-invariant metric/mask windows are built once
  on the global field and sharded by block, as in the s-step driver.

Both cores run their ``lax.while_loop`` inside ``shard_map``: the
stopping rule tests the psum'd ``rtz``, which is replicated, so the loop
is SPMD-uniform.  The fixed-iteration entry point reuses the tol core
with the ``tol2 = -1`` sentinel — the tol-driven trajectory is a prefix
of the fixed-iteration one *by construction*, exactly the single-device
contract (core/precond.py), and both match the single-device
trajectories to fp64 round-off (the psums reassociate partial sums;
everything else, including the exchanged planes, is bitwise).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

import repro.core.gs as gs_mod
from repro.core.cg import CGResult
from repro.core.cg_fused import _check_box_fields
from repro.core.geom import box_outer
from repro.core.precision import resolve_policy
from repro.core.precond import (ChebyshevPrecond, JacobiPrecond,
                                _resolve_precond)
from repro.distributed.sharding import replicate, shard_leading
from repro.distributed.sstep import _resolve_mesh
from repro.kernels import autotune as _autotune
from repro.kernels import nekbone_ax as _ax

__all__ = ["pcg_sharded_fixed_iters", "pcg_sharded_tol"]


# ---------------------------------------------------------------------------
# shard bodies: whole while_loop per shard, psum'd scalars keep it uniform
# ---------------------------------------------------------------------------

def _stitch_planes(bot, top, axis_name: str):
    """Cross-shard edition of the v2 plane stitch: block ``i`` adds block
    ``i-1``'s top plane and block ``i+1``'s bottom plane; at shard edges
    those blocks live on the neighbour shard, so their planes arrive by
    ppermute (zeros at the global ends).  Returns ``(addb, addt)``."""
    fb, fa = gs_mod.halo_exchange_z(top[-1], bot[0], (axis_name,))
    addb = jnp.concatenate([fb[None], top[:-1]], axis=0)
    addt = jnp.concatenate([bot[1:], fa[None]], axis=0)
    return addb, addt


def _pcg_jacobi_shard(b2, invd2, D, Dt, g3, mx, my, mz, cx, cy, cz, tol2,
                      *, axis_name: str, n: int,
                      grid_local: tuple[int, int, int], sz: int,
                      max_iter: int, interpret: bool, acc_name: str,
                      x_name: str):
    """Sharded mirror of ``precond._pcg_jacobi`` (runs inside shard_map).

    Per iteration: 1 plane exchange (2 ppermutes) + 2 psums (pap;
    stacked rtz/rcr).
    """
    E = b2.shape[-1]
    acc = jnp.dtype(acc_name)
    x_dtype = jnp.dtype(x_name)
    c2 = _ax.to_lanes(box_outer(cz, cy, cx).reshape(E, n ** 3),
                      n).astype(acc)
    b_acc = b2.astype(acc)
    z0 = (invd2.astype(acc) * b_acc).astype(b2.dtype)
    s0 = jax.lax.psum(
        jnp.stack([jnp.sum(b_acc * c2 * z0.astype(acc)),
                   jnp.sum(b_acc * c2 * b_acc)]), axis_name)
    rtz0, rcr0 = s0[0], s0[1]
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
            n=n, grid=grid_local, sz=sz, interpret=interpret,
            acc_dtype=acc_name)
        alpha = rtz / jax.lax.psum(jnp.sum(pap_b), axis_name)
        addb, addt = _stitch_planes(bot, top, axis_name)
        x2, z2, rtz_b, rcr_b = _ax.nekbone_pcg_update_pallas(
            x2, p2, z2, w2, addb, addt, alpha.reshape(1, 1), invd2,
            cx, cy, cz, n=n, grid=grid_local, sz=sz, interpret=interpret,
            acc_dtype=acc_name)
        ss = jax.lax.psum(jnp.stack([jnp.sum(rtz_b), jnp.sum(rcr_b)]),
                          axis_name)
        rtz_new = ss[0]
        beta = rtz_new / rtz
        hist = hist.at[kk + 1].set(jnp.sqrt(jnp.abs(ss[1])))
        return x2, z2, p2, rtz_new, beta, hist, kk + 1

    state = (jnp.zeros(b2.shape, x_dtype), z0, jnp.zeros_like(z0), rtz0,
             jnp.zeros((), acc), hist0, jnp.asarray(0))
    x2, z2, p2, rtz, beta, hist, kk = jax.lax.while_loop(cond, body, state)
    return x2, kk, hist


def _pcg_cheb_shard(b2, D, Dt, g3, mx, my, mz, cx, cy, cz, gext, mzext,
                    coef, tol2, *, axis_name: str, n: int,
                    grid_local: tuple[int, int, int], sz: int, sz_c: int,
                    k: int, max_iter: int, interpret: bool, acc_name: str,
                    x_name: str):
    """Sharded mirror of ``precond._pcg_cheb`` (runs inside shard_map).

    The Chebyshev apply exchanges a k-deep residual ghost halo and feeds
    it to ``sstep_extend_field`` — identical window logic to the s-step
    cycle, at k instead of s.  Per iteration: 2 halo exchanges (planes +
    cheb ghosts, 4 ppermutes) + 2 psums (pap; stacked rtz/rcr).
    """
    ex, ey, ez_l = grid_local
    eyex = ey * ex
    E = b2.shape[-1]
    acc = jnp.dtype(acc_name)
    x_dtype = jnp.dtype(x_name)
    c2 = _ax.to_lanes(box_outer(cz, cy, cx).reshape(E, n ** 3),
                      n).astype(acc)
    rcr0_loc = jnp.sum(b2.astype(acc) * c2 * b2.astype(acc))

    def cheb(r2):
        rb, ra = gs_mod.halo_exchange_z(r2[..., (ez_l - k) * eyex:],
                                        r2[..., :k * eyex], (axis_name,))
        rext = _ax.sstep_extend_field(r2, grid_local, sz_c, k,
                                      below=rb, above=ra)
        z2, rtz_b = _ax.nekbone_cheb_apply_pallas(
            rext, D, Dt, gext, mx, my, mzext, cx, cy, cz, coef,
            n=n, grid=grid_local, sz=sz_c, k=k, interpret=interpret,
            acc_dtype=acc_name)
        return z2, jnp.sum(rtz_b)

    z0, rtz0_loc = cheb(b2)
    s0 = jax.lax.psum(jnp.stack([rtz0_loc, rcr0_loc]), axis_name)
    rtz0 = s0[0]
    hist0 = jnp.full((max_iter + 1,), jnp.nan, dtype=acc) \
        .at[0].set(jnp.sqrt(jnp.abs(s0[1])))
    tol2 = jnp.asarray(tol2, acc)

    def cond(state):
        _, _, _, _, rtz, _, _, kk = state
        return jnp.logical_and(kk < max_iter, jnp.abs(rtz) > tol2)

    def body(state):
        x2, r2, z2, p2, rtz, rtz_prev, hist, kk = state
        beta = rtz / rtz_prev            # rtz_prev = 1 at k=0: p0 = 0
        p2, w2, bot, top, pap_b = _ax.nekbone_ax_slab_pallas(
            p2, z2, D, Dt, g3, mx, my, mz, beta.reshape(1, 1),
            n=n, grid=grid_local, sz=sz, interpret=interpret,
            acc_dtype=acc_name)
        alpha = rtz / jax.lax.psum(jnp.sum(pap_b), axis_name)
        addb, addt = _stitch_planes(bot, top, axis_name)
        x2, r2, rcr_b = _ax.nekbone_cg_update_pallas(
            x2, p2, r2, w2, addb, addt, alpha.reshape(1, 1), cx, cy, cz,
            n=n, grid=grid_local, sz=sz, interpret=interpret,
            acc_dtype=acc_name)
        z2, rtz_loc = cheb(r2)
        ss = jax.lax.psum(jnp.stack([rtz_loc, jnp.sum(rcr_b)]), axis_name)
        hist = hist.at[kk + 1].set(jnp.sqrt(jnp.abs(ss[1])))
        return x2, r2, z2, p2, ss[0], rtz, hist, kk + 1

    state = (jnp.zeros(b2.shape, x_dtype), b2, z0, jnp.zeros_like(b2),
             rtz0, jnp.ones((), acc), hist0, jnp.asarray(0))
    x2, r2, z2, p2, rtz, rtz_prev, hist, kk = jax.lax.while_loop(
        cond, body, state)
    return x2, kk, hist


# ---------------------------------------------------------------------------
# jitted shard_map wrappers
# ---------------------------------------------------------------------------

_JAC_STATICS = ("mesh", "axis_name", "n", "grid_local", "sz", "max_iter",
                "interpret", "acc_name", "x_name")


@functools.partial(jax.jit, static_argnames=_JAC_STATICS)
def _jacobi_call(b2, invd2, D, Dt, g3, mx, my, mz, cx, cy, cz, tol2, *,
                 mesh, axis_name, n, grid_local, sz, max_iter, interpret,
                 acc_name, x_name):
    ax = axis_name
    body = functools.partial(
        _pcg_jacobi_shard, axis_name=ax, n=n, grid_local=grid_local, sz=sz,
        max_iter=max_iter, interpret=interpret, acc_name=acc_name,
        x_name=x_name)
    field, metric = P(None, None, ax), P(None, None, None, ax)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(field, field, P(), P(), metric, P(), P(), P(ax), P(), P(),
                  P(ax), P()),
        out_specs=(field, P(), P()),
        check_vma=False)(b2, invd2, D, Dt, g3, mx, my, mz, cx, cy, cz, tol2)


_CHEB_STATICS = _JAC_STATICS + ("sz_c", "k")


@functools.partial(jax.jit, static_argnames=_CHEB_STATICS)
def _cheb_call(b2, D, Dt, g3, mx, my, mz, cx, cy, cz, gext, mzext, coef,
               tol2, *, mesh, axis_name, n, grid_local, sz, sz_c, k,
               max_iter, interpret, acc_name, x_name):
    ax = axis_name
    body = functools.partial(
        _pcg_cheb_shard, axis_name=ax, n=n, grid_local=grid_local, sz=sz,
        sz_c=sz_c, k=k, max_iter=max_iter, interpret=interpret,
        acc_name=acc_name, x_name=x_name)
    field, metric = P(None, None, ax), P(None, None, None, ax)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(field, P(), P(), metric, P(), P(), P(ax), P(), P(), P(ax),
                  metric, P(ax), P(), P()),
        out_specs=(field, P(), P()),
        check_vma=False)(b2, D, Dt, g3, mx, my, mz, cx, cy, cz, gext,
                         mzext, coef, tol2)


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

def _run(b, precond, tol2, max_iter, *, D, g, grid, mask, c, sz, cheb_sz,
         interpret, precision, mesh, axis_name, ndev) -> CGResult:
    from repro.kernels import ops as kernel_ops

    policy = resolve_policy(precision, b.dtype)
    b = jnp.asarray(b, policy.storage_dtype)
    E = b.shape[0]
    n = b.shape[-1]
    grid = tuple(grid)
    ex, ey, ez = grid
    mesh, axis_name, ndev = _resolve_mesh(mesh, axis_name, ndev)
    if ez % ndev:
        raise ValueError(f"EZ {ez} not divisible by mesh size {ndev}")
    ez_l = ez // ndev
    grid_local = (ex, ey, ez_l)
    if interpret is None:
        interpret = kernel_ops.default_interpret()
    # specs built by name use the caller's full-precision operator data on
    # the default device — a one-time setup, as in the single-device path.
    precond = _resolve_precond(precond, D=D, g=g, grid=grid, mask=mask, c=c)
    if precond is None:
        raise ValueError(
            "sharded PCG needs a preconditioner; for unpreconditioned "
            "sharded solves use distributed.sstep or cg_fused_sharded")
    sz, _ = _autotune.slab_config(
        "slab", grid_local, n, b.dtype, acc_dtype=policy.accum,
        precond="jacobi" if isinstance(precond, JacobiPrecond) else None,
        sz=sz, grid_order="parallel")
    if ez_l % sz:
        raise ValueError(f"local EZ {ez_l} not divisible by sz {sz}")

    _check_box_fields(grid, n, mask, c)
    (mx, my, mz), (cx, cy, cz) = kernel_ops.slab_axis_factors(grid, n,
                                                              b.dtype)
    n3 = n ** 3
    D_op = jnp.asarray(D, policy.op_storage_dtype)
    g3 = kernel_ops.diag_metric(jnp.asarray(g, policy.op_storage_dtype),
                                E, n)

    shard = functools.partial(shard_leading, mesh=mesh, axis_name=axis_name)
    rep = functools.partial(replicate, mesh=mesh)

    def lanes(f, g=_ax.to_lanes):
        """Element-sharded natural operand -> kernel layout, sharded on
        its element (last) axis; built in place on each device."""
        ndim = len(jax.eval_shape(lambda a: g(a, n), f).shape)
        spec = P(*(None,) * (ndim - 1), axis_name)
        return jax.jit(lambda a: g(a, n),
                       out_shardings=NamedSharding(mesh, spec))(shard(f))

    statics = dict(mesh=mesh, axis_name=axis_name, n=n,
                   grid_local=grid_local, sz=sz, max_iter=max_iter,
                   interpret=interpret, acc_name=policy.accum,
                   x_name=policy.x_storage_dtype.name)
    b2 = lanes(b.reshape(E, n3))
    tol2 = jnp.asarray(tol2, policy.accum_dtype)
    g3l = lanes(g3, _ax.metric_lanes)
    common = (rep(D_op), rep(D_op.T), g3l, rep(mx), rep(my),
              shard(mz), rep(cx), rep(cy), shard(cz))

    # tracing: the sharded solve is one jitted program — the host
    # boundary is this dispatch, recorded as a single span.
    from repro.obs import trace as _trace

    if isinstance(precond, JacobiPrecond):
        invd2 = lanes(jnp.asarray(precond.invdiag,
                                  policy.op_storage_dtype).reshape(E, n3))
        with _trace.span("pcg.sharded_dispatch", precond="jacobi",
                         ndev=ndev):
            x2, kk, hist = _jacobi_call(b2, invd2, *common, tol2,
                                        **statics)
    elif isinstance(precond, ChebyshevPrecond):
        k = int(precond.k)
        if k > ez_l:
            raise ValueError(
                f"Chebyshev halo k={k} exceeds local slab count {ez_l}")
        sz_c, _ = _autotune.slab_config(
            "cheb", grid_local, n, b.dtype, acc_dtype=policy.accum,
            depth=k, sz=cheb_sz, grid_order="parallel")
        if ez_l % sz_c:
            raise ValueError(f"local EZ {ez_l} not divisible by "
                             f"cheb sz {sz_c}")
        # loop-invariant operator windows on the GLOBAL field, sharded by
        # block — only the residual ghosts cross the network per apply.
        gext = jax.jit(
            lambda g: _ax.sstep_extend_field(g, grid, sz_c, k),
            out_shardings=NamedSharding(
                mesh, P(None, None, None, axis_name)))(g3l)
        mzext = shard(_ax.sstep_extend_zfactor(mz, sz_c, k))
        coef = rep(jnp.asarray(precond.scalars(), policy.accum_dtype))
        with _trace.span("pcg.sharded_dispatch", precond=f"cheb{k}",
                         ndev=ndev):
            x2, kk, hist = _cheb_call(b2, *common, gext, mzext, coef,
                                      tol2, sz_c=sz_c, k=k, **statics)
    else:
        raise TypeError(f"unsupported preconditioner {precond!r}")
    x = _ax.from_lanes(jnp.asarray(np.asarray(x2)), n).reshape(b.shape)
    return CGResult(x=x, iters=kk, rnorm=hist[kk], rnorm_history=hist)


def pcg_sharded_fixed_iters(b: jnp.ndarray, *, D: jnp.ndarray,
                            g: jnp.ndarray, grid: tuple[int, int, int],
                            niter: int, precond,
                            mask: jnp.ndarray | None = None,
                            c: jnp.ndarray | None = None,
                            sz: int | None = None,
                            cheb_sz: int | None = None,
                            interpret: bool | None = None, precision=None,
                            mesh=None, axis_name: str = "z",
                            ndev: int | None = None) -> CGResult:
    """Fixed-iteration sharded PCG (Jacobi or Chebyshev), z-slab mesh.

    Drop-in for :func:`repro.core.precond.pcg_fused_v2_fixed_iters` on
    global arrays (same trajectory to fp64 round-off); ``mesh`` /
    ``axis_name`` / ``ndev`` as in
    :func:`repro.distributed.sstep.cg_sstep_sharded_fixed_iters`.  Runs
    the tol core with the ``tol2 = -1`` sentinel, so the tol-driven
    trajectory (:func:`pcg_sharded_tol`) is a prefix of this one.
    """
    return _run(b, precond, -1.0, niter, D=D, g=g, grid=grid, mask=mask,
                c=c, sz=sz, cheb_sz=cheb_sz, interpret=interpret,
                precision=precision, mesh=mesh, axis_name=axis_name,
                ndev=ndev)


def pcg_sharded_tol(b: jnp.ndarray, *, D: jnp.ndarray, g: jnp.ndarray,
                    grid: tuple[int, int, int], precond, tol: float = 1e-8,
                    max_iter: int = 100,
                    mask: jnp.ndarray | None = None,
                    c: jnp.ndarray | None = None, sz: int | None = None,
                    cheb_sz: int | None = None,
                    interpret: bool | None = None, precision=None,
                    mesh=None, axis_name: str = "z",
                    ndev: int | None = None) -> CGResult:
    """Tolerance-driven sharded PCG: stop when ``|rtz| <= tol**2``.

    The sharded sibling of :func:`repro.core.precond.cg_fused_tol`
    (preconditioned variants): same stopping rule, checked before each
    iteration on the psum'd (replicated) ``rtz``, so every shard exits
    together.  History is NaN-padded to ``max_iter + 1``.
    """
    return _run(b, precond, float(tol) ** 2, max_iter, D=D, g=g, grid=grid,
                mask=mask, c=c, sz=sz, cheb_sz=cheb_sz, interpret=interpret,
                precision=precision, mesh=mesh, axis_name=axis_name,
                ndev=ndev)
