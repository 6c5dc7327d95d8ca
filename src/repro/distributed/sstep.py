"""Distributed s-step CG: the v3 matrix-powers pipeline sharded along z.

Single-device s-step CG (core/cg_sstep.py, DESIGN.md §8) amortizes *memory*
traffic over s iterations; this module amortizes the *network* the same way
(DESIGN.md §10).  Elements are sharded along z over a 1-D device mesh —
z-major element ordering makes the leading axis a stack of contiguous
z-slabs, so a ``PartitionSpec("z")`` on axis 0 is exactly a z-slab
decomposition — and one s-step cycle performs precisely two collectives:

1. **one s-deep ghost-slab halo exchange** — the matrix-powers kernel needs
   ``halo = s`` slabs beyond each block, so shard-boundary blocks need the
   neighbour shard's s edge slabs of both p and r.  Both fields' slabs are
   stacked into a single buffer and exchanged with one
   :func:`repro.core.gs.halo_exchange_z` call (= 2 ``ppermute``\\ s, one per
   direction) per cycle, replacing the per-iteration neighbour traffic of a
   distributed v1/v2 pipeline: s iterations of operator applications ride
   on one exchange.
2. **one Gram psum** — each shard reduces its blocks' ``(2s+1)^2`` Gram
   partials locally; a single ``jax.lax.psum`` assembles the global
   ``G = V^T C V``.

Everything else is local: the f64 recurrence runs replicated on host (one
device->host sync per cycle, as in the single-device driver — the psum'd G
is identical on every shard so the host coefficients are too), and the
multi-axpy update kernel is collective-free (its ``r·c·r`` partials return
per-shard and are summed on host, keeping the cycle at exactly one psum).

**Overlap schedule** (the ring idiom of :mod:`repro.distributed.overlap`,
applied to halos instead of all-gathers): a shard's *interior* blocks —
all but ``nb = ceil(s/sz)`` blocks per side — build their halo windows
from shard-local slabs only, so their matrix-powers ``pallas_call`` has no
data dependence on the ``ppermute``\\ s.  The cycle issues the exchange,
runs the interior powers call, then runs the boundary blocks' powers call
on the arrived ghosts: XLA's latency-hiding scheduler can overlap the
halo transfer with the interior compute, the collective-matmul trick with
the roles of compute and communication unchanged.

Windows of *loop-invariant* operator data (the metric diagonal ``gext``
and the z mask factor ``mzext``) are built once per solve on the **global**
field — block ``i``'s window is the same slabs whether the padding came
from a neighbour shard or from the same device — and device_put sharded by
block, so only p and r ever cross the network.

Correctness: the sharded trajectory equals the single-device one to fp64
round-off (the Gram psum and the host rcr sum reassociate f64 partial
sums; everything else is bitwise), verified per s in
``tests/distributed_checks.py`` and gated by the collective-count test
(:func:`cycle_collective_counts`).
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
from repro.core.cg_sstep import cycle_coefficients, estimate_theta
from repro.core.geom import box_axis_factors, box_outer
from repro.core.precision import resolve_policy
from repro.distributed.sharding import replicate, shard_leading, solver_mesh
from repro.kernels import autotune as _autotune
from repro.kernels import nekbone_ax as _ax

__all__ = ["cg_sstep_sharded_fixed_iters", "cycle_collective_counts",
           "cycle_traceables", "exchange_ghost_slabs", "count_collectives"]


# ---------------------------------------------------------------------------
# halo exchange
# ---------------------------------------------------------------------------

def exchange_ghost_slabs(f: jnp.ndarray, ez_local: int, halo: int,
                         axis_names) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exchange ``halo`` ghost z-slabs of a shard-local field.

    To be called *inside* ``shard_map``.  ``f`` is a kernel-layout field
    ``(..., E_local)`` with the z-major elements on the last axis.  Returns
    ``(below, above)`` — the neighbour shards' ``halo`` edge slabs,
    ``(..., halo*EY*EX)`` each, zeros at the global domain ends (which is
    exactly the padding :func:`repro.kernels.nekbone_ax.sstep_extend_field`
    wants there).  Costs one ``ppermute`` per direction.
    """
    if not (0 < halo <= ez_local):
        raise ValueError(f"halo {halo} out of range for ez_local {ez_local}")
    slab = f.shape[-1] // ez_local
    return gs_mod.halo_exchange_z(f[..., (ez_local - halo) * slab:],
                                  f[..., :halo * slab], axis_names)


# ---------------------------------------------------------------------------
# the sharded cycle: one exchange, interior/boundary powers, one Gram psum
# ---------------------------------------------------------------------------

def _cycle_shard(p2, r2, D, Dt, gextl, mzextl, mx, my, cx, cy, czl,
                 inv_theta, *, axis_name: str, n: int,
                 grid_local: tuple[int, int, int], sz: int, s: int,
                 interpret: bool, acc_name: str | None):
    """Shard body of one matrix-powers cycle (runs inside ``shard_map``).

    Exactly 2 ppermutes (the stacked p/r ghost-slab exchange) and 1 psum
    (the Gram block) — the invariant the collective-count test pins.
    """
    ex, ey, ez_l = grid_local
    eyex = ey * ex
    nblk = ez_l // sz
    L = sz + 2 * s
    Lee = L * eyex
    block_e = sz * eyex
    lead = p2.shape[:-1]                           # (n, n^2)
    p = p2.reshape(lead + (ez_l, eyex))
    r = r2.reshape(lead + (ez_l, eyex))

    # -- the one halo exchange of the cycle: p and r edge slabs stacked
    # into a single buffer so both fields (x both directions) ride on one
    # halo_exchange_z call = 2 ppermutes.
    from_below, from_above = exchange_ghost_slabs(
        jnp.stack([p2, r2]), ez_l, s, (axis_name,))
    pb, rb = from_below[0], from_below[1]          # (n, n^2, s*eyex) each
    pa, ra = from_above[0], from_above[1]

    def powers(pext, rext, gext, mzext, cz, nblocks):
        return _ax.nekbone_ax_powers_pallas(
            pext, rext, D, Dt, gext, mx, my, mzext, cx, cy, cz, inv_theta,
            n=n, grid=(ex, ey, nblocks * sz), sz=sz, s=s,
            interpret=interpret, acc_dtype=acc_name)

    nb = -(-s // sz)              # boundary blocks per side (windows need ghosts)
    if 2 * nb >= nblk:
        # shard too thin for an interior: single powers call on all blocks
        pext = _ax.sstep_extend_field(p2, grid_local, sz, s,
                                      below=pb, above=pa)
        rext = _ax.sstep_extend_field(r2, grid_local, sz, s,
                                      below=rb, above=ra)
        basis, gram_b = powers(pext, rext, gextl, mzextl, czl, nblk)
        gram_loc = jnp.sum(gram_b, axis=0)
    else:
        # -- overlap schedule: interior windows touch no ghost data, so the
        # interior powers call is independent of the ppermutes above and
        # XLA can run it while the boundary halo is in flight (the ring-
        # overlap idiom of distributed/overlap.py, halo edition).
        ii = np.arange(nb, nblk - nb)
        idx = (ii[:, None] * sz - s + np.arange(L)[None, :]).ravel()

        def windows(f, idx, nw):
            return jnp.take(f, idx, axis=-2).reshape(lead + (nw * Lee,))

        basis_i, gram_i = powers(
            windows(p, idx, len(ii)), windows(r, idx, len(ii)),
            gextl[..., nb * Lee:(nblk - nb) * Lee], mzextl[nb:nblk - nb],
            czl[nb * sz:(nblk - nb) * sz], len(ii))

        # -- boundary blocks: windows over [ghosts-below | local | ghosts-
        # above]; in padded coordinates block i's window starts at i*sz.
        ghost = lead + (s, eyex)
        fp = jnp.concatenate([pb.reshape(ghost), p, pa.reshape(ghost)],
                             axis=-2)
        fr = jnp.concatenate([rb.reshape(ghost), r, ra.reshape(ghost)],
                             axis=-2)
        ib = np.concatenate([np.arange(nb), np.arange(nblk - nb, nblk)])
        idxb = (ib[:, None] * sz + np.arange(L)[None, :]).ravel()
        gbnd = jnp.concatenate([gextl[..., :nb * Lee],
                                gextl[..., (nblk - nb) * Lee:]], axis=-1)
        mzbnd = jnp.concatenate([mzextl[:nb], mzextl[nblk - nb:]], axis=0)
        czbnd = jnp.concatenate([czl[:nb * sz], czl[(nblk - nb) * sz:]],
                                axis=0)
        basis_b, gram_bb = powers(windows(fp, idxb, 2 * nb),
                                  windows(fr, idxb, 2 * nb), gbnd, mzbnd,
                                  czbnd, 2 * nb)

        half = nb * block_e
        basis = jnp.concatenate(
            [basis_b[..., :half], basis_i, basis_b[..., half:]], axis=-1)
        gram_loc = jnp.sum(gram_i, axis=0) + jnp.sum(gram_bb, axis=0)

    G = jax.lax.psum(gram_loc, axis_name)          # the one Gram psum
    return basis, G


def _cycle_mapped(mesh, axis_name: str, n: int,
                  grid_local: tuple[int, int, int], sz: int, s: int,
                  interpret: bool, acc_name: str | None):
    """shard_map-wrapped cycle on global operands (un-jitted; shared by the
    driver's jit below and the collective-count tracer)."""
    ax = axis_name
    body = functools.partial(
        _cycle_shard, axis_name=ax, n=n, grid_local=grid_local, sz=sz, s=s,
        interpret=interpret, acc_name=acc_name)
    field, metric = P(None, None, ax), P(None, None, None, ax)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(field, field, P(), P(), metric, P(ax), P(), P(), P(), P(),
                  P(ax), P()),
        out_specs=(metric, P()),
        check_vma=False)                      # pallas_call has no VMA rule


def _update_mapped(mesh, axis_name: str, n: int,
                   grid_local: tuple[int, int, int], sz: int, s: int,
                   interpret: bool, acc_name: str | None):
    """shard_map-wrapped multi-axpy update: collective-free; the per-block
    rcr partials come back sharded and are summed on host."""
    ax = axis_name

    def body(x2, p2, r2, basis, coef, cx, cy, czl):
        return _ax.nekbone_sstep_update_pallas(
            x2, p2, r2, basis, coef, cx, cy, czl, n=n, grid=grid_local,
            sz=sz, s=s, interpret=interpret, acc_dtype=acc_name)

    field, basis = P(None, None, ax), P(None, None, None, ax)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(field, field, field, basis, P(), P(), P(), P(ax)),
        out_specs=(field, field, field, P(ax)),
        check_vma=False)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "axis_name", "n", "grid_local", "sz", "s", "interpret",
    "acc_name"))
def _cycle_call(p2, r2, D, Dt, gext, mzext, mx, my, cx, cy, cz, inv_theta,
                *, mesh, axis_name, n, grid_local, sz, s, interpret,
                acc_name):
    return _cycle_mapped(mesh, axis_name, n, grid_local, sz, s, interpret,
                         acc_name)(p2, r2, D, Dt, gext, mzext, mx, my, cx,
                                   cy, cz, inv_theta)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "axis_name", "n", "grid_local", "sz", "s", "interpret",
    "acc_name"))
def _update_call(x2, p2, r2, basis, coef, cx, cy, cz, *, mesh, axis_name,
                 n, grid_local, sz, s, interpret, acc_name):
    return _update_mapped(mesh, axis_name, n, grid_local, sz, s, interpret,
                          acc_name)(x2, p2, r2, basis, coef, cx, cy, cz)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _resolve_mesh(mesh, axis_name: str, ndev: int | None):
    if mesh is None:
        mesh = solver_mesh(ndev, axis_name=axis_name)
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"sharded solvers want a 1-D mesh, got axes {mesh.axis_names}")
    return mesh, mesh.axis_names[0], int(np.prod(mesh.devices.shape))


def cg_sstep_sharded_fixed_iters(
        b: jnp.ndarray, *, D: jnp.ndarray, g: jnp.ndarray,
        grid: tuple[int, int, int], niter: int, s: int = 4,
        mask: jnp.ndarray | None = None, c: jnp.ndarray | None = None,
        sz: int | None = None, theta: float | None = None,
        tol: float | None = None, interpret: bool | None = None,
        precision=None, mesh=None, axis_name: str = "z",
        ndev: int | None = None) -> CGResult:
    """Sharded s-step CG: z-slab decomposition over a 1-D mesh.

    Drop-in for :func:`repro.core.cg_sstep.cg_sstep_fixed_iters` (global
    arrays in, :class:`CGResult` out; trajectory equal to fp64 round-off)
    with the per-cycle communication contract of DESIGN.md §10: one s-deep
    ghost-slab halo exchange and one Gram psum per cycle, nothing else.

    Extra args over the single-device driver:
      mesh:      explicit 1-D device mesh (default:
                 :func:`repro.distributed.sharding.solver_mesh`).
      axis_name: mesh axis carrying the z slabs (default ``"z"``).
      ndev:      device count when building the default mesh (default: all).

    Constraints: ``EZ % ndev == 0``, ``EZ_local % sz == 0`` and
    ``s <= EZ_local`` (ghost slabs come from the adjacent shard only — a
    deeper halo would need multi-hop exchange, out of scope).
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
    mesh, axis_name, ndev = _resolve_mesh(mesh, axis_name, ndev)
    if ez % ndev:
        raise ValueError(f"EZ {ez} not divisible by mesh size {ndev}")
    ez_l = ez // ndev
    grid_local = (ex, ey, ez_l)
    if interpret is None:
        interpret = kernel_ops.default_interpret()
    sz, _ = _autotune.slab_config("sstep", grid_local, n, b.dtype,
                                  acc_dtype=policy.accum, depth=s, sz=sz,
                                  grid_order="parallel")
    if ez_l % sz:
        raise ValueError(f"local EZ {ez_l} not divisible by sz {sz}")
    if s > ez_l:
        raise ValueError(
            f"halo depth s={s} exceeds local slab count {ez_l} "
            f"(single-neighbour exchange)")

    _check_box_fields(grid, n, mask, c)
    (mx, my, mz), (cx, cy, cz) = kernel_ops.slab_axis_factors(grid, n,
                                                              b.dtype)
    n3 = n ** 3
    acc = policy.accum_dtype
    x_dtype = policy.x_storage_dtype
    D_op = jnp.asarray(D, policy.op_storage_dtype)
    g3 = kernel_ops.diag_metric(jnp.asarray(g, policy.op_storage_dtype),
                                E, n)
    shard = functools.partial(shard_leading, mesh=mesh, axis_name=axis_name)
    rep = functools.partial(replicate, mesh=mesh)
    by_elem = NamedSharding(mesh, P(axis_name))
    lanes = NamedSharding(mesh, P(None, None, axis_name))
    # loop-invariant halo windows of the GLOBAL field, in the kernels'
    # layout: block i's window holds the same slabs whether its halo
    # padding was gathered locally or exchanged from a neighbour, so these
    # shard by block with no per-cycle traffic.  They are built from the
    # sharded metric straight into their shards (nothing is assembled on
    # one device); only p and r cross the network per cycle.
    gext = jax.jit(
        lambda g: _ax.sstep_extend_field(_ax.metric_lanes(g, n), grid, sz,
                                         s),
        out_shardings=NamedSharding(mesh, P(None, None, None, axis_name)))(
            shard(g3))
    mzext = shard(_ax.sstep_extend_zfactor(mz, sz, s))
    if theta is None:
        if mask is None:
            mask = box_outer(
                *reversed(box_axis_factors(grid, n)[0])).reshape(b.shape)
        theta = estimate_theta(jnp.asarray(D, b.dtype),
                               jnp.asarray(g, b.dtype), grid,
                               jnp.asarray(mask, b.dtype))
    inv_theta = jnp.full((1, 1), 1.0 / theta, acc)

    # the cycles run in the kernels' (n, n^2, E) layout, sharded on E
    r2 = p2 = jax.jit(lambda f: _ax.to_lanes(f, n), out_shardings=lanes)(
        shard(b.reshape(E, n3)))
    x2 = jnp.zeros(r2.shape, x_dtype, device=lanes)
    cz = shard(cz)
    D_op, mx, my, cx, cy, inv_theta = (
        rep(D_op), rep(mx), rep(my), rep(cx), rep(cy), rep(inv_theta))
    Dt_op = rep(D_op.T)
    statics = dict(mesh=mesh, axis_name=axis_name, n=n,
                   grid_local=grid_local, sz=sz, s=s, interpret=interpret,
                   acc_name=policy.accum)

    tol2 = None if tol is None else float(tol) ** 2
    hist: list[float] = []
    rcr_parts = None
    rcr_last = None
    it = 0
    from repro.obs import trace as _trace

    while it < niter:
        if rcr_parts is not None:
            # the update kernel's rcr partials come back per-shard (no
            # device collective — the psum budget stays at 1/cycle); the
            # global reduction is this host f64 sum.
            rcr_last = float(np.asarray(rcr_parts, np.float64).sum())
            if tol2 is not None and abs(rcr_last) <= tol2:
                break
        m = min(s, niter - it)
        with _trace.span("sstep.sharded_cycle", it=it, s=s, ndev=ndev):
            basis, G = _cycle_call(p2, r2, D_op, Dt_op, gext, mzext, mx,
                                   my, cx, cy, cz, inv_theta, **statics)
            Gh = np.asarray(G, np.dtype(policy.gram))
            coef_np, rtzs, m = cycle_coefficients(Gh, s, m, theta, tol2)
            if m == 0:
                break
            hist.extend(np.sqrt(np.abs(v)) for v in rtzs)
            coef = rep(jnp.asarray(coef_np, acc))
            x2, r2, p2, rcr_parts = _update_call(x2, p2, r2, basis, coef,
                                                 cx, cy, cz, **statics)
        it += m
        if tol2 is not None and m < s:
            break
    if rcr_parts is not None:
        rcr_last = float(np.asarray(rcr_parts, np.float64).sum())
    if rcr_last is None:                  # niter == 0 (or tol met at start)
        c2 = box_outer(np.asarray(cz, np.float64), np.asarray(cy, np.float64),
                       np.asarray(cx, np.float64)).reshape(E, n3)
        r_h = np.asarray(_ax.from_lanes(r2, n), np.float64)
        rcr_last = float(np.sum(r_h * c2 * r_h))
    hist.append(float(np.sqrt(abs(rcr_last))))
    hist_arr = jnp.asarray(np.asarray(hist, np.float64), acc)
    x = jax.jit(lambda x: _ax.from_lanes(x, n).reshape(b.shape),
                out_shardings=by_elem)(x2)
    return CGResult(x=x, iters=jnp.asarray(it), rnorm=hist_arr[-1],
                    rnorm_history=hist_arr)


# ---------------------------------------------------------------------------
# collective accounting: trace a cycle, count the primitives
# ---------------------------------------------------------------------------

_COLLECTIVES = ("ppermute", "psum", "all_gather", "all_to_all")


def _walk_jaxpr(jaxpr, counts: dict):
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        for key in _COLLECTIVES:
            if key in name:
                counts[key] = counts.get(key, 0) + 1
        for v in eqn.params.values():
            _walk_param(v, counts)


def _walk_param(v, counts: dict):
    # duck-typed recursion: ClosedJaxpr has .jaxpr, Jaxpr has .eqns; sub-
    # jaxprs hide under different param keys across jax versions.
    if hasattr(v, "eqns"):
        _walk_jaxpr(v, counts)
    elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
        _walk_jaxpr(v.jaxpr, counts)
    elif isinstance(v, (tuple, list)):
        for x in v:
            _walk_param(x, counts)


def count_collectives(fn, *args) -> dict:
    """Counts of collective primitives in ``jax.make_jaxpr(fn)(*args)``.

    Keys: ``ppermute``, ``psum``, ``all_gather``, ``all_to_all`` (absent
    when zero).  ``args`` may be ``jax.ShapeDtypeStruct``\\ s.
    """
    closed = jax.make_jaxpr(fn)(*args)
    counts: dict = {}
    _walk_jaxpr(closed.jaxpr, counts)
    return counts


def cycle_traceables(*, grid: tuple[int, int, int], n: int,
                     s: int = 4, sz: int = 1, mesh=None,
                     axis_name: str = "z", ndev: int | None = None,
                     interpret: bool = True, precision=None):
    """The sharded cycle/update launches as traceable (fn, arg-spec) pairs.

    Returns ``((cycle_fn, cycle_args), (update_fn, update_args))`` with
    ``jax.ShapeDtypeStruct`` arg specs shaped exactly as the sharded
    driver's per-cycle operands.  Tracing needs no committed arrays, so
    this works at any ``ndev`` including 1 — the surface behind both
    :func:`cycle_collective_counts` (the §10 contract test) and the
    :mod:`repro.obs.drift` collective checks.
    """
    policy = resolve_policy(precision, jnp.float32)
    mesh, axis_name, ndev = _resolve_mesh(mesh, axis_name, ndev)
    ex, ey, ez = grid
    if ez % ndev:
        raise ValueError(f"EZ {ez} not divisible by mesh size {ndev}")
    ez_l = ez // ndev
    grid_local = (ex, ey, ez_l)
    if ez_l % sz or s > ez_l:
        raise ValueError((grid, ndev, sz, s))
    E = ex * ey * ez
    L = sz + 2 * s
    Lee = L * ey * ex
    nblk = ez // sz
    K = 2 * s + 1
    st = policy.storage_dtype
    op = policy.op_storage_dtype
    acc = policy.accum_dtype
    S = jax.ShapeDtypeStruct
    lanes = (n, n * n, E)
    field = S(lanes, st)
    cycle_args = (field, field, S((n, n), op), S((n, n), op),
                  S((3, n, n * n, nblk * Lee), op), S((nblk, L, n), st),
                  S((ex, n), st), S((ey, n), st), S((ex, n), st),
                  S((ey, n), st), S((ez, n), st), S((1, 1), acc))
    update_args = (S(lanes, policy.x_storage_dtype), field, field,
                   S((2 * s - 1,) + lanes, st), S((3, K), acc),
                   S((ex, n), st), S((ey, n), st), S((ez, n), st))
    cyc = _cycle_mapped(mesh, axis_name, n, grid_local, sz, s, interpret,
                        policy.accum)
    upd = _update_mapped(mesh, axis_name, n, grid_local, sz, s, interpret,
                         policy.accum)
    return (cyc, cycle_args), (upd, update_args)


def cycle_collective_counts(*, grid: tuple[int, int, int], n: int,
                            s: int = 4, sz: int = 1, mesh=None,
                            axis_name: str = "z", ndev: int | None = None,
                            interpret: bool = True,
                            precision=None) -> dict:
    """Collective counts of one sharded s-step cycle + update (traced).

    Returns ``{"cycle": {...}, "update": {...}}``.  The DESIGN.md §10
    contract — asserted by the acceptance test — is
    ``cycle == {"ppermute": 2, "psum": 1}`` (one stacked p/r halo exchange,
    one Gram reduction) and ``update == {}`` (collective-free).
    """
    (cyc, cycle_args), (upd, update_args) = cycle_traceables(
        grid=grid, n=n, s=s, sz=sz, mesh=mesh, axis_name=axis_name,
        ndev=ndev, interpret=interpret, precision=precision)
    return {"cycle": count_collectives(cyc, *cycle_args),
            "update": count_collectives(upd, *update_args)}
