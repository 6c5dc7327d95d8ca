"""Jitted public wrappers around the Pallas kernels.

Each wrapper:
  * accepts natural shapes and reshapes/pads to the kernel's HBM layout,
  * picks ``interpret=True`` off-TPU, so the CPU test suite runs the kernel
    code in the Pallas interpreter, and ``interpret=False`` on a TPU
    backend, where Mosaic compiles every kernel (``chip_smoke.py`` runs
    them on a chip, ``tests/test_tpu_compile.py`` compiles them for a
    described v5e); a kernel Mosaic refuses raises there, it never falls
    back,
  * exposes the tuning knobs (block sizes) with roofline-reasoned defaults.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import autotune as _autotune
from repro.kernels import flash_attn as _flash
from repro.kernels import nekbone_ax as _ax
from repro.kernels import wkv6 as _wkv6

__all__ = ["nekbone_ax", "nekbone_ax_dots", "nekbone_ax_dots_slab",
           "nekbone_ax_dots_slab_block", "nekbone_cg_update",
           "nekbone_cg_update_block", "nekbone_ax_powers",
           "nekbone_sstep_update", "nekbone_pcg_update",
           "nekbone_cheb_precond", "nekbone_interp", "slab_axis_factors",
           "diag_metric", "flash_attention", "wkv6", "default_interpret"]


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _lanes(f: jnp.ndarray) -> jnp.ndarray:
    """``(..., E, n, n, n)`` natural field -> ``(..., n, n^2, E)``."""
    n = f.shape[-1]
    return _ax.to_lanes(f.reshape(f.shape[:-3] + (n ** 3,)), n)


def _natural(f: jnp.ndarray, shape) -> jnp.ndarray:
    """Inverse of :func:`_lanes`, to the natural ``shape``."""
    return _ax.from_lanes(f, shape[-1]).reshape(shape)


def _plane_lanes(planes, lead: tuple, nblk: int, n: int,
                 slab: int) -> jnp.ndarray:
    """``(..., nblk, EY*EX*n^2)`` planes ordered ``(y, x, j, i)`` (zeros
    when ``None``) -> the kernels' ``(..., nblk, n^2, EY*EX)``."""
    if planes is None:
        return jnp.zeros(lead + (nblk, n * n, slab))
    p = jnp.asarray(planes).reshape(lead + (nblk, slab, n * n))
    return jnp.swapaxes(p, -1, -2)


def _pick_block_e(E: int, n: int,
                  vmem_budget_bytes: int = _autotune.VMEM_LIMIT_BYTES) -> int:
    """Back-compat alias for the VMEM heuristic (see kernels/autotune.py).

    Default ``block_e`` selection now goes through the cached
    :func:`repro.kernels.autotune.pick_block_e`, which measures candidates
    on real TPUs; this name is kept for callers of the static heuristic.
    """
    return _autotune.vmem_block_e(E, n, vmem_budget_bytes)


@functools.partial(jax.jit,
                   static_argnames=("block_e", "interpret"))
def _nekbone_ax_impl(u, D, Dt, g, block_e, interpret):
    E = u.shape[0]
    n = u.shape[-1]
    w = _ax.nekbone_ax_pallas(
        _lanes(u), D, Dt, _ax.metric_lanes(g.reshape(E, 6, n ** 3), n),
        n=n, block_e=block_e, interpret=interpret)
    return _natural(w, u.shape)


def nekbone_ax(u: jnp.ndarray, D: jnp.ndarray, g: jnp.ndarray, *,
               block_e: int | None = None,
               interpret: bool | None = None) -> jnp.ndarray:
    """Fused local Poisson operator  w = D^T (G (D u)).

    Args:
      u: (E, n, n, n) nodal values, layout [e, k, j, i].
      D: (n, n) derivative matrix (dxm1).
      g: (E, 6, n, n, n) metric fields (rr, rs, rt, ss, st, tt).
      block_e: elements per VMEM block (default: autotuned to ~8 MiB).
      interpret: force Pallas interpret mode (defaults to off-TPU detection).

    Elements are zero-padded to a multiple of ``block_e`` if needed.
    """
    E = u.shape[0]
    n = u.shape[-1]
    interpret = default_interpret() if interpret is None else interpret
    block_e = block_e or _autotune.pick_block_e(E, n, u.dtype)
    pad = (-E) % block_e
    if pad:
        u = jnp.concatenate([u, jnp.zeros((pad,) + u.shape[1:], u.dtype)])
        g = jnp.concatenate([g, jnp.zeros((pad,) + g.shape[1:], g.dtype)])
    w = _nekbone_ax_impl(u, D, jnp.asarray(D).T, g, block_e, interpret)
    return w[:E] if pad else w


def nekbone_ax_dots(p: jnp.ndarray, D: jnp.ndarray, g: jnp.ndarray,
                    mask: jnp.ndarray, r: jnp.ndarray, c: jnp.ndarray, *,
                    block_e: int | None = None,
                    interpret: bool | None = None):
    """Fused CG-iteration kernel: masked local Ax + the two inner products.

    Args:
      p, r: (E, n, n, n) search direction / residual (p continuous).
      D: (n, n); g: (E, 6, n, n, n); mask, c: (E, n, n, n).

    Returns ``(w, pap, rcz)``: the *masked local* operator output (still to
    be assembled with gs — mask and gs commute) and the tree-reduced scalars
    ``pap == p·c·(mask gs w)`` and ``rcz == r·c·r``.  Zero-padded blocks
    contribute zero to both partials, so arbitrary E is safe.
    """
    E = p.shape[0]
    n = p.shape[-1]
    interpret = default_interpret() if interpret is None else interpret
    block_e = block_e or _autotune.pick_block_e(E, n, p.dtype)
    pad = (-E) % block_e
    if pad:
        def zpad(x):
            return jnp.concatenate(
                [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])

        p, g, mask, r, c = map(zpad, (p, g, mask, r, c))
    Ep = p.shape[0]
    w, pap_b, rcz_b = _ax.nekbone_ax_dots_pallas(
        _lanes(p), jnp.asarray(D), jnp.asarray(D).T,
        _ax.metric_lanes(g.reshape(Ep, 6, n ** 3), n), _lanes(mask),
        _lanes(r), _lanes(c), n=n, block_e=block_e, interpret=interpret)
    w = _natural(w, p.shape)
    return (w[:E] if pad else w), jnp.sum(pap_b), jnp.sum(rcz_b)


def slab_axis_factors(grid: tuple[int, int, int], n: int, dtype):
    """Per-axis mask and c factors of the structured box, as jnp arrays.

    Thin dtype-casting wrapper over :func:`repro.core.geom.box_axis_factors`
    (the single source of the factorization); the factor values (0, 1, 1/2)
    are exact in every supported dtype, so the in-kernel outer products
    reproduce the full fields bitwise.  Made once per shape and dtype and
    shared (jax arrays are immutable): every v2-family solve asks for
    them, and six host-to-device copies per solve are host work no solve
    needs.
    """
    return _slab_axis_factors(tuple(grid), int(n), jnp.dtype(dtype).name,
                              bool(jax.config.jax_enable_x64))


@functools.lru_cache(maxsize=64)
def _slab_axis_factors(grid, n, dtype_name, x64):
    from repro.core.geom import box_axis_factors

    masks, cs = box_axis_factors(grid, n)
    # concrete even when the first call comes from inside a jit trace:
    # a cached tracer would leak into every later call.
    with jax.ensure_compile_time_eval():
        return (tuple(jnp.asarray(m, dtype_name) for m in masks),
                tuple(jnp.asarray(c, dtype_name) for c in cs))


def diag_metric(g: jnp.ndarray, E: int, n: int) -> jnp.ndarray:
    """Pack the metric to its (rr, ss, tt) diagonal, shape (E, 3, n^3).

    Accepts an already-packed (E, 3, ...) metric, or the general 6-component
    one when its off-diagonal entries are (verifiably) zero — true for every
    axis-aligned ``BoxMesh``.  Tracers skip the check (callers under jit
    close over concrete mesh fields, so the check ran at trace time).
    """
    import numpy as np

    from repro.core.geom import GEOM_RR, GEOM_RS, GEOM_RT, GEOM_SS, GEOM_ST, \
        GEOM_TT

    if g.shape[1] == 3:
        return g.reshape(E, 3, n ** 3)
    if g.shape[1] != 6:
        raise ValueError(f"metric must have 3 or 6 components, got {g.shape}")
    try:
        off = np.asarray(g[:, (GEOM_RS, GEOM_RT, GEOM_ST)])
        if off.any():
            raise ValueError(
                "the slab (v2) pipeline requires an axis-aligned (diagonal-"
                "metric) mesh; off-diagonal metric entries are non-zero")
    except jax.errors.TracerArrayConversionError:
        pass
    return g[:, (GEOM_RR, GEOM_SS, GEOM_TT)].reshape(E, 3, n ** 3)


def nekbone_ax_dots_slab(p_prev: jnp.ndarray, r: jnp.ndarray,
                         D: jnp.ndarray, g3: jnp.ndarray,
                         grid: tuple[int, int, int], *, beta: float = 0.0,
                         sz: int | None = None,
                         grid_order: str | None = None,
                         interpret: bool | None = None,
                         acc_dtype: str | None = None):
    """v2 slab dots kernel on natural shapes, with the planes stitched.

    Computes ``p = r + beta * p_prev`` and the *fully assembled* masked
    operator output ``w = mask * gs(D^T G D p)`` — the kernel performs the
    x/y and intra-block z direct-stiffness summation in VMEM, and this
    wrapper adds the cross-block boundary planes host-side (the fused CG
    driver stitches them inside the update kernel instead).

    Args:
      p_prev, r: (E, n, n, n); elements z-major over ``grid``.
      D: (n, n); g3: (E, 3, n, n, n) metric diagonal (rr, ss, tt), or the
         full (E, 6, ...) metric of an axis-aligned box (off-diagonals
         validated zero, then dropped — see :func:`diag_metric`).
      grid: (EX, EY, EZ); beta: direction-update scalar.
      sz, grid_order: slab split and grid iteration order (default:
        :func:`repro.kernels.autotune.slab_config`).
      acc_dtype: explicit in-kernel accumulation dtype (precision policy).

    Returns ``(p, w, pap)`` with ``pap == p·c·(mask gs w_local)`` tree-
    reduced from the per-block partials.
    """
    grid = tuple(grid)
    E = p_prev.shape[0]
    n = p_prev.shape[-1]
    interpret = default_interpret() if interpret is None else interpret
    sz, grid_order = _autotune.slab_config(
        "slab", grid, n, p_prev.dtype, acc_dtype=acc_dtype, sz=sz,
        grid_order=grid_order)
    (mx, my, mz), _ = slab_axis_factors(grid, n, p_prev.dtype)
    D = jnp.asarray(D, p_prev.dtype)
    g3 = diag_metric(jnp.asarray(g3, p_prev.dtype), E, n)
    acc = _ax._accum(p_prev.dtype, acc_dtype)
    beta_arr = jnp.full((1, 1), beta, acc)
    p2, w2, bot, top, pap_b = _ax.nekbone_ax_slab_pallas(
        _lanes(p_prev), _lanes(r), D, D.T, _ax.metric_lanes(g3, n),
        mx, my, mz, beta_arr, n=n, grid=grid, sz=sz, interpret=interpret,
        acc_dtype=acc_dtype, grid_order=grid_order)
    w2 = _ax.stitch_planes(w2, bot, top, grid, sz)
    return (_natural(p2, p_prev.shape), _natural(w2, p_prev.shape),
            jnp.sum(pap_b))


def nekbone_ax_powers(p: jnp.ndarray, r: jnp.ndarray, D: jnp.ndarray,
                      g3: jnp.ndarray, grid: tuple[int, int, int], *,
                      s: int, theta: float = 1.0, sz: int | None = None,
                      grid_order: str | None = None,
                      interpret: bool | None = None,
                      acc_dtype: str | None = None):
    """v3 matrix-powers kernel on natural shapes (DESIGN.md §8).

    Builds the halo windows (``halo = s`` slabs, zero-padded past the
    domain) and evaluates the scaled Krylov basis of one s-step cycle —
    ``A' = (mask gs ax_local) / theta`` chained s times from ``p`` and
    s-1 times from ``r`` — plus the (2s+1)^2 Gram block of
    ``V = [p, A'p.., r, A'r..]`` under the weight ``c``.

    Args:
      p, r: (E, n, n, n), z-major over ``grid``; both continuous+masked.
      D: (n, n); g3: diagonal (E, 3, ...) or verifiably-diagonal 6-component
         metric; theta: basis scale (``A' = A/theta``).
      s: powers per cycle (>= 1).
      sz, grid_order: slab split and grid iteration order (default:
        :func:`repro.kernels.autotune.slab_config`, kind ``"sstep"``).

    Returns ``(basis, gram)``: basis ``(E, 2s-1, n, n, n)`` holding
    ``[A'p..A'^s p, A'r..A'^{s-1} r]`` and the summed ``(2s+1, 2s+1)``
    Gram matrix in the accumulation dtype.
    """
    grid = tuple(grid)
    E = p.shape[0]
    n = p.shape[-1]
    interpret = default_interpret() if interpret is None else interpret
    sz, grid_order = _autotune.slab_config(
        "sstep", grid, n, p.dtype, acc_dtype=acc_dtype, depth=s, sz=sz,
        grid_order=grid_order)
    (mx, my, mz), (cx, cy, cz) = slab_axis_factors(grid, n, p.dtype)
    D = jnp.asarray(D, p.dtype)
    g3 = _ax.metric_lanes(diag_metric(jnp.asarray(g3, p.dtype), E, n), n)
    acc = _ax._accum(p.dtype, acc_dtype)
    pext = _ax.sstep_extend_field(_lanes(p), grid, sz, s)
    rext = _ax.sstep_extend_field(_lanes(r), grid, sz, s)
    gext = _ax.sstep_extend_field(g3, grid, sz, s)
    mzext = _ax.sstep_extend_zfactor(mz, sz, s)
    inv_theta = jnp.full((1, 1), 1.0 / theta, acc)
    basis, gram_b = _ax.nekbone_ax_powers_pallas(
        pext, rext, D, D.T, gext, mx, my, mzext, cx, cy, cz, inv_theta,
        n=n, grid=grid, sz=sz, s=s, interpret=interpret, acc_dtype=acc_dtype,
        grid_order=grid_order)
    basis = jnp.moveaxis(_natural(basis, (2 * s - 1,) + p.shape), 0, 1)
    return basis, jnp.sum(gram_b, axis=0)


def nekbone_sstep_update(x: jnp.ndarray, p: jnp.ndarray, r: jnp.ndarray,
                         basis: jnp.ndarray, coef: jnp.ndarray,
                         grid: tuple[int, int, int], *, s: int,
                         sz: int | None = None,
                         interpret: bool | None = None,
                         acc_dtype: str | None = None):
    """v3 multi-axpy s-step update kernel on natural shapes.

    Applies the whole cycle of vector updates from the f64 recurrence
    coefficients: ``x += V e``, ``r = V b``, ``p = V a`` with ``V`` in the
    powers kernel's column order, plus the post-cycle weighted norm
    ``sum(r_new * c * r_new)`` (``c`` rebuilt in-kernel).

    Args:
      x, p, r: (E, n, n, n); basis: (E, 2s-1, n, n, n) from
      :func:`nekbone_ax_powers`; coef: (3, 2s+1) rows (e, b, a).

    Returns ``(x_new, r_new, p_new, rcr)``.
    """
    grid = tuple(grid)
    n = x.shape[-1]
    interpret = default_interpret() if interpret is None else interpret
    sz, _ = _autotune.slab_config("sstep", grid, n, p.dtype,
                                  acc_dtype=acc_dtype, depth=s, sz=sz,
                                  grid_order="parallel")
    _, (cx, cy, cz) = slab_axis_factors(grid, n, x.dtype)
    acc = _ax._accum(x.dtype, acc_dtype)
    x2, r2, p2, rcr_b = _ax.nekbone_sstep_update_pallas(
        _lanes(x), _lanes(p), _lanes(r), _lanes(jnp.moveaxis(basis, 1, 0)),
        jnp.asarray(coef, acc), cx, cy, cz, n=n, grid=grid, sz=sz, s=s,
        interpret=interpret, acc_dtype=acc_dtype)
    return (_natural(x2, x.shape), _natural(r2, x.shape),
            _natural(p2, x.shape), jnp.sum(rcr_b))


def nekbone_cg_update(x: jnp.ndarray, p: jnp.ndarray, r: jnp.ndarray,
                      w: jnp.ndarray, alpha: float,
                      grid: tuple[int, int, int], *,
                      addb: jnp.ndarray | None = None,
                      addt: jnp.ndarray | None = None,
                      sz: int | None = None,
                      interpret: bool | None = None,
                      acc_dtype: str | None = None):
    """Merged CG vector-update kernel on natural shapes.

    Computes ``x + alpha p``, ``r - alpha (w + planes)`` and the weighted
    norm ``sum(r_new * c * r_new)`` of the updated residual, with ``c``
    rebuilt in-kernel from the box's per-axis factors.

    Args:
      x, p, r, w: (E, n, n, n); grid: (EX, EY, EZ); alpha: step scalar.
      addb/addt: optional (EZ//sz, EY*EX*n^2) boundary planes added at each
                 block's bottom/top before the axpy (default zeros).

    Returns ``(x_new, r_new, rtz_new)``.
    """
    ex, ey, ez = grid = tuple(grid)
    n = x.shape[-1]
    interpret = default_interpret() if interpret is None else interpret
    sz, _ = _autotune.slab_config("slab", grid, n, x.dtype,
                                  acc_dtype=acc_dtype, sz=sz,
                                  grid_order="parallel")
    nblk = ez // sz
    _, (cx, cy, cz) = slab_axis_factors(grid, n, x.dtype)
    acc = _ax._accum(x.dtype, acc_dtype)
    alpha_arr = jnp.full((1, 1), alpha, acc)
    x2, r2, rcr_b = _ax.nekbone_cg_update_pallas(
        _lanes(x), _lanes(p), _lanes(r), _lanes(w),
        _plane_lanes(addb, (), nblk, n, ex * ey).astype(x.dtype),
        _plane_lanes(addt, (), nblk, n, ex * ey).astype(x.dtype),
        alpha_arr, cx, cy, cz, n=n, grid=grid, sz=sz, interpret=interpret,
        acc_dtype=acc_dtype)
    return _natural(x2, x.shape), _natural(r2, x.shape), jnp.sum(rcr_b)


def nekbone_ax_dots_slab_block(p_prev: jnp.ndarray, r: jnp.ndarray,
                               D: jnp.ndarray, g3: jnp.ndarray,
                               grid: tuple[int, int, int], *,
                               beta=0.0, sz: int | None = None,
                                     grid_order: str | None = None,
                               interpret: bool | None = None,
                               acc_dtype: str | None = None):
    """Batched v2 slab dots kernel on natural shapes (DESIGN.md §12).

    The multi-RHS sibling of :func:`nekbone_ax_dots_slab`: ``p_prev``/``r``
    carry a leading RHS-batch axis (b, E, n, n, n) and ``beta`` is a scalar
    or length-b vector.  The operator residents (D, metric diagonals, mask
    factors) are loaded once per slab residency and shared across the
    batch; the cross-block boundary planes are stitched host-side here.

    Returns ``(p, w, pap)`` with ``pap`` a length-b vector of per-RHS
    ``p·c·(mask gs w_local)`` partial reductions.
    """
    grid = tuple(grid)
    nrhs, E = p_prev.shape[0], p_prev.shape[1]
    n = p_prev.shape[-1]
    interpret = default_interpret() if interpret is None else interpret
    sz, grid_order = _autotune.slab_config(
        "slab", grid, n, p_prev.dtype, acc_dtype=acc_dtype, nrhs=nrhs,
        sz=sz, grid_order=grid_order)
    (mx, my, mz), _ = slab_axis_factors(grid, n, p_prev.dtype)
    D = jnp.asarray(D, p_prev.dtype)
    g3 = diag_metric(jnp.asarray(g3, p_prev.dtype), E, n)
    acc = _ax._accum(p_prev.dtype, acc_dtype)
    beta_arr = jnp.broadcast_to(jnp.asarray(beta, acc),
                                (nrhs,)).reshape(1, nrhs)
    p3, w3, bot, top, pap_b = _ax.nekbone_ax_slab_block_pallas(
        _lanes(p_prev), _lanes(r), D, D.T, _ax.metric_lanes(g3, n),
        mx, my, mz, beta_arr, n=n, grid=grid, sz=sz,
        interpret=interpret, acc_dtype=acc_dtype, grid_order=grid_order)
    w3 = _ax.stitch_planes(w3, bot, top, grid, sz)
    return (_natural(p3, p_prev.shape), _natural(w3, p_prev.shape),
            jnp.sum(pap_b, axis=0))


def nekbone_cg_update_block(x: jnp.ndarray, p: jnp.ndarray, r: jnp.ndarray,
                            w: jnp.ndarray, alpha,
                            grid: tuple[int, int, int], *,
                            addb: jnp.ndarray | None = None,
                            addt: jnp.ndarray | None = None,
                            sz: int | None = None,
                            interpret: bool | None = None,
                            acc_dtype: str | None = None):
    """Batched merged CG vector-update kernel on natural shapes.

    The multi-RHS sibling of :func:`nekbone_cg_update`: fields carry a
    leading RHS-batch axis (b, E, n, n, n), ``alpha`` is a scalar or
    length-b vector, ``addb``/``addt`` are (b, EZ//sz, EY*EX*n^2).

    Returns ``(x_new, r_new, rtz_new)`` with ``rtz_new`` a length-b
    vector of per-RHS weighted norms of the updated residual.
    """
    ex, ey, ez = grid = tuple(grid)
    nrhs = x.shape[0]
    n = x.shape[-1]
    interpret = default_interpret() if interpret is None else interpret
    sz, _ = _autotune.slab_config("slab", grid, n, x.dtype,
                                  acc_dtype=acc_dtype, nrhs=nrhs, sz=sz,
                                  grid_order="parallel")
    nblk = ez // sz
    _, (cx, cy, cz) = slab_axis_factors(grid, n, x.dtype)
    acc = _ax._accum(x.dtype, acc_dtype)
    alpha_arr = jnp.broadcast_to(jnp.asarray(alpha, acc),
                                 (nrhs,)).reshape(1, nrhs)
    x3, r3, rcr_b = _ax.nekbone_cg_update_block_pallas(
        _lanes(x), _lanes(p), _lanes(r), _lanes(w),
        _plane_lanes(addb, (nrhs,), nblk, n, ex * ey).astype(x.dtype),
        _plane_lanes(addt, (nrhs,), nblk, n, ex * ey).astype(x.dtype),
        alpha_arr, cx, cy, cz, n=n, grid=grid, sz=sz, interpret=interpret,
        acc_dtype=acc_dtype)
    return (_natural(x3, x.shape), _natural(r3, x.shape),
            jnp.sum(rcr_b, axis=0))


def nekbone_pcg_update(x: jnp.ndarray, p: jnp.ndarray, z: jnp.ndarray,
                       w: jnp.ndarray, alpha: float, invdiag: jnp.ndarray,
                       grid: tuple[int, int, int], *,
                       addb: jnp.ndarray | None = None,
                       addt: jnp.ndarray | None = None,
                       sz: int | None = None,
                       interpret: bool | None = None,
                       acc_dtype: str | None = None):
    """Merged Jacobi-PCG vector-update kernel on natural shapes.

    The solver carries ``z = invdiag * r`` (the preconditioned residual,
    DESIGN.md §9.2); this computes ``x + alpha p``,
    ``z - alpha invdiag (w + planes)`` and the two weighted partials of
    the reconstructed residual ``r = z / invdiag``:
    ``rtz = r·c·z`` (the PCG beta numerator) and ``rcr = r·c·r`` (the
    history entry), with ``c`` rebuilt in-kernel.

    Args:
      x, p, z, w: (E, n, n, n); invdiag: (E, n, n, n) assembled 1/diag(A)
      (1 at masked rows); grid/alpha/addb/addt as
      :func:`nekbone_cg_update`.

    Returns ``(x_new, z_new, rtz, rcr)``.
    """
    ex, ey, ez = grid = tuple(grid)
    n = x.shape[-1]
    interpret = default_interpret() if interpret is None else interpret
    sz, _ = _autotune.slab_config("slab", grid, n, x.dtype,
                                  acc_dtype=acc_dtype, precond="jacobi",
                                  sz=sz, grid_order="parallel")
    nblk = ez // sz
    _, (cx, cy, cz) = slab_axis_factors(grid, n, x.dtype)
    acc = _ax._accum(x.dtype, acc_dtype)
    alpha_arr = jnp.full((1, 1), alpha, acc)
    x2, z2, rtz_b, rcr_b = _ax.nekbone_pcg_update_pallas(
        _lanes(x), _lanes(p), _lanes(z), _lanes(w),
        _plane_lanes(addb, (), nblk, n, ex * ey).astype(x.dtype),
        _plane_lanes(addt, (), nblk, n, ex * ey).astype(x.dtype),
        alpha_arr, _lanes(jnp.asarray(invdiag).reshape(x.shape)), cx, cy,
        cz, n=n, grid=grid, sz=sz, interpret=interpret, acc_dtype=acc_dtype)
    return (_natural(x2, x.shape), _natural(z2, x.shape), jnp.sum(rtz_b),
            jnp.sum(rcr_b))


def nekbone_cheb_precond(r: jnp.ndarray, D: jnp.ndarray, g3: jnp.ndarray,
                         coef: jnp.ndarray, grid: tuple[int, int, int], *,
                         k: int, sz: int | None = None,
                         grid_order: str | None = None,
                         interpret: bool | None = None,
                         acc_dtype: str | None = None):
    """Chebyshev preconditioner application on natural shapes.

    Builds the halo windows (``halo = k`` slabs, like
    :func:`nekbone_ax_powers`) and evaluates ``z = q_k(A) r`` — k chained
    masked, assembled operator applications combined by the Chebyshev
    recurrence scalars (DESIGN.md §9.3) — plus the weighted partial
    ``rtz = r·c·z``.

    Args:
      r: (E, n, n, n), continuous + masked, z-major over ``grid``.
      D: (n, n); g3: diagonal (E, 3, ...) or verifiably-diagonal
         6-component metric; coef: (k+1, 2) recurrence scalars
         (:func:`repro.core.precond.cheb_scalars`).
      k: polynomial degree (>= 1).
      sz, grid_order: slab split and grid iteration order (default:
        :func:`repro.kernels.autotune.slab_config`, kind ``"cheb"``).

    Returns ``(z, rtz)``.
    """
    grid = tuple(grid)
    E = r.shape[0]
    n = r.shape[-1]
    interpret = default_interpret() if interpret is None else interpret
    sz, grid_order = _autotune.slab_config(
        "cheb", grid, n, r.dtype, acc_dtype=acc_dtype, depth=k, sz=sz,
        grid_order=grid_order)
    (mx, my, mz), (cx, cy, cz) = slab_axis_factors(grid, n, r.dtype)
    D = jnp.asarray(D, r.dtype)
    g3 = _ax.metric_lanes(diag_metric(jnp.asarray(g3, r.dtype), E, n), n)
    acc = _ax._accum(r.dtype, acc_dtype)
    rext = _ax.sstep_extend_field(_lanes(r), grid, sz, k)
    gext = _ax.sstep_extend_field(g3, grid, sz, k)
    mzext = _ax.sstep_extend_zfactor(mz, sz, k)
    z2, rtz_b = _ax.nekbone_cheb_apply_pallas(
        rext, D, D.T, gext, mx, my, mzext, cx, cy, cz,
        jnp.asarray(coef, acc), n=n, grid=grid, sz=sz, k=k,
        interpret=interpret, acc_dtype=acc_dtype, grid_order=grid_order)
    return _natural(z2, r.shape), jnp.sum(rtz_b)


def nekbone_interp(u: jnp.ndarray, M: jnp.ndarray,
                   grid: tuple[int, int, int], *, sz: int | None = None,
                   interpret: bool | None = None,
                   acc_dtype: str | None = None) -> jnp.ndarray:
    """Tensor-product GLL-to-GLL interpolation on natural shapes.

    Applies ``M`` — ``(n_out, n_in)``, e.g.
    :func:`repro.core.pmg.gll_interp_matrix` — along each local direction
    of ``u`` (E, n_in, n_in, n_in): the p-multigrid transfer operator
    (DESIGN.md §13).  ``M`` itself prolongs when built fine-from-coarse;
    pass ``J.T`` for the matching restriction core.  Element-local, so
    the result is slab-split-invariant (fp64-bitwise across ``sz``).

    Returns (E, n_out, n_out, n_out) in ``u``'s dtype.
    """
    ex, ey, ez = grid = tuple(grid)
    E = u.shape[0]
    nin = u.shape[-1]
    M = jnp.asarray(M, u.dtype)
    nout = M.shape[0]
    assert M.shape == (nout, nin), (M.shape, (nout, nin))
    interpret = default_interpret() if interpret is None else interpret
    sz, _ = _autotune.slab_config("slab", grid, max(nin, nout), u.dtype,
                                  acc_dtype=acc_dtype, precond="pmg:interp",
                                  sz=sz, grid_order="parallel")
    v = _ax.nekbone_interp_pallas(
        _lanes(u), M.T, nin=nin, nout=nout, grid=grid, sz=sz,
        interpret=interpret, acc_dtype=acc_dtype)
    return _natural(v, (E, nout, nout, nout))


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    window: int | None = None, softcap: float | None = None,
                    q_offset: int = 0, block_q: int = 512, block_k: int = 512,
                    interpret: bool | None = None):
    """Block online-softmax attention (prefill hot-spot). See flash_attn.py."""
    interpret = default_interpret() if interpret is None else interpret
    return _flash.flash_attention(
        q, k, v, causal=causal, scale=scale, window=window, softcap=softcap,
        q_offset=q_offset, block_q=block_q, block_k=block_k,
        interpret=interpret)


def wkv6(r, k, v, w, u, *, initial_state=None, return_state: bool = False,
         block_t: int = 16, variant: str = "chunked",
         interpret: bool | None = None):
    """RWKV6 linear-attention recurrence (state streamed through VMEM)."""
    interpret = default_interpret() if interpret is None else interpret
    return _wkv6.wkv6(r, k, v, w, u, initial_state=initial_state,
                      return_state=return_state, block_t=block_t,
                      variant=variant, interpret=interpret)
