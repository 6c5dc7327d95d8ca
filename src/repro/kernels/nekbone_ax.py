"""Pallas-TPU kernels for the Nekbone local Poisson operator (paper §IV-C).

This is the paper's optimized ``Ax`` kernel re-derived for the TPU memory
hierarchy (DESIGN.md §2).  The CUDA version marches an ``n x n`` thread layer
through the element's k-layers; the TPU version keeps a *block of elements*
resident in VMEM and marches the same k-layers, with the elements of the
block on the 128-wide lane axis.

**Kernel layout** (DESIGN.md §2.1).  Inside every kernel a field block is
``(n, n^2, be)``: the k-layer on a major axis, the layer's ``(j, i)`` nodes
on sublanes (n^2 = 100 rows at n = 10, 4 % sublane padding) and ``be``
elements on lanes.  No in-kernel reshape ever touches the lane axis — the
Mosaic compiler refuses to split or merge it — so

* the r/s derivatives are per-layer matmuls with the Kronecker-expanded
  matrices ``kron(I, D)`` / ``kron(D, I)`` (n^2 x n^2, on the MXU),
* the t derivative combines whole layers with ``D``'s scalars (SMEM, VPU),
* the direct-stiffness face sums are lane rolls (``pltpu.roll``) by 1, EX
  and EX*EY elements, with the face rows moved by 0/1 permutation matmuls,
* per-block inner-product partials leave as ``(1, 1, 1)`` vector tiles.

The ``*_pallas`` wrappers take and return fields in this layout in HBM too:
``(..., n, n^2, E)`` fields, ``(C, n, n^2, E)`` metrics, ``(nblk, n^2,
EX*EY)`` boundary planes.  The CG drivers convert the right-hand side and
the operator once per solve (:func:`to_lanes`, :func:`metric_lanes`) and the
solution once at the end (:func:`from_lanes`), so no transpose runs inside
an iteration; ``kernels/ops.py`` converts around single calls.

Mosaic has no float64.  Every wrapper refuses f64 operands unless it runs in
interpret mode, where the fp64 oracle tests exercise the same kernel code.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.autotune import VMEM_LIMIT_BYTES

__all__ = ["GRID_ORDERS", "to_lanes", "from_lanes", "metric_lanes",
           "shift_planes", "stitch_planes",
           "nekbone_ax_kernel", "nekbone_ax_pallas",
           "nekbone_ax_dots_kernel", "nekbone_ax_dots_pallas",
           "nekbone_ax_pap_kernel", "nekbone_ax_pap_pallas",
           "nekbone_ax_slab_kernel", "nekbone_ax_slab_pallas",
           "nekbone_cg_update_kernel", "nekbone_cg_update_pallas",
           "nekbone_ax_slab_block_kernel", "nekbone_ax_slab_block_pallas",
           "nekbone_cg_update_block_kernel",
           "nekbone_cg_update_block_pallas",
           "nekbone_ax_powers_kernel", "nekbone_ax_powers_pallas",
           "nekbone_sstep_update_kernel", "nekbone_sstep_update_pallas",
           "sstep_extend_field", "sstep_extend_zfactor",
           "nekbone_pcg_update_kernel", "nekbone_pcg_update_pallas",
           "nekbone_pcg_update_block_kernel",
           "nekbone_pcg_update_block_pallas",
           "nekbone_cheb_apply_kernel", "nekbone_cheb_apply_pallas",
           "nekbone_interp_kernel", "nekbone_interp_pallas"]

# Grid-iteration-order knob for the slab-family pallas_calls: "parallel"
# declares the (1-D) slab grid embarrassingly parallel, "arbitrary" forces
# sequential issue order.  Swept jointly with sz by autotune.
GRID_ORDERS = ("parallel", "arbitrary")

# In-place contract of the v2 loop kernels (DESIGN.md §3.4): operand index
# -> output index of ``input_output_aliases``, the same at the wrapper's
# arguments and results.  The slab kernels write p over p, the update
# kernels x over x and r (or z) over r (or z), so a CG loop carries its
# fields without a copy.  Each grid step reads and writes only its own
# block of these fields; what crosses blocks leaves through bot/top.
SLAB_ALIASES = {0: 0}
UPDATE_ALIASES = {0: 0, 2: 1}

_HI = jax.lax.Precision.HIGHEST
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _accum(dtype, acc_dtype: str | None) -> jnp.dtype:
    """In-kernel accumulation dtype for a given storage dtype.

    ``acc_dtype`` is the precision policy's explicit choice (DESIGN.md §7);
    ``None`` keeps the historical rule — f64 accumulates in f64 (the
    interpret-mode oracle path), every narrower storage dtype in f32.
    """
    if acc_dtype is not None:
        return jnp.dtype(acc_dtype)
    return jnp.dtype(jnp.float64 if dtype == jnp.float64 else jnp.float32)


def _acc_tag(acc_dtype: str | None) -> str:
    """Kernel-name suffix for an explicit accumulation dtype."""
    return "" if acc_dtype is None else f"_acc{jnp.dtype(acc_dtype).name}"


def _order_tag(grid_order: str = "parallel") -> str:
    """Kernel-name suffix for a non-default grid order."""
    if grid_order not in GRID_ORDERS:
        raise ValueError(f"unknown grid order {grid_order!r}; "
                         f"available: {GRID_ORDERS}")
    return "" if grid_order == "parallel" else f"_go{grid_order}"


def _call(kernel, *, name: str, grid, in_specs, out_specs, out_shape,
          interpret: bool, operands, grid_order: str = "parallel",
          scratch: tuple | list = (), aliases: dict | None = None):
    """``pallas_call`` with the repo-wide VMEM limit and the f64 guard.

    Compiled (non-interpret) calls refuse float64 operands before they
    reach Mosaic, which has no f64 type.  ``aliases`` maps operand index
    to output index (``input_output_aliases``): that output is written
    over that input's buffer (the in-place contract of
    :data:`SLAB_ALIASES` and :data:`UPDATE_ALIASES`).
    """
    if not interpret:
        f64 = [jnp.dtype(x.dtype).name for x in operands
               if jnp.dtype(x.dtype) == jnp.float64]
        f64 += [s.dtype.name for s in jax.tree.leaves(out_shape)
                if jnp.dtype(s.dtype) == jnp.float64]
        if f64:
            raise TypeError(
                f"{name}: float64 is not supported by the TPU kernel "
                "compiler (Mosaic); use an f32 or bf16 storage policy with "
                "f32 accumulation, or interpret mode for the f64 oracle")
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret, name=name,
        scratch_shapes=list(scratch), input_output_aliases=aliases or {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(grid_order,),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(*operands)


# ---------------------------------------------------------------------------
# HBM layout conversion and structural operands (XLA side of the wrappers)
# ---------------------------------------------------------------------------

def to_lanes(f: jnp.ndarray, n: int) -> jnp.ndarray:
    """``(..., E, n^3)`` element-major -> ``(..., n, n^2, E)`` kernel layout."""
    lead, E = f.shape[:-2], f.shape[-2]
    return jnp.swapaxes(f, -1, -2).reshape(lead + (n, n * n, E))


def from_lanes(f: jnp.ndarray, n: int) -> jnp.ndarray:
    """Inverse of :func:`to_lanes`."""
    lead, E = f.shape[:-3], f.shape[-1]
    return jnp.swapaxes(f.reshape(lead + (n ** 3, E)), -1, -2)


def metric_lanes(g: jnp.ndarray, n: int) -> jnp.ndarray:
    """``(..., E, C, n^3)`` metric -> ``(..., C, n, n^2, E)``."""
    return to_lanes(jnp.swapaxes(g, -2, -3), n)


def shift_planes(bot: jnp.ndarray, top: jnp.ndarray):
    """Update-kernel stitch operands from the slab kernel's planes.

    ``bot``/``top`` are ``(..., nblk, n^2, EX*EY)``; returns ``(addb,
    addt)`` with ``addb[i] = top[i-1]`` and ``addt[i] = bot[i+1]``, zeros
    at the domain ends.
    """
    z = jnp.zeros_like(top[..., :1, :, :])
    return (jnp.concatenate([z, top[..., :-1, :, :]], axis=-3),
            jnp.concatenate([bot[..., 1:, :, :], z], axis=-3))


def stitch_planes(w: jnp.ndarray, bot: jnp.ndarray, top: jnp.ndarray,
                  grid: tuple[int, int, int], sz: int) -> jnp.ndarray:
    """Add the cross-block boundary planes to a slab kernel's ``w``.

    ``w`` is ``(..., n, n^2, E)`` (in-block assembled), ``bot``/``top`` the
    kernel's ``(..., nblk, n^2, EX*EY)`` planes; returns the fully
    assembled field (the update kernels do this stitch in VMEM instead).
    """
    ex, ey, ez = grid
    slab, nblk = ex * ey, ez // sz
    if nblk == 1:
        return w
    lead, n = w.shape[:-3], w.shape[-3]
    v = w.reshape(lead + (n, n * n, nblk, sz * slab))
    below = jnp.moveaxis(top[..., :-1, :, :], -3, -2)
    above = jnp.moveaxis(bot[..., 1:, :, :], -3, -2)
    v = v.at[..., 0, :, 1:, :slab].add(below)
    v = v.at[..., n - 1, :, :-1, (sz - 1) * slab:].add(above)
    return v.reshape(w.shape)


def _kron_ops(M: jnp.ndarray, f) -> jnp.ndarray:
    """``[kron(I, M), kron(M, I)]`` — the in-plane (r, s) contractions of
    one k-layer with ``(j, i)`` rows, as a ``(2, n^2, n^2)`` stack."""
    M = jnp.asarray(M, f)
    eye = jnp.eye(M.shape[0], dtype=f)
    return jnp.stack([jnp.kron(eye, M), jnp.kron(M, eye)])


def _face_perms(n: int, f) -> jnp.ndarray:
    """0/1 row moves of the x and y faces of a ``(j, i)`` layer.

    ``[0]``: row (j, n-1) <- (j, 0);  ``[1]``: (j, 0) <- (j, n-1);
    ``[2]``: row (n-1, i) <- (0, i);  ``[3]``: (0, i) <- (n-1, i).
    """
    P = np.zeros((4, n * n, n * n))
    for a in range(n):
        P[0, a * n + n - 1, a * n] = 1
        P[1, a * n, a * n + n - 1] = 1
        P[2, (n - 1) * n + a, a] = 1
        P[3, a, (n - 1) * n + a] = 1
    return jnp.asarray(P, f)


def _lane_flags(ex: int, ey: int, nz: int, f) -> jnp.ndarray:
    """Neighbour flags of the ``nz*ey*ex`` z-major elements of one block.

    Rows: x+1, x-1, y+1, y-1, z+1, z-1 neighbour inside the block (1/0),
    padded to 8 rows.  Identical for every block of whole slabs.
    """
    z, y, x = np.meshgrid(np.arange(nz), np.arange(ey), np.arange(ex),
                          indexing="ij")
    x, y, z = x.ravel(), y.ravel(), z.ravel()
    fl = np.zeros((8, x.size))
    fl[0], fl[1] = x < ex - 1, x > 0
    fl[2], fl[3] = y < ey - 1, y > 0
    fl[4], fl[5] = z < nz - 1, z > 0
    return jnp.asarray(fl, f)


def _plane_factor(fx, fy, lanes: int) -> jnp.ndarray:
    """``fy[y(e), j] * fx[x(e), i]`` as ``(n^2, lanes)`` for z-major lanes."""
    ey, n = fy.shape
    ex = fx.shape[0]
    fxy = (fy[:, None, :, None] * fx[None, :, None, :]).reshape(ey * ex,
                                                                n * n)
    return jnp.tile(fxy.T, (1, lanes // (ex * ey)))


def _z_lanes(fz, slab: int) -> jnp.ndarray:
    """``(..., Z, n)`` per-slab z factor -> ``(..., n, 1, Z*slab)`` lanes."""
    return jnp.repeat(jnp.swapaxes(fz, -1, -2), slab, axis=-1)[..., None, :]


def _part(parts: jnp.ndarray) -> jnp.ndarray:
    """``(nblk, 1, b)`` partial tiles -> ``(nblk, b)``."""
    return parts.reshape(parts.shape[0], parts.shape[-1])


# ---------------------------------------------------------------------------
# in-kernel building blocks (values are lists of n layers of (n^2, lanes))
# ---------------------------------------------------------------------------

def _mm(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Full-precision 2-D matmul in the operand (accumulation) dtype."""
    return jax.lax.dot(a, b, precision=_HI, preferred_element_type=a.dtype)


def _tdir(d_ref, vs, trans: bool):
    """Layer combination ``out[k] = sum_l D[k, l] vs[l]`` (``D[l, k]`` when
    ``trans``) over a static layer list, with ``D``'s scalars from SMEM."""
    nout = d_ref.shape[1] if trans else d_ref.shape[0]
    return [_lincomb(d_ref, lambda l: vs[l], k, len(vs), trans)
            for k in range(nout)]


def _lincomb(d_ref, src, k, n: int, trans: bool):
    """``sum_l D[k, l] src(l)`` (``D[l, k]`` when ``trans``) for one output
    layer ``k`` (static or traced), summed in ``l`` order."""
    acc = None
    for l in range(n):
        t = (d_ref[l, k] if trans else d_ref[k, l]) * src(l)
        acc = t if acc is None else acc + t
    return acc


def _scratch(n: int, lanes: int, f, count: int) -> list:
    """``count`` VMEM scratch buffers of one ``(n, n^2, lanes)`` field."""
    return [pltpu.VMEM((n, n * n, lanes), f) for _ in range(count)]


def _apply(src, dst, kf_ref, kb_ref, d_ref, g, scr, f, *, full=False,
           post=None, carry=0):
    """One operator application ``D^T G D``, marched layer by layer.

    ``src(k)`` loads input layer ``k`` (traced ``k``) in the accumulation
    dtype ``f``, ``g(m, k)`` metric component ``m`` of layer ``k`` (the
    diagonal rr, ss, tt, or all six rr rs rt ss st tt when ``full``).  The
    metric-applied gradient goes through the three ``scr`` scratch fields;
    output layer ``w`` is passed through ``post(k, w, carry) -> (w,
    carry)`` and stored to ``dst[k]``.  Returns the final carry.  Per
    layer: the r/s contractions are Kronecker matmuls, the t contraction
    combines layers with D's SMEM scalars; the sum is r + s + t.
    """
    ur, us, ut = scr
    n = ur.shape[0]

    def fwd(k, c):
        a = src(k)
        wr = _mm(kf_ref[0].astype(f), a)
        ws = _mm(kf_ref[1].astype(f), a)
        wt = _lincomb(d_ref, src, k, n, trans=False)
        if full:
            grr, grs, grt, gss, gst, gtt = (g(m, k) for m in range(6))
            ur[k] = grr * wr + grs * ws + grt * wt
            us[k] = grs * wr + gss * ws + gst * wt
            ut[k] = grt * wr + gst * ws + gtt * wt
        else:
            ur[k] = g(0, k) * wr
            us[k] = g(1, k) * ws
            ut[k] = g(2, k) * wt
        return c

    def bwd(k, c):
        w = (_mm(kb_ref[0].astype(f), ur[k]) + _mm(kb_ref[1].astype(f), us[k])
             + _lincomb(d_ref, lambda l: ut[l], k, n, trans=True))
        if post is not None:
            w, c = post(k, w, c)
        dst[k] = w.astype(dst.dtype)
        return c

    jax.lax.fori_loop(0, n, fwd, 0)
    return jax.lax.fori_loop(0, n, bwd, carry)


def _total(x: jnp.ndarray) -> jnp.ndarray:
    """Sum of a 2-D value as a ``(1, 1)`` value."""
    return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _dot_layers(a, b, c=None) -> jnp.ndarray:
    """``sum(a * c * b)`` over layer lists (``c`` optional), as ``(1, 1)``."""
    acc = None
    for k in range(len(a)):
        t = a[k] * c[k] * b[k] if c is not None else a[k] * b[k]
        acc = t if acc is None else acc + t
    return _total(acc)


def _roll(a: jnp.ndarray, shift: int) -> jnp.ndarray:
    """``out[:, e] = a[:, e + shift]`` (cyclic over the lane axis)."""
    lanes = a.shape[-1]
    return pltpu.roll(a, (-shift) % lanes, 1)


def _ds_xy(a, pm_ref, fl, *, ex: int, ey: int):
    """x then y face sums of one ``(n^2, lanes)`` layer of z-major elements.

    The same pair sums as ``core/gs.ds_sum_local`` restricted to the block:
    each face pair is ``a + b`` from the pre-step values, written to both
    sides (``pm_ref``: :func:`_face_perms`; ``fl``: :func:`_lane_flags`).
    """
    if ex > 1:
        a = (a + fl[0:1] * _mm(pm_ref[0], _roll(a, 1))
             + fl[1:2] * _mm(pm_ref[1], _roll(a, -1)))
    if ey > 1:
        a = (a + fl[2:3] * _mm(pm_ref[2], _roll(a, ex))
             + fl[3:4] * _mm(pm_ref[3], _roll(a, -ex)))
    return a


def _ds_z(ref, fl, *, slab: int, nz: int, lead: tuple = ()):
    """In-place z face sums between the slabs of a block (after x, y)."""
    if nz > 1:
        n = ref.shape[len(lead)]
        lo, hi = ref[lead + (0,)], ref[lead + (n - 1,)]
        ref[lead + (n - 1,)] = hi + fl[4:5] * _roll(lo, slab)
        ref[lead + (0,)] = lo + fl[5:6] * _roll(hi, -slab)


def _load(ref, f, *lead):
    """Layer list of ``ref[lead..., k]`` upcast to ``f``."""
    n = ref.shape[len(lead)]
    return [ref[lead + (k,)].astype(f) for k in range(n)]


def _store(ref, layers, *lead):
    for k, a in enumerate(layers):
        ref[lead + (k,)] = a.astype(ref.dtype)


def _zrow(ref, k, *lead):
    """Row ``k`` ``(1, lanes)`` of a ``(..., n, 1, lanes)`` z-factor ref."""
    return ref[lead + (k,)]


# ---------------------------------------------------------------------------
# BlockSpec helpers (1-D grid over element blocks)
# ---------------------------------------------------------------------------

def _field_spec(n: int, be: int, lead: tuple = ()):
    nl = len(lead)
    return pl.BlockSpec(lead + (n, n * n, be),
                        lambda i: (0,) * (nl + 2) + (i,))


def _full_spec(shape):
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


def _lane_spec(rows: int, lanes: int):
    """``(rows, 1, lanes)`` block of a lane-blocked ``(rows, 1, E)`` array."""
    return pl.BlockSpec((rows, 1, lanes), lambda i: (0, 0, i))


def _plane_spec(n: int, slab: int, lead: tuple = ()):
    """``(..., 1, n^2, slab)`` block of ``(..., nblk, n^2, slab)`` planes."""
    nl = len(lead)
    return pl.BlockSpec(lead + (1, n * n, slab),
                        lambda i: (0,) * nl + (i, 0, 0))


def _widen(plane: jnp.ndarray, be: int, last: bool) -> jnp.ndarray:
    """``(n^2, slab)`` plane -> ``(n^2, be)`` block layer holding it at the
    first (``last=False``) or last slab's lanes, zeros elsewhere."""
    slab = plane.shape[-1]
    if slab == be:
        return plane
    z = jnp.zeros((plane.shape[0], be - slab), plane.dtype)
    return jnp.concatenate([z, plane] if last else [plane, z], axis=1)


def _part_spec(b: int = 1):
    return pl.BlockSpec((1, 1, b), lambda i: (i, 0, 0))


def _part_shape(nblk: int, acc, b: int = 1):
    return jax.ShapeDtypeStruct((nblk, 1, b), acc)


# ---------------------------------------------------------------------------
# v1: the plain fused operator and the fused CG-iteration kernels
# ---------------------------------------------------------------------------

def nekbone_ax_kernel(u_ref, kf_ref, kb_ref, d_ref, g_ref, w_ref, *scr,
                      acc_dtype: str | None = None):
    """Fused  w = D^T ( G (D u) )  for one block of elements.

    Refs: u_ref/w_ref ``(n, n^2, be)``; kf_ref/kb_ref ``(2, n^2, n^2)``
    forward/backward Kronecker operators; d_ref ``(n, n)`` D in SMEM;
    g_ref ``(6, n, n^2, be)`` metric; ``scr`` three VMEM scratch fields.
    """
    f = _accum(u_ref.dtype, acc_dtype)
    _apply(lambda k: u_ref[k].astype(f), w_ref, kf_ref, kb_ref, d_ref,
           lambda m, k: g_ref[m, k].astype(f), scr, f, full=True)


def _operator_operands(D, Dt, f):
    return _kron_ops(D, f), _kron_ops(Dt, f), jnp.asarray(D, f)


def _op_specs(n):
    return [_full_spec((2, n * n, n * n)), _full_spec((2, n * n, n * n)),
            _SMEM]


@functools.partial(jax.jit, static_argnames=("n", "block_e", "interpret",
                                             "acc_dtype"))
def nekbone_ax_pallas(u: jnp.ndarray, D: jnp.ndarray, Dt: jnp.ndarray,
                      g: jnp.ndarray, *, n: int, block_e: int,
                      interpret: bool = False,
                      acc_dtype: str | None = None) -> jnp.ndarray:
    """pallas_call wrapper on kernel-layout operands.

    Args:
      u: (n, n^2, E), g: (6, n, n^2, E), D/Dt: (n, n); E divisible by
      block_e.  Returns w (n, n^2, E).
    """
    E = u.shape[-1]
    assert E % block_e == 0, (E, block_e)
    f = _accum(u.dtype, acc_dtype)
    kf, kb, d = _operator_operands(D, Dt, f)
    return _call(
        functools.partial(nekbone_ax_kernel, acc_dtype=acc_dtype),
        name=f"nekbone_ax_n{n}_be{block_e}{_acc_tag(acc_dtype)}",
        grid=(E // block_e,),
        in_specs=[_field_spec(n, block_e)] + _op_specs(n)
        + [_field_spec(n, block_e, (6,))],
        out_specs=_field_spec(n, block_e),
        out_shape=jax.ShapeDtypeStruct((n, n * n, E), u.dtype),
        interpret=interpret, scratch=_scratch(n, block_e, f, 3),
        operands=(u, kf, kb, d, g))


def _masked_pap(p_ref, w_ref, kf_ref, kb_ref, d_ref, g_ref, mask_ref, scr,
                f):
    """Masked full-metric Ax into ``w_ref``; returns the ``sum(p * w)``
    partial as ``(1, 1)``."""
    def post(k, w, acc):
        w = w * mask_ref[k].astype(f)
        return w, acc + p_ref[k].astype(f) * w

    acc = _apply(lambda k: p_ref[k].astype(f), w_ref, kf_ref, kb_ref, d_ref,
                 lambda m, k: g_ref[m, k].astype(f), scr, f, full=True,
                 post=post, carry=jnp.zeros(p_ref.shape[1:], f))
    return _total(acc)


def nekbone_ax_dots_kernel(p_ref, kf_ref, kb_ref, d_ref, g_ref, mask_ref,
                           r_ref, c_ref, w_ref, pap_ref, rcz_ref, *scr,
                           acc_dtype: str | None = None):
    """Masked Ax plus the two CG inner-product partials, one element block.

        w   = mask * (D^T G D p)                    (block output)
        pap = sum(p * w)                            (per-block partial)
        rcz = sum(r * c * r)                        (per-block partial)

    ``pap`` relies on ``p`` being continuous (the CG invariant): then
    ``sum_blocks pap == p·c·A p`` with ``A = mask ∘ gs ∘ ax_local``
    (DESIGN.md §3.2).  Fields are ``(n, n^2, be)`` blocks; the partials are
    ``(1, 1, 1)`` tiles.
    """
    f = _accum(p_ref.dtype, acc_dtype)
    pap_ref[0] = _masked_pap(p_ref, w_ref, kf_ref, kb_ref, d_ref, g_ref,
                             mask_ref, scr, f).astype(pap_ref.dtype)
    rcz_ref[0] = _dot_layers(_load(r_ref, f), _load(r_ref, f),
                             _load(c_ref, f)).astype(rcz_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "block_e", "interpret",
                                             "acc_dtype"))
def nekbone_ax_dots_pallas(p: jnp.ndarray, D: jnp.ndarray, Dt: jnp.ndarray,
                           g: jnp.ndarray, mask: jnp.ndarray,
                           r: jnp.ndarray, c: jnp.ndarray, *, n: int,
                           block_e: int, interpret: bool = False,
                           acc_dtype: str | None = None):
    """Multi-output pallas_call for the fused CG iteration.

    Args: kernel-layout fields (n, n^2, E) (g: (6, n, n^2, E)); E divisible
    by block_e.  Returns ``(w, pap_parts, rcz_parts)`` with partials
    ``(E//block_e, 1)`` in the accumulation dtype.
    """
    E = p.shape[-1]
    assert E % block_e == 0, (E, block_e)
    nblk = E // block_e
    f = _accum(p.dtype, acc_dtype)
    kf, kb, d = _operator_operands(D, Dt, f)
    field = _field_spec(n, block_e)
    w, pap, rcz = _call(
        functools.partial(nekbone_ax_dots_kernel, acc_dtype=acc_dtype),
        name=f"nekbone_ax_dots_n{n}_be{block_e}{_acc_tag(acc_dtype)}",
        grid=(nblk,),
        in_specs=[field] + _op_specs(n)
        + [_field_spec(n, block_e, (6,)), field, field, field],
        out_specs=(field, _part_spec(), _part_spec()),
        out_shape=(jax.ShapeDtypeStruct((n, n * n, E), p.dtype),
                   _part_shape(nblk, f), _part_shape(nblk, f)),
        interpret=interpret, scratch=_scratch(n, block_e, f, 3),
        operands=(p, kf, kb, d, g, mask, r, c))
    return w, _part(pap), _part(rcz)


def nekbone_ax_pap_kernel(p_ref, kf_ref, kb_ref, d_ref, g_ref, mask_ref,
                          w_ref, pap_ref, *scr, acc_dtype: str | None = None):
    """Masked Ax plus the ``p·c·Ap`` partial only (DESIGN.md §3.3): the
    dots kernel with the ``r·c·r`` partial carried by the solver instead."""
    f = _accum(p_ref.dtype, acc_dtype)
    pap_ref[0] = _masked_pap(p_ref, w_ref, kf_ref, kb_ref, d_ref, g_ref,
                             mask_ref, scr, f).astype(pap_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "block_e", "interpret",
                                             "acc_dtype"))
def nekbone_ax_pap_pallas(p: jnp.ndarray, D: jnp.ndarray, Dt: jnp.ndarray,
                          g: jnp.ndarray, mask: jnp.ndarray, *, n: int,
                          block_e: int, interpret: bool = False,
                          acc_dtype: str | None = None):
    """pallas_call wrapper: returns ``(w, pap_parts)`` (carried-rtz path);
    operands as :func:`nekbone_ax_dots_pallas`."""
    E = p.shape[-1]
    assert E % block_e == 0, (E, block_e)
    nblk = E // block_e
    f = _accum(p.dtype, acc_dtype)
    kf, kb, d = _operator_operands(D, Dt, f)
    field = _field_spec(n, block_e)
    w, pap = _call(
        functools.partial(nekbone_ax_pap_kernel, acc_dtype=acc_dtype),
        name=f"nekbone_ax_pap_n{n}_be{block_e}{_acc_tag(acc_dtype)}",
        grid=(nblk,),
        in_specs=[field] + _op_specs(n)
        + [_field_spec(n, block_e, (6,)), field],
        out_specs=(field, _part_spec()),
        out_shape=(jax.ShapeDtypeStruct((n, n * n, E), p.dtype),
                   _part_shape(nblk, f)),
        interpret=interpret, scratch=_scratch(n, block_e, f, 3),
        operands=(p, kf, kb, d, g, mask))
    return w, _part(pap)


# ---------------------------------------------------------------------------
# v2 slab pipeline: in-kernel gather-scatter + merged vector updates
# (DESIGN.md §3.4).  The grid marches whole z-slabs of the element box, so a
# block's lanes are ``sz*EY*EX`` z-major elements and the x/y direct-
# stiffness summation and the intra-block z interfaces are lane rolls on the
# VMEM-resident output; the block's two boundary layers leave the kernel as
# side outputs.  The Dirichlet mask and the weight c = mask/mult are per-axis
# products on the structured box (core/geom.py): the wrappers expand them to
# an (n^2, lanes) x/y plane factor shared by every block and an (n, E)
# lane-blocked z factor.
# ---------------------------------------------------------------------------

def _slab_front(src, dst, kf_ref, kb_ref, d_ref, g, mask, pm_ref, fl, scr,
                f, *, ex, ey, nz, lead: tuple = ()):
    """Masked operator, the pre-assembly ``sum(src * w)`` partial, and the
    in-block assembly of one block of ``nz`` slabs into ``dst[lead]``;
    returns the partial as ``(1, 1)``.  ``mask(k)`` is layer k's Dirichlet
    mask; ``scr`` four scratch fields in the accumulation dtype (the last
    holds the output until its assembly is complete)."""
    def post(k, w, acc):
        v = w * mask(k)
        # continuity identity (DESIGN.md §3.2): the partial must see the
        # *unassembled* masked output — summation redistributes values.
        return _ds_xy(v, pm_ref, fl, ex=ex, ey=ey), acc + src(k) * v

    ur, us, ut, wacc = scr
    acc = _apply(src, wacc, kf_ref, kb_ref, d_ref, g, (ur, us, ut), f,
                 post=post, carry=jnp.zeros(wacc.shape[1:], f))
    _ds_z(wacc, fl, slab=ex * ey, nz=nz)

    def store(k, c):
        dst[lead + (k,)] = wacc[k].astype(dst.dtype)
        return c

    jax.lax.fori_loop(0, wacc.shape[0], store, 0)
    return _total(acc)


def _direction(p_out, r_ref, p_ref, beta, f, *lead):
    """``p_out = r + beta * p_prev`` at ``lead``, rounded through the
    storage dtype: the update kernel applies alpha to the stored p, so w
    must be A of exactly that vector (identity for f32/f64, load-bearing
    for bf16).  The RHS index goes into the load and store index, never
    into a ref view: Mosaic refuses a view of a ref whose n^2 rows are not
    a multiple of 8 (n = 10) once its block spans more than 128 lanes."""
    def body(k, c):
        i = lead + (k,)
        p_out[i] = (r_ref[i].astype(f)
                    + beta * p_ref[i].astype(f)).astype(p_out.dtype)
        return c

    jax.lax.fori_loop(0, p_out.shape[len(lead)], body, 0)


def nekbone_ax_slab_kernel(p_ref, r_ref, kf_ref, kb_ref, d_ref, g_ref,
                           mxy_ref, mz_ref, fl_ref, pm_ref, beta_ref, p_out,
                           w_ref, bot_ref, top_ref, pap_ref, *scr, ex: int,
                           ey: int, sz: int, acc_dtype: str | None = None):
    """Fused CG front-half on one block of ``sz`` whole z-slabs.

        p   = r + beta * p_prev              (merged-CG direction update)
        w   = mask * (D^T G D p)             (diagonal metric, structural mask)
        pap = sum(p * w)                     (partial, *before* assembly)
        w  <- ds_sum within the block        (x, y, and intra-block z faces)

    Refs: p/r/p_out/w ``(n, n^2, be)``; g ``(3, n, n^2, be)`` metric
    diagonal; mxy ``(n^2, be)`` and mz ``(n, 1, be)`` mask factors; fl
    ``(8, be)`` lane flags; pm ``(4, n^2, n^2)`` face moves; beta ``(1, 1)``
    in SMEM; bot/top ``(1, n^2, EX*EY)`` the assembled layer k=0 of the
    block's first slab / k=n-1 of its last; pap ``(1, 1, 1)``; ``scr``
    four VMEM scratch fields.
    """
    f = _accum(p_ref.dtype, acc_dtype)
    _direction(p_out, r_ref, p_ref, beta_ref[0, 0], f)
    mxy = mxy_ref[...].astype(f)
    pap = _slab_front(lambda k: p_out[k].astype(f), w_ref, kf_ref, kb_ref,
                      d_ref, lambda m, k: g_ref[m, k].astype(f),
                      lambda k: mz_ref[k].astype(f) * mxy, pm_ref,
                      fl_ref[...], scr, f, ex=ex, ey=ey, nz=sz)
    pap_ref[0] = pap.astype(pap_ref.dtype)
    _boundary_planes(w_ref, bot_ref, top_ref, ex * ey)


def _boundary_planes(w_ref, bot_ref, top_ref, slab: int, *lead):
    """The block's outgoing planes: layer 0 of its first slab and layer
    n-1 of its last (the cross-block halves of the z face sums)."""
    n, be = w_ref.shape[len(lead)], w_ref.shape[-1]
    bot_ref[lead + (0,)] = w_ref[lead + (0, slice(None), slice(0, slab))]
    top_ref[lead + (0,)] = w_ref[lead + (n - 1, slice(None),
                                         slice(be - slab, be))]


def _box_layers(fz_ref, fxy, f, *lead):
    """Per-layer structural factor ``fz[k] * fxy`` of a block."""
    n = fz_ref.shape[len(lead)]
    return [_zrow(fz_ref, k, *lead).astype(f) * fxy for k in range(n)]


def _slab_structure(grid, n, sz, mx, my, mz, f):
    """Structural operands of a slab block: (mxy, mz lanes, flags, perms)."""
    ex, ey, _ = grid
    be = sz * ex * ey
    return (_plane_factor(mx, my, be), _z_lanes(mz, ex * ey),
            _lane_flags(ex, ey, sz, f), _face_perms(n, f))


def _struct_specs(n, be):
    return [_full_spec((n * n, be)), _lane_spec(n, be), _full_spec((8, be)),
            _full_spec((4, n * n, n * n))]


@functools.partial(jax.jit, static_argnames=("n", "grid", "sz", "interpret",
                                             "acc_dtype", "grid_order"))
def nekbone_ax_slab_pallas(p: jnp.ndarray, r: jnp.ndarray, D: jnp.ndarray,
                           Dt: jnp.ndarray, g3: jnp.ndarray, mx: jnp.ndarray,
                           my: jnp.ndarray, mz: jnp.ndarray,
                           beta: jnp.ndarray, *, n: int,
                           grid: tuple[int, int, int], sz: int,
                           interpret: bool = False,
                           acc_dtype: str | None = None,
                           grid_order: str = "parallel"):
    """Multi-output pallas_call for the v2 slab dots kernel.

    Args:
      p/r: (n, n^2, E); g3: (3, n, n^2, E) metric diagonal; mx/my/mz:
      (EX|EY|EZ, n) per-axis mask factors; beta: (1, 1) scalar operand;
      grid: (EX, EY, EZ) with ``EZ % sz == 0`` and elements z-major.
      acc_dtype: explicit accumulation dtype (precision policy); the field
      outputs stay in the storage dtype of ``p``, the pap partials in acc.
      grid_order: ``GRID_ORDERS`` point, autotuned jointly with ``sz``.

    Returns ``(p_new, w, bot, top, pap_parts)`` with the boundary planes of
    shape ``(EZ//sz, n^2, EX*EY)`` and partials ``(EZ//sz, 1)``.
    """
    ex, ey, ez = grid
    E = p.shape[-1]
    assert E == ex * ey * ez and ez % sz == 0, (grid, sz, E)
    slab = ex * ey
    be = sz * slab
    nblk = ez // sz
    f = _accum(p.dtype, acc_dtype)
    kf, kb, d = _operator_operands(D, Dt, f)
    field = _field_spec(n, be)
    sdt = p.dtype
    p, w, bot, top, pap = _call(
        functools.partial(nekbone_ax_slab_kernel, ex=ex, ey=ey, sz=sz,
                          acc_dtype=acc_dtype),
        name=(f"nekbone_ax_slab_n{n}_sz{sz}{_acc_tag(acc_dtype)}"
              f"{_order_tag(grid_order)}"),
        grid=(nblk,),
        in_specs=[field, field] + _op_specs(n)
        + [_field_spec(n, be, (3,))] + _struct_specs(n, be) + [_SMEM],
        out_specs=(field, field, _plane_spec(n, slab), _plane_spec(n, slab),
                   _part_spec()),
        out_shape=(jax.ShapeDtypeStruct((n, n * n, E), sdt),
                   jax.ShapeDtypeStruct((n, n * n, E), sdt),
                   jax.ShapeDtypeStruct((nblk, n * n, slab), sdt),
                   jax.ShapeDtypeStruct((nblk, n * n, slab), sdt),
                   _part_shape(nblk, f)),
        interpret=interpret, grid_order=grid_order,
        scratch=_scratch(n, be, f, 4), aliases=SLAB_ALIASES,
        operands=(p, r, kf, kb, d, g3,
                  *_slab_structure(grid, n, sz, mx, my, mz, f),
                  jnp.asarray(beta, f).reshape(1, 1)))
    return p, w, bot, top, _part(pap)


def _stitch(v, addb, addt):
    """Add the neighbour planes ``(n^2, slab)`` to a block's layer list:
    ``addb`` to layer 0 of its first slab, ``addt`` to layer n-1 of its
    last."""
    v = list(v)
    be = v[0].shape[-1]
    v[0] = v[0] + _widen(addb, be, last=False)
    v[-1] = v[-1] + _widen(addt, be, last=True)
    return v


def _update_one(x, p, r, w, addb, addt, alpha, c, r_dtype):
    """Stitch + both axpys + post-update ``r·c·r`` for one RHS."""
    v = _stitch(w, addb, addt)
    x = [a + alpha * b for a, b in zip(x, p)]
    # the r·c·r partial must see the *stored* residual: the carried rtz is
    # next iteration's beta numerator, and that iteration reads the rounded
    # r from HBM.  Identity for f32/f64 storage; load-bearing for bf16.
    r = [(a - alpha * b).astype(r_dtype).astype(a.dtype)
         for a, b in zip(r, v)]
    return x, r, _dot_layers(r, r, c)


def nekbone_cg_update_kernel(x_ref, p_ref, r_ref, w_ref, addb_ref, addt_ref,
                             alpha_ref, cxy_ref, cz_ref, x_out, r_out,
                             rcr_ref, *, acc_dtype: str | None = None):
    """Merged CG back-half on one slab block (DESIGN.md §3.4).

        w   += neighbour boundary layers     (zero outside the edge slab)
        x   += alpha * p
        r   -= alpha * w
        rcr  = sum(r * c * r)                (c from the structural factors)

    Refs: fields ``(n, n^2, be)``; addb/addt ``(1, n^2, EX*EY)``; alpha
    ``(1, 1)`` in SMEM; cxy ``(n^2, be)``, cz ``(n, be)``; rcr ``(1, 1, 1)``.
    """
    f = _accum(x_ref.dtype, acc_dtype)
    c = _box_layers(cz_ref, cxy_ref[...].astype(f), f)
    x, r, rcr = _update_one(
        _load(x_ref, f), _load(p_ref, f), _load(r_ref, f), _load(w_ref, f),
        addb_ref[0].astype(f), addt_ref[0].astype(f), alpha_ref[0, 0], c,
        r_out.dtype)
    rcr_ref[0] = rcr.astype(rcr_ref.dtype)
    _store(x_out, x)
    _store(r_out, r)


@functools.partial(jax.jit, static_argnames=("n", "grid", "sz", "interpret",
                                             "acc_dtype"))
def nekbone_cg_update_pallas(x: jnp.ndarray, p: jnp.ndarray,
                             r: jnp.ndarray, w: jnp.ndarray,
                             addb: jnp.ndarray, addt: jnp.ndarray,
                             alpha: jnp.ndarray, cx: jnp.ndarray,
                             cy: jnp.ndarray, cz: jnp.ndarray, *, n: int,
                             grid: tuple[int, int, int], sz: int,
                             interpret: bool = False,
                             acc_dtype: str | None = None):
    """Multi-output pallas_call for the merged vector-update kernel.

    Args mirror :func:`nekbone_ax_slab_pallas`; ``addb``/``addt`` are the
    *shifted* boundary planes ``(EZ//sz, n^2, EX*EY)`` (``addb[b] =
    top[b-1]``, ``addt[b] = bot[b+1]``, zeros at the global ends —
    :func:`shift_planes`).  Returns ``(x_new, r_new, rcr_parts)``.
    """
    ex, ey, ez = grid
    E = x.shape[-1]
    assert E == ex * ey * ez and ez % sz == 0, (grid, sz, E)
    slab = ex * ey
    be = sz * slab
    nblk = ez // sz
    f = _accum(x.dtype, acc_dtype)
    field = _field_spec(n, be)
    plane = _plane_spec(n, slab)
    x, r, rcr = _call(
        functools.partial(nekbone_cg_update_kernel, acc_dtype=acc_dtype),
        name=f"nekbone_cg_update_n{n}_sz{sz}{_acc_tag(acc_dtype)}",
        grid=(nblk,),
        in_specs=[field] * 4 + [plane, plane, _SMEM,
                                _full_spec((n * n, be)), _lane_spec(n, be)],
        out_specs=(field, field, _part_spec()),
        out_shape=(jax.ShapeDtypeStruct((n, n * n, E), x.dtype),
                   jax.ShapeDtypeStruct((n, n * n, E), r.dtype),
                   _part_shape(nblk, f)),
        interpret=interpret, aliases=UPDATE_ALIASES,
        operands=(x, p, r, w, addb, addt,
                  jnp.asarray(alpha, f).reshape(1, 1),
                  _plane_factor(cx, cy, be), _z_lanes(cz, slab)))
    return x, r, _part(rcr)


# ---------------------------------------------------------------------------
# Multi-RHS (block) v2 pipeline: the same two slab kernels carrying a static
# RHS-batch dimension b (DESIGN.md §12).  The operator-side residents are
# loaded once per slab residency and reused across all b right-hand sides;
# the per-RHS work is a static unroll of the single-RHS expression graph, so
# each lane is operation-for-operation the b=1 kernel (fp64-bitwise).
# Per-RHS scalars travel as (1, b) SMEM operands; the partials leave as a
# (1, 1, b) tile, one lane per RHS.
# ---------------------------------------------------------------------------

def _lane_row(vals) -> jnp.ndarray:
    """``(1, b)`` row from ``b`` values of shape ``(1, 1)``."""
    b = len(vals)
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
    row = jnp.zeros((1, b), vals[0].dtype)
    for j, v in enumerate(vals):
        row = jnp.where(idx == j, v, row)
    return row


def nekbone_ax_slab_block_kernel(p_ref, r_ref, kf_ref, kb_ref, d_ref, g_ref,
                                 mxy_ref, mz_ref, fl_ref, pm_ref, beta_ref,
                                 p_out, w_ref, bot_ref, top_ref, pap_ref,
                                 *scr, ex: int, ey: int, sz: int, nrhs: int,
                                 acc_dtype: str | None = None):
    """Batched CG front-half: ``nekbone_ax_slab_kernel`` over ``nrhs`` RHS.

    Vector refs carry a leading ``nrhs`` axis (fields ``(nrhs, n, n^2,
    be)``, planes ``(nrhs, 1, n^2, EX*EY)``); operator refs keep their
    shapes and are shared.  ``beta_ref`` is ``(1, nrhs)`` SMEM, ``pap_ref``
    ``(1, 1, nrhs)``.
    """
    f = _accum(p_ref.dtype, acc_dtype)
    mxy = mxy_ref[...].astype(f)
    fl = fl_ref[...]
    paps = []
    for j in range(nrhs):
        _direction(p_out, r_ref, p_ref, beta_ref[0, j], f, j)
        paps.append(_slab_front(
            lambda k, j=j: p_out[j, k].astype(f), w_ref, kf_ref, kb_ref,
            d_ref, lambda m, k: g_ref[m, k].astype(f),
            lambda k: mz_ref[k].astype(f) * mxy, pm_ref, fl, scr, f,
            ex=ex, ey=ey, nz=sz, lead=(j,)))
        _boundary_planes(w_ref, bot_ref, top_ref, ex * ey, j)
    pap_ref[0] = _lane_row(paps).astype(pap_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "grid", "sz", "interpret",
                                             "acc_dtype", "grid_order"))
def nekbone_ax_slab_block_pallas(p: jnp.ndarray, r: jnp.ndarray,
                                 D: jnp.ndarray, Dt: jnp.ndarray,
                                 g3: jnp.ndarray, mx: jnp.ndarray,
                                 my: jnp.ndarray, mz: jnp.ndarray,
                                 beta: jnp.ndarray, *, n: int,
                                 grid: tuple[int, int, int], sz: int,
                                 interpret: bool = False,
                                 acc_dtype: str | None = None,
                                 grid_order: str = "parallel"):
    """Multi-output pallas_call for the batched v2 slab kernel.

    Args mirror :func:`nekbone_ax_slab_pallas` with a leading RHS axis:
    ``p``/``r`` are (b, n, n^2, E) and ``beta`` is (1, b).  Returns
    ``(p_new, w, bot, top, pap_parts)`` with planes (b, EZ//sz, n^2,
    EX*EY) and partials (EZ//sz, b).
    """
    ex, ey, ez = grid
    nrhs, E = p.shape[0], p.shape[-1]
    assert E == ex * ey * ez and ez % sz == 0, (grid, sz, E)
    slab = ex * ey
    be = sz * slab
    nblk = ez // sz
    f = _accum(p.dtype, acc_dtype)
    kf, kb, d = _operator_operands(D, Dt, f)
    field = _field_spec(n, be, (nrhs,))
    plane = _plane_spec(n, slab, (nrhs,))
    sdt = p.dtype
    p, w, bot, top, pap = _call(
        functools.partial(nekbone_ax_slab_block_kernel, ex=ex, ey=ey, sz=sz,
                          nrhs=nrhs, acc_dtype=acc_dtype),
        name=(f"nekbone_ax_slab_b{nrhs}_n{n}_sz{sz}{_acc_tag(acc_dtype)}"
              f"{_order_tag(grid_order)}"),
        grid=(nblk,),
        in_specs=[field, field] + _op_specs(n)
        + [_field_spec(n, be, (3,))] + _struct_specs(n, be) + [_SMEM],
        out_specs=(field, field, plane, plane, _part_spec(nrhs)),
        out_shape=(jax.ShapeDtypeStruct((nrhs, n, n * n, E), sdt),
                   jax.ShapeDtypeStruct((nrhs, n, n * n, E), sdt),
                   jax.ShapeDtypeStruct((nrhs, nblk, n * n, slab), sdt),
                   jax.ShapeDtypeStruct((nrhs, nblk, n * n, slab), sdt),
                   _part_shape(nblk, f, nrhs)),
        interpret=interpret, grid_order=grid_order,
        scratch=_scratch(n, be, f, 4), aliases=SLAB_ALIASES,
        operands=(p, r, kf, kb, d, g3,
                  *_slab_structure(grid, n, sz, mx, my, mz, f),
                  jnp.asarray(beta, f).reshape(1, nrhs)))
    return p, w, bot, top, _part(pap)


def nekbone_cg_update_block_kernel(x_ref, p_ref, r_ref, w_ref, addb_ref,
                                   addt_ref, alpha_ref, cxy_ref, cz_ref,
                                   x_out, r_out, rcr_ref, *, nrhs: int,
                                   acc_dtype: str | None = None):
    """Batched CG back-half: ``nekbone_cg_update_kernel`` over ``nrhs`` RHS.

    The weight ``c`` is rebuilt once and shared across the batch; stitch,
    both axpys and the ``r·c·r`` partial run per RHS (``alpha_ref`` (1, b)
    SMEM, ``rcr_ref`` (1, 1, b)).
    """
    f = _accum(x_ref.dtype, acc_dtype)
    c = _box_layers(cz_ref, cxy_ref[...].astype(f), f)
    rcrs = []
    for j in range(nrhs):
        x, r, rcr = _update_one(
            _load(x_ref, f, j), _load(p_ref, f, j), _load(r_ref, f, j),
            _load(w_ref, f, j), addb_ref[j, 0].astype(f),
            addt_ref[j, 0].astype(f), alpha_ref[0, j], c, r_out.dtype)
        rcrs.append(rcr)
        _store(x_out, x, j)
        _store(r_out, r, j)
    rcr_ref[0] = _lane_row(rcrs).astype(rcr_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "grid", "sz", "interpret",
                                             "acc_dtype"))
def nekbone_cg_update_block_pallas(x: jnp.ndarray, p: jnp.ndarray,
                                   r: jnp.ndarray, w: jnp.ndarray,
                                   addb: jnp.ndarray, addt: jnp.ndarray,
                                   alpha: jnp.ndarray, cx: jnp.ndarray,
                                   cy: jnp.ndarray, cz: jnp.ndarray, *,
                                   n: int, grid: tuple[int, int, int],
                                   sz: int, interpret: bool = False,
                                   acc_dtype: str | None = None):
    """Multi-output pallas_call for the batched merged-update kernel.

    Args mirror :func:`nekbone_cg_update_pallas` with a leading RHS axis
    ((b, n, n^2, E) fields, (b, EZ//sz, n^2, EX*EY) shifted planes, (1, b)
    alpha).  Returns ``(x_new, r_new, rcr_parts)`` with partials
    (EZ//sz, b).
    """
    ex, ey, ez = grid
    nrhs, E = x.shape[0], x.shape[-1]
    assert E == ex * ey * ez and ez % sz == 0, (grid, sz, E)
    slab = ex * ey
    be = sz * slab
    nblk = ez // sz
    f = _accum(x.dtype, acc_dtype)
    field = _field_spec(n, be, (nrhs,))
    plane = _plane_spec(n, slab, (nrhs,))
    x, r, rcr = _call(
        functools.partial(nekbone_cg_update_block_kernel, nrhs=nrhs,
                          acc_dtype=acc_dtype),
        name=f"nekbone_cg_update_b{nrhs}_n{n}_sz{sz}{_acc_tag(acc_dtype)}",
        grid=(nblk,),
        in_specs=[field] * 4 + [plane, plane, _SMEM,
                                _full_spec((n * n, be)), _lane_spec(n, be)],
        out_specs=(field, field, _part_spec(nrhs)),
        out_shape=(jax.ShapeDtypeStruct((nrhs, n, n * n, E), x.dtype),
                   jax.ShapeDtypeStruct((nrhs, n, n * n, E), r.dtype),
                   _part_shape(nblk, f, nrhs)),
        interpret=interpret, aliases=UPDATE_ALIASES,
        operands=(x, p, r, w, addb, addt,
                  jnp.asarray(alpha, f).reshape(1, nrhs),
                  _plane_factor(cx, cy, be), _z_lanes(cz, slab)))
    return x, r, _part(rcr)


# ---------------------------------------------------------------------------
# v3 s-step pipeline: matrix-powers slab kernel + multi-axpy update
# (DESIGN.md §8).  One kernel invocation evaluates the whole 2s+1-vector
# Krylov basis {p, Ap, .., A^s p, r, Ar, .., A^{s-1} r} of an s-step CG
# cycle in a single slab residency.  Chaining A across block boundaries
# needs a matrix-powers ghost region: blocks march sz owned slabs plus s
# halo slabs on each side (zero-padded past the domain ends).  The owned
# basis slices are fully assembled, so no plane side channel exists; the
# redundant halo reads are the side channel instead
# (cost.sstep_halo_streams).  The (2s+1)^2 Gram block is reduced in-kernel
# over the owned slabs; the s x s recurrence is solved in f64 on the host
# (core/cg_sstep.py).
# ---------------------------------------------------------------------------

def sstep_extend_field(f: jnp.ndarray, grid: tuple[int, int, int], sz: int,
                       halo: int, below: jnp.ndarray | None = None,
                       above: jnp.ndarray | None = None) -> jnp.ndarray:
    """Gather per-block halo windows of a kernel-layout field, zero-padded.

    Args:
      f: (..., E) field with z-major elements on the last axis (a
          ``(n, n^2, E)`` vector or a ``(3, n, n^2, E)`` metric).
      below/above: optional ``halo``-deep ghost slabs replacing the zero
          padding at the low/high z end — ``(..., halo*EY*EX)``.  This is
          the distributed halo hook (distributed/sstep.py): when ``grid``
          is a *shard-local* grid, the neighbour shards' boundary slabs go
          here and the resulting windows are exactly the single-device ones.
    Returns (..., (EZ//sz) * (sz + 2*halo)*EY*EX): the windows side by side
    on the last axis; block ``i``'s holds slabs ``[i*sz - halo, i*sz + sz +
    halo)`` with zeros past the domain ends.
    """
    ex, ey, ez = grid
    slab = ex * ey
    nblk = ez // sz
    L = sz + 2 * halo
    lead = f.shape[:-1]
    fz = f.reshape(lead + (ez, slab))
    pad_shape = lead + (halo, slab)
    pb = (jnp.zeros(pad_shape, f.dtype) if below is None
          else below.reshape(pad_shape).astype(f.dtype))
    pa = (jnp.zeros(pad_shape, f.dtype) if above is None
          else above.reshape(pad_shape).astype(f.dtype))
    fp = jnp.concatenate([pb, fz, pa], axis=-2)
    idx = (np.arange(nblk)[:, None] * sz + np.arange(L)[None, :]).ravel()
    return jnp.take(fp, idx, axis=-2).reshape(lead + (nblk * L * slab,))


def sstep_extend_zfactor(fz: jnp.ndarray, sz: int, halo: int,
                         below: jnp.ndarray | None = None,
                         above: jnp.ndarray | None = None) -> jnp.ndarray:
    """Per-block halo windows of a per-axis z factor ``(EZ, n)``.

    Out-of-domain halo rows are padded with ones: the fields there are
    zero, so the factor value is inert, and ones never introduce false
    Dirichlet zeros.  ``below``/``above`` replace the pad with
    neighbour-shard factor rows ``(halo, n)`` (the distributed hook, as in
    :func:`sstep_extend_field`).  Returns (EZ//sz, sz+2*halo, n).
    """
    ez, n = fz.shape
    nblk = ez // sz
    L = sz + 2 * halo
    pb = (jnp.ones((halo, n), fz.dtype) if below is None
          else below.reshape(halo, n).astype(fz.dtype))
    pa = (jnp.ones((halo, n), fz.dtype) if above is None
          else above.reshape(halo, n).astype(fz.dtype))
    fp = jnp.concatenate([pb, fz, pa], axis=0)
    idx = jnp.arange(nblk)[:, None] * sz + jnp.arange(L)[None, :]
    return fp[idx]


def _window_apply(src, dst, kf_ref, kb_ref, d_ref, g, mask, pm_ref, fl,
                  scr, f, *, ex, ey, L):
    """One masked, window-assembled operator application into ``dst``."""
    def post(k, w, c):
        return _ds_xy(w * mask(k), pm_ref, fl, ex=ex, ey=ey), c

    _apply(src, dst, kf_ref, kb_ref, d_ref, g, scr, f, post=post)
    _ds_z(dst, fl, slab=ex * ey, nz=L)


def _own(a: jnp.ndarray, ho: int, be: int) -> jnp.ndarray:
    """Owned lanes ``[ho, ho + be)`` of a window layer."""
    return a[:, ho:ho + be]


def _window_specs(n, Lee):
    """The metric window and the window structure operands."""
    return [_field_spec(n, Lee, (3,)), _full_spec((n * n, Lee)),
            pl.BlockSpec((1, n, 1, Lee), lambda i: (i, 0, 0, 0)),
            _full_spec((8, Lee)), _full_spec((4, n * n, n * n))]


def _window_operands(gext, mx, my, mzext, grid, n, L, f):
    ex, ey, _ = grid
    Lee = L * ex * ey
    return (gext, _plane_factor(mx, my, Lee),
            _z_lanes(mzext, ex * ey), _lane_flags(ex, ey, L, f),
            _face_perms(n, f))


def nekbone_ax_powers_kernel(pext_ref, rext_ref, kf_ref, kb_ref, d_ref,
                             gext_ref, mxy_ref, mzext_ref, fl_ref, pm_ref,
                             cxy_ref, cz_ref, th_ref, basis_ref, gram_ref,
                             ur, us, ut, va, vb, *, ex: int, ey: int,
                             sz: int, s: int, halo: int,
                             acc_dtype: str | None = None):
    """Matrix-powers front-half of one s-step CG cycle, one slab block.

    In one VMEM residency over ``L = sz + 2*halo`` slabs (``halo = s``):

        v_{j+1} = (1/theta) * mask * gs_block(D^T G D v_j)   chained s times
                  from v_0 = p, and s-1 times from v_0 = r
        G_ab    = sum_own(V_a * c * V_b)                     Gram partials

    with ``V = [p, A'p, .., A'^s p, r, A'r, .., A'^{s-1} r]`` (``A' = A /
    theta``, DESIGN.md §8).  Every basis vector is rounded through the
    *storage* dtype before it feeds the next application and the Gram.

    Refs: pext/rext ``(n, n^2, Lee)`` windows; gext ``(3, n, n^2,
    Lee)``; mxy ``(n^2, Lee)``, mzext ``(1, n, 1, Lee)``, fl ``(8, Lee)``,
    pm as the slab kernel; cxy ``(n^2, be)`` and cz ``(n, 1, be)`` the
    owned weight; th ``(1, 1)`` 1/theta in SMEM; basis ``(2s-1, n, n^2,
    be)`` owned ``[A'p..A'^s p, A'r..A'^{s-1} r]``; gram ``(1, 2s+1,
    2s+1)`` (symmetric: the upper triangle, mirrored); ur/us/ut/va/vb VMEM
    scratch windows.
    """
    L = sz + 2 * halo
    slab = ex * ey
    be, ho = sz * slab, halo * slab
    n = pext_ref.shape[0]
    f = _accum(pext_ref.dtype, acc_dtype)
    out_dtype = basis_ref.dtype
    fl = fl_ref[...]
    mxy = mxy_ref[...].astype(f)
    inv_th = th_ref[0, 0]
    g = lambda m, k: gext_ref[m, k].astype(f)            # noqa: E731
    mask = lambda k: mzext_ref[0, k].astype(f) * mxy     # noqa: E731

    def chain(v0_ref, napps, m0):
        src = lambda k: v0_ref[k].astype(f)              # noqa: E731
        for j in range(napps):
            dst = (va, vb)[j % 2]
            _window_apply(src, dst, kf_ref, kb_ref, d_ref, g, mask, pm_ref,
                          fl, (ur, us, ut), f, ex=ex, ey=ey, L=L)

            def scale(k, c, dst=dst, m=m0 + j):
                # round through storage: the next application and the
                # Gram must see exactly the vector the update re-reads.
                v = (dst[k] * inv_th).astype(out_dtype)
                dst[k] = v.astype(f)
                basis_ref[m, k] = _own(v, ho, be)
                return c

            jax.lax.fori_loop(0, n, scale, 0)
            src = lambda k, dst=dst: dst[k]              # noqa: E731

    chain(pext_ref, s, 0)
    chain(rext_ref, s - 1, s)
    K = 2 * s + 1
    ri = jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
    cxy = cxy_ref[...].astype(f)

    def gram_layer(k, gram):
        c = cz_ref[k].astype(f) * cxy
        V = ([_own(pext_ref[k], ho, be).astype(f)]
             + [basis_ref[m, k].astype(f) for m in range(s)]
             + [_own(rext_ref[k], ho, be).astype(f)]
             + [basis_ref[s + m, k].astype(f) for m in range(s - 1)])
        for a in range(K):
            ca = V[a] * c
            for b in range(a, K):
                hit = ((ri == a) & (ci == b)) | ((ri == b) & (ci == a))
                gram = jnp.where(hit, gram + _total(ca * V[b]), gram)
        return gram

    gram = jax.lax.fori_loop(0, n, gram_layer, jnp.zeros((K, K), f))
    gram_ref[0] = gram.astype(gram_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "grid", "sz", "s",
                                             "interpret", "acc_dtype",
                                             "grid_order"))
def nekbone_ax_powers_pallas(pext: jnp.ndarray, rext: jnp.ndarray,
                             D: jnp.ndarray, Dt: jnp.ndarray,
                             gext: jnp.ndarray, mx: jnp.ndarray,
                             my: jnp.ndarray, mzext: jnp.ndarray,
                             cx: jnp.ndarray, cy: jnp.ndarray,
                             cz: jnp.ndarray, inv_theta: jnp.ndarray, *,
                             n: int, grid: tuple[int, int, int], sz: int,
                             s: int, interpret: bool = False,
                             acc_dtype: str | None = None,
                             grid_order: str = "parallel"):
    """Multi-output pallas_call for the v3 matrix-powers kernel.

    Args:
      pext/rext: (n, n^2, (EZ//sz)*Lee) halo windows
        (:func:`sstep_extend_field` with ``halo = s``); gext: (3, n, n^2,
        (EZ//sz)*Lee); mzext: (EZ//sz, L, n) (:func:`sstep_extend_zfactor`);
        cz: (EZ, n); inv_theta: (1, 1) basis scale.

    Returns ``(basis, gram_parts)``: basis ``(2s-1, n, n^2, E)`` in the
    storage dtype of ``pext``, Gram partials ``(EZ//sz, 2s+1, 2s+1)`` in
    the accumulation dtype.
    """
    ex, ey, ez = grid
    assert ez % sz == 0 and s >= 1, (grid, sz, s)
    halo = s
    L = sz + 2 * halo
    slab = ex * ey
    Lee = L * slab
    be = sz * slab
    nblk = ez // sz
    E = nblk * be
    K = 2 * s + 1
    nb = 2 * s - 1
    assert pext.shape == (n, n * n, nblk * Lee), (pext.shape, (nblk, Lee))
    f = _accum(pext.dtype, acc_dtype)
    kf, kb, d = _operator_operands(D, Dt, f)
    win = _field_spec(n, Lee)
    basis, gram = _call(
        functools.partial(nekbone_ax_powers_kernel, ex=ex, ey=ey, sz=sz,
                          s=s, halo=halo, acc_dtype=acc_dtype),
        name=(f"nekbone_ax_powers_n{n}_sz{sz}_s{s}{_acc_tag(acc_dtype)}"
              f"{_order_tag(grid_order)}"),
        grid=(nblk,),
        in_specs=[win, win] + _op_specs(n) + _window_specs(n, Lee)
        + [_full_spec((n * n, be)), _lane_spec(n, be), _SMEM],
        out_specs=(_field_spec(n, be, (nb,)),
                   pl.BlockSpec((1, K, K), lambda i: (i, 0, 0))),
        out_shape=(jax.ShapeDtypeStruct((nb, n, n * n, E), pext.dtype),
                   jax.ShapeDtypeStruct((nblk, K, K), f)),
        interpret=interpret, grid_order=grid_order,
        scratch=_scratch(n, Lee, f, 5),
        operands=(pext, rext, kf, kb, d,
                  *_window_operands(gext, mx, my, mzext, grid, n, L, f),
                  _plane_factor(cx, cy, be), _z_lanes(cz, slab),
                  jnp.asarray(inv_theta, f).reshape(1, 1)))
    return basis, gram


def nekbone_sstep_update_kernel(x_ref, p_ref, r_ref, basis_ref, coef_ref,
                                cxy_ref, cz_ref, x_out, r_out, p_out,
                                rcr_ref, *, s: int,
                                acc_dtype: str | None = None):
    """Multi-axpy back-half of one s-step cycle (DESIGN.md §8).

        x += V @ e_s,   r = V @ b_s,   p = V @ a_s,   rcr = sum(r*c*r)

    with ``V = [p, basis.., r, basis..]`` in the powers kernel's column
    order and ``(e_s, b_s, a_s)`` the rows of ``coef_ref`` ((3, 2s+1),
    SMEM).  The ``r·c·r`` partial reduces over the *stored* residual.
    """
    f = _accum(x_ref.dtype, acc_dtype)
    n = x_ref.shape[0]
    c = _box_layers(cz_ref, cxy_ref[...].astype(f), f)
    p, r = _load(p_ref, f), _load(r_ref, f)
    basis = [_load(basis_ref, f, m) for m in range(2 * s - 1)]
    terms = [p] + basis[:s] + [r] + basis[s:]
    x = _load(x_ref, f)
    rn, pn = [], []
    for k in range(n):
        xa, ra, pa = x[k], None, None
        for t, v in enumerate(terms):
            xa = xa + coef_ref[0, t] * v[k]
            rt, pt = coef_ref[1, t] * v[k], coef_ref[2, t] * v[k]
            ra = rt if ra is None else ra + rt
            pa = pt if pa is None else pa + pt
        x[k] = xa
        rn.append(ra.astype(r_out.dtype).astype(f))
        pn.append(pa)
    rcr_ref[0] = _dot_layers(rn, rn, c).astype(rcr_ref.dtype)
    _store(x_out, x)
    _store(r_out, rn)
    _store(p_out, pn)


@functools.partial(jax.jit, static_argnames=("n", "grid", "sz", "s",
                                             "interpret", "acc_dtype"))
def nekbone_sstep_update_pallas(x: jnp.ndarray, p: jnp.ndarray,
                                r: jnp.ndarray, basis: jnp.ndarray,
                                coef: jnp.ndarray, cx: jnp.ndarray,
                                cy: jnp.ndarray, cz: jnp.ndarray, *, n: int,
                                grid: tuple[int, int, int], sz: int, s: int,
                                interpret: bool = False,
                                acc_dtype: str | None = None):
    """Multi-output pallas_call for the s-step update kernel.

    Args mirror :func:`nekbone_ax_powers_pallas`; fields are (n, n^2, E),
    ``basis`` is ``(2s-1, n, n^2, E)`` and ``coef`` the (3, 2s+1)
    coefficient block.  Returns ``(x_new, r_new, p_new, rcr_parts)``.
    """
    ex, ey, ez = grid
    E = x.shape[-1]
    assert E == ex * ey * ez and ez % sz == 0, (grid, sz, E)
    slab = ex * ey
    be = sz * slab
    nblk = ez // sz
    nb = 2 * s - 1
    f = _accum(x.dtype, acc_dtype)
    field = _field_spec(n, be)
    x, r, p, rcr = _call(
        functools.partial(nekbone_sstep_update_kernel, s=s,
                          acc_dtype=acc_dtype),
        name=f"nekbone_sstep_update_n{n}_sz{sz}_s{s}{_acc_tag(acc_dtype)}",
        grid=(nblk,),
        in_specs=[field] * 3 + [_field_spec(n, be, (nb,)), _SMEM,
                                _full_spec((n * n, be)), _lane_spec(n, be)],
        out_specs=(field, field, field, _part_spec()),
        out_shape=(jax.ShapeDtypeStruct((n, n * n, E), x.dtype),
                   jax.ShapeDtypeStruct((n, n * n, E), r.dtype),
                   jax.ShapeDtypeStruct((n, n * n, E), p.dtype),
                   _part_shape(nblk, f)),
        interpret=interpret,
        operands=(x, p, r, basis, jnp.asarray(coef, f),
                  _plane_factor(cx, cy, be), _z_lanes(cz, slab)))
    return x, r, p, _part(rcr)


# ---------------------------------------------------------------------------
# Preconditioning kernels (DESIGN.md §9).  Jacobi PCG carries z = D^-1 r and
# replaces the v2 update kernel; Chebyshev evaluates z = q_k(A) r with the
# v3 halo machinery (k ghost slabs per block side) in one slab residency.
# ---------------------------------------------------------------------------

def _pcg_update_one(x, p, z, w, addb, addt, alpha, invd, diag, c, z_dtype):
    """Stitch + both axpys + the ``r·c·z`` and ``r·c·r`` partials of one
    Jacobi-PCG right-hand side (``diag = 1 / invd``, shared by a batch)."""
    v = _stitch(w, addb, addt)
    x = [a + alpha * b for a, b in zip(x, p)]
    # both partials see the *stored* z (§7 rule 2).
    z = [(a - alpha * (i * b)).astype(z_dtype).astype(a.dtype)
         for a, i, b in zip(z, invd, v)]
    zcz = [a * ck * a for a, ck in zip(z, c)]
    rtz = _total(sum(t * d for t, d in zip(zcz, diag)))
    rcr = _total(sum(t * d * d for t, d in zip(zcz, diag)))
    return x, z, rtz, rcr


def nekbone_pcg_update_kernel(x_ref, p_ref, z_ref, w_ref, addb_ref,
                              addt_ref, alpha_ref, invd_ref, cxy_ref,
                              cz_ref, x_out, z_out, rtz_ref, rcr_ref, *,
                              acc_dtype: str | None = None):
    """Merged Jacobi-PCG back-half on one slab block (DESIGN.md §9.2).

        w   += neighbour boundary layers          (the v2 stitch)
        x   += alpha * p
        z   -= alpha * invdiag * w                (z-coordinate r-update)
        rtz  = sum(r * c * z) = sum(z * c * z / invdiag)
        rcr  = sum(r * c * r) = sum(z * c * z / invdiag^2)

    with ``r = z / invdiag`` reconstructed in VMEM (invdiag is 1 at masked
    rows).  Refs as :func:`nekbone_cg_update_kernel` with ``z`` in place
    of ``r`` plus the ``invd`` field and two ``(1, 1, 1)`` partials.
    """
    f = _accum(x_ref.dtype, acc_dtype)
    c = _box_layers(cz_ref, cxy_ref[...].astype(f), f)
    invd = _load(invd_ref, f)
    diag = [1.0 / i for i in invd]          # exact where invd == 1 (masked)
    x, z, rtz, rcr = _pcg_update_one(
        _load(x_ref, f), _load(p_ref, f), _load(z_ref, f), _load(w_ref, f),
        addb_ref[0].astype(f), addt_ref[0].astype(f), alpha_ref[0, 0],
        invd, diag, c, z_out.dtype)
    rtz_ref[0] = rtz.astype(rtz_ref.dtype)
    rcr_ref[0] = rcr.astype(rcr_ref.dtype)
    _store(x_out, x)
    _store(z_out, z)


@functools.partial(jax.jit, static_argnames=("n", "grid", "sz", "interpret",
                                             "acc_dtype"))
def nekbone_pcg_update_pallas(x: jnp.ndarray, p: jnp.ndarray,
                              z: jnp.ndarray, w: jnp.ndarray,
                              addb: jnp.ndarray, addt: jnp.ndarray,
                              alpha: jnp.ndarray, invd: jnp.ndarray,
                              cx: jnp.ndarray, cy: jnp.ndarray,
                              cz: jnp.ndarray, *, n: int,
                              grid: tuple[int, int, int], sz: int,
                              interpret: bool = False,
                              acc_dtype: str | None = None):
    """Multi-output pallas_call for the Jacobi-PCG update kernel.

    Args mirror :func:`nekbone_cg_update_pallas` with the carried
    preconditioned residual ``z`` in the residual slot plus ``invd``:
    (n, n^2, E) assembled 1/diag(A).  Returns
    ``(x_new, z_new, rtz_parts, rcr_parts)``.
    """
    ex, ey, ez = grid
    E = x.shape[-1]
    assert E == ex * ey * ez and ez % sz == 0, (grid, sz, E)
    slab = ex * ey
    be = sz * slab
    nblk = ez // sz
    f = _accum(x.dtype, acc_dtype)
    field = _field_spec(n, be)
    plane = _plane_spec(n, slab)
    x, z, rtz, rcr = _call(
        functools.partial(nekbone_pcg_update_kernel, acc_dtype=acc_dtype),
        name=f"nekbone_pcg_update_n{n}_sz{sz}{_acc_tag(acc_dtype)}",
        grid=(nblk,),
        in_specs=[field] * 4 + [plane, plane, _SMEM, field,
                                _full_spec((n * n, be)), _lane_spec(n, be)],
        out_specs=(field, field, _part_spec(), _part_spec()),
        out_shape=(jax.ShapeDtypeStruct((n, n * n, E), x.dtype),
                   jax.ShapeDtypeStruct((n, n * n, E), z.dtype),
                   _part_shape(nblk, f), _part_shape(nblk, f)),
        interpret=interpret, aliases=UPDATE_ALIASES,
        operands=(x, p, z, w, addb, addt,
                  jnp.asarray(alpha, f).reshape(1, 1), invd,
                  _plane_factor(cx, cy, be), _z_lanes(cz, slab)))
    return x, z, _part(rtz), _part(rcr)


def nekbone_pcg_update_block_kernel(x_ref, p_ref, z_ref, w_ref, addb_ref,
                                    addt_ref, alpha_ref, invd_ref, cxy_ref,
                                    cz_ref, x_out, z_out, rtz_ref, rcr_ref,
                                    *, nrhs: int,
                                    acc_dtype: str | None = None):
    """Batched Jacobi-PCG back-half: ``nekbone_pcg_update_kernel`` over
    ``nrhs`` right-hand sides that share one operator.

    The weight ``c`` and the diagonal ``invd`` (with its inverse) are read
    once per slab residency and shared by every RHS; stitch, axpys and the
    two partials run per RHS (``alpha_ref`` (1, b) SMEM, ``rtz_ref`` and
    ``rcr_ref`` (1, 1, b)).  Vector refs carry a leading ``nrhs`` axis.
    """
    f = _accum(x_ref.dtype, acc_dtype)
    c = _box_layers(cz_ref, cxy_ref[...].astype(f), f)
    invd = _load(invd_ref, f)
    diag = [1.0 / i for i in invd]
    rtzs, rcrs = [], []
    for j in range(nrhs):
        x, z, rtz, rcr = _pcg_update_one(
            _load(x_ref, f, j), _load(p_ref, f, j), _load(z_ref, f, j),
            _load(w_ref, f, j), addb_ref[j, 0].astype(f),
            addt_ref[j, 0].astype(f), alpha_ref[0, j], invd, diag, c,
            z_out.dtype)
        rtzs.append(rtz)
        rcrs.append(rcr)
        _store(x_out, x, j)
        _store(z_out, z, j)
    rtz_ref[0] = _lane_row(rtzs).astype(rtz_ref.dtype)
    rcr_ref[0] = _lane_row(rcrs).astype(rcr_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "grid", "sz", "interpret",
                                             "acc_dtype"))
def nekbone_pcg_update_block_pallas(x: jnp.ndarray, p: jnp.ndarray,
                                    z: jnp.ndarray, w: jnp.ndarray,
                                    addb: jnp.ndarray, addt: jnp.ndarray,
                                    alpha: jnp.ndarray, invd: jnp.ndarray,
                                    cx: jnp.ndarray, cy: jnp.ndarray,
                                    cz: jnp.ndarray, *, n: int,
                                    grid: tuple[int, int, int], sz: int,
                                    interpret: bool = False,
                                    acc_dtype: str | None = None):
    """Multi-output pallas_call for the batched Jacobi-PCG update kernel.

    Args mirror :func:`nekbone_pcg_update_pallas` with a leading RHS axis
    on the vector fields ((b, n, n^2, E)) and shifted planes ((b, EZ//sz,
    n^2, EX*EY)), a (1, b) ``alpha``, and one shared (n, n^2, E)
    ``invd``.  Returns ``(x_new, z_new, rtz_parts, rcr_parts)`` with
    partials (EZ//sz, b).
    """
    ex, ey, ez = grid
    nrhs, E = x.shape[0], x.shape[-1]
    assert E == ex * ey * ez and ez % sz == 0, (grid, sz, E)
    slab = ex * ey
    be = sz * slab
    nblk = ez // sz
    f = _accum(x.dtype, acc_dtype)
    field = _field_spec(n, be, (nrhs,))
    plane = _plane_spec(n, slab, (nrhs,))
    x, z, rtz, rcr = _call(
        functools.partial(nekbone_pcg_update_block_kernel, nrhs=nrhs,
                          acc_dtype=acc_dtype),
        name=f"nekbone_pcg_update_b{nrhs}_n{n}_sz{sz}{_acc_tag(acc_dtype)}",
        grid=(nblk,),
        in_specs=[field] * 4 + [plane, plane, _SMEM, _field_spec(n, be),
                                _full_spec((n * n, be)), _lane_spec(n, be)],
        out_specs=(field, field, _part_spec(nrhs), _part_spec(nrhs)),
        out_shape=(jax.ShapeDtypeStruct((nrhs, n, n * n, E), x.dtype),
                   jax.ShapeDtypeStruct((nrhs, n, n * n, E), z.dtype),
                   _part_shape(nblk, f, nrhs), _part_shape(nblk, f, nrhs)),
        interpret=interpret, aliases=UPDATE_ALIASES,
        operands=(x, p, z, w, addb, addt,
                  jnp.asarray(alpha, f).reshape(1, nrhs), invd,
                  _plane_factor(cx, cy, be), _z_lanes(cz, slab)))
    return x, z, _part(rtz), _part(rcr)


def nekbone_cheb_apply_kernel(rext_ref, kf_ref, kb_ref, d_ref, gext_ref,
                              mxy_ref, mzext_ref, fl_ref, pm_ref, cxy_ref,
                              cz_ref, coef_ref, z_ref, rtz_ref, ur, us, ut,
                              d_s, res_s, z_s, ad_s, *, ex: int, ey: int,
                              sz: int, k: int, halo: int,
                              acc_dtype: str | None = None):
    """Chebyshev preconditioner application, one slab block (DESIGN.md §9.3).

    Evaluates ``z = q_k(A) r`` in one VMEM residency over ``L = sz +
    2*halo`` slabs (``halo = k``) by the incremental-residual recurrence
    (scalars precomputed host-side, ``core/precond.cheb_scalars``):

        d   = coef[0,0] * r;   z = d;   res = r
        for i in 1..k:
            res -= A d                      (masked, window-assembled)
            d    = coef[i,0] * d + coef[i,1] * res
            z   += d
        rtz = sum_own(r * c * z)            (the PCG beta numerator)

    ``z`` is rounded through the storage dtype before the reduction.  Refs
    as :func:`nekbone_ax_powers_kernel`, with ``coef`` (k+1, 2) in SMEM,
    ``z_ref`` the owned ``(n, n^2, be)`` block, ``rtz`` ``(1, 1, 1)`` and
    seven VMEM scratch windows.
    """
    L = sz + 2 * halo
    slab = ex * ey
    be, ho = sz * slab, halo * slab
    n = rext_ref.shape[0]
    f = _accum(rext_ref.dtype, acc_dtype)
    fl = fl_ref[...]
    mxy = mxy_ref[...].astype(f)
    r = lambda kk: rext_ref[kk].astype(f)                # noqa: E731

    def init(kk, c):
        d_s[kk] = coef_ref[0, 0] * r(kk)
        z_s[kk] = d_s[kk]
        res_s[kk] = r(kk)
        return c

    jax.lax.fori_loop(0, n, init, 0)
    for i in range(1, k + 1):
        _window_apply(lambda kk: d_s[kk], ad_s, kf_ref, kb_ref, d_ref,
                      lambda m, kk: gext_ref[m, kk].astype(f),
                      lambda kk: mzext_ref[0, kk].astype(f) * mxy, pm_ref,
                      fl, (ur, us, ut), f, ex=ex, ey=ey, L=L)

        def step(kk, c, i=i):
            res = res_s[kk] - ad_s[kk]
            res_s[kk] = res
            dn = coef_ref[i, 0] * d_s[kk] + coef_ref[i, 1] * res
            d_s[kk] = dn
            z_s[kk] = z_s[kk] + dn
            return c

        jax.lax.fori_loop(0, n, step, 0)
    cxy = cxy_ref[...].astype(f)

    def own(kk, acc):
        z = _own(z_s[kk], ho, be).astype(z_ref.dtype)
        z_ref[kk] = z
        c = cz_ref[kk].astype(f) * cxy
        return acc + _own(r(kk), ho, be) * c * z.astype(f)

    acc = jax.lax.fori_loop(0, n, own, jnp.zeros((n * n, be), f))
    rtz_ref[0] = _total(acc).astype(rtz_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "grid", "sz", "k",
                                             "interpret", "acc_dtype",
                                             "grid_order"))
def nekbone_cheb_apply_pallas(rext: jnp.ndarray, D: jnp.ndarray,
                              Dt: jnp.ndarray, gext: jnp.ndarray,
                              mx: jnp.ndarray, my: jnp.ndarray,
                              mzext: jnp.ndarray, cx: jnp.ndarray,
                              cy: jnp.ndarray, cz: jnp.ndarray,
                              coef: jnp.ndarray, *, n: int,
                              grid: tuple[int, int, int], sz: int, k: int,
                              interpret: bool = False,
                              acc_dtype: str | None = None,
                              grid_order: str = "parallel"):
    """Multi-output pallas_call for the Chebyshev-apply kernel.

    Args:
      rext: (n, n^2, (EZ//sz)*Lee) halo'd residual windows
        (:func:`sstep_extend_field` with ``halo = k``); gext:
        (3, n, n^2, (EZ//sz)*Lee); mzext: (EZ//sz, L, n); cz: (EZ, n);
        coef: (k+1, 2) Chebyshev recurrence scalars.

    Returns ``(z, rtz_parts)``: z ``(n, n^2, E)`` in the storage dtype of
    ``rext``, rtz partials ``(EZ//sz, 1)`` in the accumulation dtype.
    """
    ex, ey, ez = grid
    assert ez % sz == 0 and k >= 1, (grid, sz, k)
    halo = k
    L = sz + 2 * halo
    slab = ex * ey
    Lee = L * slab
    be = sz * slab
    nblk = ez // sz
    E = nblk * be
    assert rext.shape == (n, n * n, nblk * Lee), (rext.shape, (nblk, Lee))
    assert coef.shape == (k + 1, 2), coef.shape
    f = _accum(rext.dtype, acc_dtype)
    kf, kb, d = _operator_operands(D, Dt, f)
    win = _field_spec(n, Lee)
    z, rtz = _call(
        functools.partial(nekbone_cheb_apply_kernel, ex=ex, ey=ey, sz=sz,
                          k=k, halo=halo, acc_dtype=acc_dtype),
        name=(f"nekbone_cheb_apply_n{n}_sz{sz}_k{k}{_acc_tag(acc_dtype)}"
              f"{_order_tag(grid_order)}"),
        grid=(nblk,),
        in_specs=[win] + _op_specs(n) + _window_specs(n, Lee)
        + [_full_spec((n * n, be)), _lane_spec(n, be), _SMEM],
        out_specs=(_field_spec(n, be), _part_spec()),
        out_shape=(jax.ShapeDtypeStruct((n, n * n, E), rext.dtype),
                   _part_shape(nblk, f)),
        interpret=interpret, grid_order=grid_order,
        scratch=_scratch(n, Lee, f, 7),
        operands=(rext, kf, kb, d,
                  *_window_operands(gext, mx, my, mzext, grid, n, L, f),
                  _plane_factor(cx, cy, be), _z_lanes(cz, slab),
                  jnp.asarray(coef, f)))
    return z, _part(rtz)


def nekbone_interp_kernel(u_ref, ar_ref, as_ref, mt_ref, v_ref, *,
                          acc_dtype: str | None = None):
    """Tensor-product GLL-to-GLL interpolation of one element block.

    The p-multigrid transfer operator (DESIGN.md §13): ``mt`` — ``(nin,
    nout)``, rows indexed by the *input* grid — is contracted along i
    (``ar = kron(I, mt^T)``), then j (``as = kron(mt^T, I)``), both per
    k-layer on the MXU, then along k with ``mt``'s scalars (SMEM).
    ``mt = J^T`` prolongs, ``mt = J`` restricts.  Element-local, so slab
    splits give identical results.

    Refs: u_ref ``(nin, nin^2, be)``, ar ``(nin*nout, nin^2)``, as
    ``(nout^2, nin*nout)``, v_ref ``(nout, nout^2, be)``.
    """
    f = _accum(u_ref.dtype, acc_dtype)
    _store(v_ref, _interp_layers(_load(u_ref, f), ar_ref[...].astype(f),
                                as_ref[...].astype(f), mt_ref))


def _interp_operators(mt: jnp.ndarray):
    """In-plane operators ``(kron(I, mt^T), kron(mt^T, I))`` of the
    interpolation by ``mt`` (nin, nout), in ``mt``'s dtype."""
    nin, nout = mt.shape
    return (jnp.kron(jnp.eye(nin, dtype=mt.dtype), mt.T),
            jnp.kron(mt.T, jnp.eye(nout, dtype=mt.dtype)))


def _interp_layers(layers, ar, as_, mt):
    """Interpolate a list of ``nin`` k-layers ``(nin^2, lanes)`` to
    ``nout`` layers: i then j per layer (MXU), then k (scalars of ``mt``).
    """
    w = [_mm(as_, _mm(ar, a)) for a in layers]
    return _tdir(mt, w, True)


@functools.partial(jax.jit, static_argnames=("nin", "nout", "grid", "sz",
                                             "interpret", "acc_dtype",
                                             "grid_order"))
def nekbone_interp_pallas(u: jnp.ndarray, mt: jnp.ndarray, *, nin: int,
                          nout: int, grid: tuple[int, int, int], sz: int,
                          interpret: bool = False,
                          acc_dtype: str | None = None,
                          grid_order: str = "parallel") -> jnp.ndarray:
    """pallas_call wrapper for :func:`nekbone_interp_kernel`.

    ``u`` is ``(nin, nin^2, E)``; ``mt`` is ``(nin, nout)``; returns
    ``(nout, nout^2, E)`` in the storage dtype of ``u``.  Blocked by
    z-slabs of ``sz`` element layers like the rest of the slab family.
    """
    ex, ey, ez = grid
    assert ez % sz == 0, (grid, sz)
    be = sz * ey * ex
    nblk = ez // sz
    E = nblk * be
    assert u.shape == (nin, nin * nin, E), (u.shape, (nin, E))
    assert mt.shape == (nin, nout), (mt.shape, (nin, nout))
    f = _accum(u.dtype, acc_dtype)
    mtf = jnp.asarray(mt, f)
    ar, as_ = _interp_operators(mtf)
    v = _call(
        functools.partial(nekbone_interp_kernel, acc_dtype=acc_dtype),
        name=(f"nekbone_interp_{nin}to{nout}_sz{sz}{_acc_tag(acc_dtype)}"
              f"{_order_tag(grid_order)}"),
        grid=(nblk,),
        in_specs=[_field_spec(nin, be), _full_spec(ar.shape),
                  _full_spec(as_.shape), _SMEM],
        out_specs=_field_spec(nout, be),
        out_shape=jax.ShapeDtypeStruct((nout, nout * nout, E), u.dtype),
        interpret=interpret, grid_order=grid_order,
        operands=(u, ar, as_, mtf))
    return v
