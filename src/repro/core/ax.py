"""Local spectral-element Poisson operator ``w = D^T (G (D u))`` per element.

Three implementations, mirroring the paper's version ladder:

* :func:`ax_local_listing1` — faithful transcription of the paper's Listing 1
  (the *original* Nekbone GPU version): two passes with ``ur/us/ut``
  materialized between them.  This is the paper-faithful baseline.
* :func:`ax_local_fused` — single fused expression; XLA is free to fuse the
  element-wise geometry application with the contractions (the analog of the
  *shared-memory* version: less HBM traffic, still compiler-scheduled).
* ``kernels/nekbone_ax.py`` (via :func:`ax_local`) — the Pallas kernel: the
  paper's optimized 2-D-thread-structure kernel re-derived for TPU (whole
  element block resident in VMEM, both stages fused, single HBM round-trip).

Layout: ``u[e, k, j, i]`` with ``i`` <-> x <-> the paper's ``r`` direction.
``D[a, b] = dl_b/dx(x_a)`` so an x-derivative contracts ``u``'s last axis with
``D``'s second axis.  ``g[e, m, k, j, i]`` with m in (rr, rs, rt, ss, st, tt).
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["ax_local_listing1", "ax_local_fused", "local_grad3", "local_grad3_t",
           "apply_metric", "ax_local"]


def local_grad3(u: jnp.ndarray, D: jnp.ndarray):
    """Reference-space gradient: returns (wr, ws, wt), each like ``u``.

    wr[e,k,j,i] = sum_l D[i,l] u[e,k,j,l]   (x / r direction)
    ws[e,k,j,i] = sum_l D[j,l] u[e,k,l,i]   (y / s direction)
    wt[e,k,j,i] = sum_l D[k,l] u[e,l,j,i]   (z / t direction)
    """
    wr = jnp.einsum("il,ekjl->ekji", D, u)
    ws = jnp.einsum("jl,ekli->ekji", D, u)
    wt = jnp.einsum("kl,elji->ekji", D, u)
    return wr, ws, wt


def local_grad3_t(ur: jnp.ndarray, us: jnp.ndarray, ut: jnp.ndarray,
                  D: jnp.ndarray) -> jnp.ndarray:
    """Transposed gradient (assembly of weak-form contributions).

    w[e,k,j,i] = sum_l D[l,i] ur[e,k,j,l] + D[l,j] us[e,k,l,i]
                 + D[l,k] ut[e,l,j,i]
    """
    w = jnp.einsum("li,ekjl->ekji", D, ur)
    w += jnp.einsum("lj,ekli->ekji", D, us)
    w += jnp.einsum("lk,elji->ekji", D, ut)
    return w


def apply_metric(wr, ws, wt, g):
    """Apply the 6-entry symmetric metric: (ur, us, ut) = G @ (wr, ws, wt).

    A 3-component ``g`` is the packed (rr, ss, tt) diagonal of an
    axis-aligned mesh (``NekboneCase.box_fields``).
    """
    if g.shape[1] == 3:
        return g[:, 0] * wr, g[:, 1] * ws, g[:, 2] * wt
    grr, grs, grt, gss, gst, gtt = (g[:, m] for m in range(6))
    ur = grr * wr + grs * ws + grt * wt
    us = grs * wr + gss * ws + gst * wt
    ut = grt * wr + gst * ws + gtt * wt
    return ur, us, ut


def ax_local_listing1(u: jnp.ndarray, D: jnp.ndarray,
                      g: jnp.ndarray) -> jnp.ndarray:
    """Paper Listing 1: two explicit passes with materialized intermediates.

    Pass 1 computes and *stores* ``ur, us, ut`` (in the original CUDA version
    these round-trip through global memory); pass 2 re-reads them for the
    transposed contraction.  Kept un-fused on purpose via
    ``jax.lax.optimization_barrier`` so benchmarks see the original version's
    memory traffic.
    """
    import jax

    wr, ws, wt = local_grad3(u, D)
    ur, us, ut = apply_metric(wr, ws, wt, g)
    # Force materialization between the two passes (global-memory round trip
    # in the original implementation).
    ur, us, ut = jax.lax.optimization_barrier((ur, us, ut))
    return local_grad3_t(ur, us, ut, D)


def ax_local_fused(u: jnp.ndarray, D: jnp.ndarray, g: jnp.ndarray) -> jnp.ndarray:
    """Single fused expression; XLA fuses geometry with the contractions."""
    wr, ws, wt = local_grad3(u, D)
    ur, us, ut = apply_metric(wr, ws, wt, g)
    return local_grad3_t(ur, us, ut, D)


def ax_local(u: jnp.ndarray, D: jnp.ndarray, g: jnp.ndarray, *,
             impl: str = "fused", **kw) -> jnp.ndarray:
    """Dispatch between implementations (``listing1`` | ``fused`` |
    ``pallas`` | ``pallas_fused_cg`` | ``pallas_fused_cg_v2``).

    The ``pallas_fused_cg*`` names select the step-fused CG pipelines
    (core/cg_fused.py); their *local operator* is the same Pallas kernel
    math, so standalone ``ax`` applications route to it here and only the
    solve loop differs.
    """
    if impl == "listing1":
        return ax_local_listing1(u, D, g)
    if impl == "fused":
        return ax_local_fused(u, D, g)
    if impl in ("pallas", "pallas_fused_cg", "pallas_fused_cg_v2",
                "pallas_sstep_v3"):
        from repro.kernels import ops as kernel_ops

        return kernel_ops.nekbone_ax(u, D, g, **kw)
    raise ValueError(f"unknown ax impl: {impl!r}")
