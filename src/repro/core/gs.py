"""Gather-scatter (direct stiffness summation) for the structured box mesh.

Nekbone's ``gs_op`` sums the values of coincident nodes on shared element
faces/edges/corners so every copy holds the assembled value.  On the
structured box this reduces to, per direction, summing the two coincident
node planes of neighbouring elements — applied direction-by-direction the
edge/corner cases compose correctly (the operation is associative).

Distribution: elements are sharded along the *outermost* element-grid axis
(z).  Each shard performs the local summation, then exchanges its outer
boundary planes with its neighbours via ``lax.ppermute`` — the TPU analog of
Nekbone's nearest-neighbour MPI exchange.  The shard axis may be a hierarchy
(e.g. ``('pod', 'data')``): the exchange handles inner-axis neighbours and
the pod-boundary crossings with masked permutes, uniformly SPMD.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


__all__ = ["ds_sum_local", "ds_sum_sharded", "halo_exchange_z"]


def _box_view(u: jnp.ndarray, grid: tuple[int, int, int], lanes: bool):
    """6-D view of a field plus its (element, node) axis pairs per
    direction x, y, z.  Natural fields are ``(E, n, n, n)`` viewed as
    ``(ez, ey, ex, k, j, i)``; kernel-layout fields ``(n, n^2, E)``
    (kernels/nekbone_ax.py) as ``(k, j, i, ez, ey, ex)``."""
    ex, ey, ez = grid
    if lanes:
        n = u.shape[0]
        return (u.reshape(n, n, n, ez, ey, ex),
                ((5, 2), (4, 1), (3, 0)))
    n = u.shape[-1]
    return u.reshape(ez, ey, ex, n, n, n), ((2, 5), (1, 4), (0, 3))


def _at(ndim: int, axes: dict) -> tuple:
    """Index tuple selecting ``axes[a]`` on axis ``a``, all of the rest."""
    return tuple(axes.get(a, slice(None)) for a in range(ndim))


def ds_sum_local(u: jnp.ndarray, grid: tuple[int, int, int], *,
                 lanes: bool = False) -> jnp.ndarray:
    """Direct-stiffness sum over a local (un-sharded) element grid.

    Args:
      u:    ``(E, n, n, n)`` with ``E = EX*EY*EZ`` and e z-major
            (``e = (ez*EY + ey)*EX + ex``), local layout ``(k, j, i)``;
            or, with ``lanes``, the kernel layout ``(n, n^2, E)``.
      grid: ``(EX, EY, EZ)``.

    Returns the assembled field, same shape; coincident nodes carry the sum.
    """
    v, dirs = _box_view(u, grid, lanes)
    for (ea, na), ne in zip(dirs, grid):
        if ne > 1:  # face node n-1 of element e meets node 0 of e+1
            lo = _at(v.ndim, {ea: slice(None, -1), na: -1})
            hi = _at(v.ndim, {ea: slice(1, None), na: 0})
            s = v[lo] + v[hi]
            v = v.at[lo].set(s).at[hi].set(s)
    return v.reshape(u.shape)


def _flat_shift(v: jnp.ndarray, axis_names: tuple, up: bool) -> jnp.ndarray:
    """Value of ``v`` on the previous (``up``) / next (``down``) shard in the
    lexicographic flattening of ``axis_names``; zeros at the global boundary.

    Recursive carry scheme: a cyclic permute over the innermost axis moves
    every block one step; blocks that wrapped around (crossed an inner-group
    boundary) are corrected by recursively flat-shifting them over the outer
    axes — exactly positional addition with carries.
    """
    axis_names = tuple(axis_names)
    inner = axis_names[-1]
    n = jax.lax.axis_size(inner)
    idx = jax.lax.axis_index(inner)
    if up:
        perm = [(i, (i + 1) % n) for i in range(n)]
        at_edge = (idx == 0)                 # received a wrapped block
    else:
        perm = [((i + 1) % n, i) for i in range(n)]
        at_edge = (idx == n - 1)
    y = jax.lax.ppermute(v, inner, perm)
    edge = at_edge.astype(v.dtype)
    if len(axis_names) == 1:
        return y * (1.0 - edge)              # global boundary: zeros
    fix = _flat_shift(y * edge, axis_names[:-1], up)
    return y * (1.0 - edge) + fix * edge


def halo_exchange_z(top: jnp.ndarray, bottom: jnp.ndarray, axis_names):
    """Exchange z-boundary planes between lexicographic shard neighbours.

    Every shard sends ``top`` to the next shard and ``bottom`` to the
    previous shard in the flattened ``axis_names`` order (hierarchies like
    ``('pod', 'data')`` compose via carry permutes).  Returns
    ``(from_below, from_above)`` — zeros at the global boundaries, so
    callers can add unconditionally.
    """
    from_below = _flat_shift(top, axis_names, up=True)
    from_above = _flat_shift(bottom, axis_names, up=False)
    return from_below, from_above


def ds_sum_sharded(u: jnp.ndarray, grid_local: tuple[int, int, int],
                   axis_names, *, lanes: bool = False) -> jnp.ndarray:
    """Direct-stiffness sum where the z element axis is sharded.

    To be called *inside* ``shard_map``.  ``u`` is the shard-local block
    ``(E_local, n, n, n)`` (or ``(n, n^2, E_local)`` with ``lanes``);
    ``grid_local`` its local element grid ``(EX, EY, EZ_local)``.  The z
    interface planes between shards are exchanged with
    :func:`halo_exchange_z` and summed.

    The local pass runs first; because the cross-shard interface is a z-plane
    and the x/y summations act within that plane on each side independently,
    local-then-exchange produces the fully assembled result.
    """
    v, dirs = _box_view(ds_sum_local(u, grid_local, lanes=lanes),
                        grid_local, lanes)
    ea, na = dirs[2]
    top = _at(v.ndim, {ea: -1, na: -1})
    bottom = _at(v.ndim, {ea: 0, na: 0})
    from_below, from_above = halo_exchange_z(v[top], v[bottom], axis_names)
    v = v.at[bottom].add(from_below)
    v = v.at[top].add(from_above)
    return v.reshape(u.shape)
