"""Grid-order parity (DESIGN.md §11.2, kernels/nekbone_ax.py).

Every ``grid_order`` point the autotuner sweeps with the slab size must
be *bitwise* identical at fp64 — the order only changes how the grid's
element blocks are issued, never a floating-point sum, so the autotuner
is free to pick any point of the sweep space without perturbing the
solver's round-off trajectory.  The checks run through the full
ops-level wrappers (plane stitch, halo windows, Gram blocks included) on
randomized grids.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.gs import ds_sum_local
from repro.core.nekbone import NekboneCase
from repro.kernels import ops
from repro.kernels.nekbone_ax import GRID_ORDERS


def _continuous_field(rng, case):
    u = jnp.asarray(rng.normal(size=case.mask.shape), case.dtype)
    return ds_sum_local(u, case.grid) * case.mask


def _random_case(seed):
    r = np.random.default_rng(seed)
    grid = tuple(int(v) for v in r.integers(1, 4, size=3))
    n = int(r.integers(3, 7))
    return NekboneCase(n=n, grid=grid, dtype=jnp.float64)


@pytest.mark.parametrize("seed", [0, 1])
def test_slab_kernel_bitwise_across_configs(rng, x64, seed):
    case = _random_case(seed)
    p_prev = _continuous_field(rng, case)
    r = _continuous_field(rng, case)

    ref = None
    for grid_order in GRID_ORDERS:
        p_out, w, pap = ops.nekbone_ax_dots_slab(
            p_prev, r, case.D, case.g, case.grid, beta=0.37,
            grid_order=grid_order, interpret=True)
        got = (np.asarray(p_out), np.asarray(w), float(pap))
        if ref is None:
            ref = got
            continue
        np.testing.assert_array_equal(got[0], ref[0],
                                      err_msg=f"{grid_order=}")
        np.testing.assert_array_equal(got[1], ref[1],
                                      err_msg=f"{grid_order=}")
        assert got[2] == ref[2], grid_order


@pytest.mark.parametrize("seed", [0, 1])
def test_powers_kernel_bitwise_across_configs(rng, x64, seed):
    case = _random_case(seed)
    p = _continuous_field(rng, case)
    r = _continuous_field(rng, case)

    ref = None
    for grid_order in GRID_ORDERS:
        basis, gram = ops.nekbone_ax_powers(
            p, r, case.D, case.g, case.grid, s=2, theta=1.3,
            grid_order=grid_order, interpret=True)
        got = (np.asarray(basis), np.asarray(gram))
        if ref is None:
            ref = got
            continue
        np.testing.assert_array_equal(got[0], ref[0],
                                      err_msg=f"{grid_order=}")
        np.testing.assert_array_equal(got[1], ref[1],
                                      err_msg=f"{grid_order=}")


@pytest.mark.parametrize("seed", [0, 1])
def test_cheb_kernel_bitwise_across_configs(rng, x64, seed):
    from repro.core import precond as pc

    case = _random_case(seed)
    r = _continuous_field(rng, case)
    coef = pc.cheb_scalars(2, 0.1, 1.9)

    ref = None
    for grid_order in GRID_ORDERS:
        out = ops.nekbone_cheb_precond(
            r, case.D, case.g, coef, case.grid, k=2,
            grid_order=grid_order, interpret=True)
        got = tuple(np.asarray(o) for o in out) \
            if isinstance(out, tuple) else (np.asarray(out),)
        if ref is None:
            ref = got
            continue
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(a, b,
                                          err_msg=f"{grid_order=}")


def test_full_solver_bitwise_across_configs(x64):
    """End to end: the whole v2 fixed-iteration solve is bitwise invariant
    to the configuration the autotuner picks."""
    from repro.core.cg_fused import cg_fused_v2_fixed_iters

    case = NekboneCase(n=4, grid=(2, 2, 2), dtype=jnp.float64)
    _, b = case.manufactured()

    ref = None
    for grid_order in GRID_ORDERS:
        res = cg_fused_v2_fixed_iters(
            b, D=case.D, g=case.g, grid=case.grid, niter=3,
            mask=case.mask, c=case.c, grid_order=grid_order,
            interpret=True)
        x = np.asarray(res.x)
        if ref is None:
            ref = x
            continue
        np.testing.assert_array_equal(x, ref,
                                      err_msg=f"{grid_order=}")
