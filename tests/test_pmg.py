"""p-multigrid preconditioner (core/pmg.py + precond._pcg_pmg, ISSUE 9).

Pins, in order:

* the degree ladder and the transfer-matrix algebra (polynomial
  exactness up to the coarse degree, interpolation-sense
  restrict∘prolong identity, endpoint 0/1 rows);
* the Pallas interpolation kernel against the dense XLA reference —
  fp64 BITWISE, across slab splits (same dot_general pattern by
  construction);
* the fused V-cycle PCG driver against the XLA reference V-cycle
  through reference PCG (trajectory parity, the same way the Chebyshev
  driver was verified);
* SPD-contract evidence: symmetry of the reference cycle in the
  c-weighted inner product and positivity of <r, M r>;
* the iteration-count acceptance: pmg beats cheb4 on a shared case.

The E=1024/n=10 paper-case acceptance (<= half of cheb4's iterations to
rtol 1e-8) runs in benchmarks/pmg_smoke.py and the pcg_pmg bench rows.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import repro.core.cg as cg_mod
import repro.core.pmg as pmg
import repro.core.precond as pc
from repro.core.gs import ds_sum_local
from repro.core.nekbone import NekboneCase
from repro.kernels import nekbone_ax as ax_kernels

GRID = (2, 2, 4)


def _case(n=5, grid=GRID):
    return NekboneCase(n=n, grid=grid, dtype=jnp.float64,
                       ax_impl="pallas_fused_cg_v2")


def _masked_rhs(rng, case):
    u = jnp.asarray(rng.normal(size=case.mask.shape), case.dtype)
    return ds_sum_local(u * case.mask, case.grid) * case.mask


# ---------------------------------------------------------------------------
# ladder + transfer matrices (satellite 3)
# ---------------------------------------------------------------------------

def test_pmg_degree_ladder():
    from repro.core.cost import pmg_degrees

    assert pmg_degrees(10) == (10, 5, 3, 2)
    assert pmg_degrees(5) == (5, 3, 2)
    assert pmg_degrees(6) == (6, 3, 2)
    assert pmg_degrees(2) == (2,)


def test_interp_matrix_polynomial_exactness():
    """J (nf, nc) reproduces polynomials up to degree nc-1 exactly, and
    J^T-restriction of a fine polynomial sampled back is exact for the
    identity composition R_mat @ P_mat on the coarse grid."""
    from repro.core.sem import gll_points_weights

    for nf, nc in ((10, 5), (5, 3), (3, 2), (7, 4)):
        J = pmg.gll_interp_matrix(nf, nc)
        xf = np.asarray(gll_points_weights(nf)[0], np.float64)
        xc = np.asarray(gll_points_weights(nc)[0], np.float64)
        for p in range(nc):                # all polynomials in the space
            np.testing.assert_allclose(J @ xc ** p, xf ** p,
                                       rtol=0, atol=5e-14)


def test_interp_matrix_endpoint_rows_exact():
    for nf, nc in ((10, 5), (5, 3), (3, 2)):
        J = pmg.gll_interp_matrix(nf, nc)
        e0 = np.zeros(nc)
        e0[0] = 1.0
        eN = np.zeros(nc)
        eN[-1] = 1.0
        np.testing.assert_array_equal(J[0], e0)     # exact 0/1, not approx
        np.testing.assert_array_equal(J[-1], eN)


def test_prolong_then_restrict_identity_on_coarse():
    """Interpolation-sense identity: sampling the prolonged field back on
    the coarse GLL grid recovers it exactly — gll_interp_matrix(nc, nf) @
    gll_interp_matrix(nf, nc) == I (the fine space contains the coarse
    polynomials)."""
    for nf, nc in ((10, 5), (5, 3), (3, 2)):
        back = pmg.gll_interp_matrix(nc, nf) @ pmg.gll_interp_matrix(nf, nc)
        np.testing.assert_allclose(back, np.eye(nc), rtol=0, atol=5e-14)


def test_interp3_prolong_then_sample_back_identity_3d(x64, rng):
    """The 3-D composition through interp3 (and hence the kernel path)
    inherits the 1-D identity."""
    nf, nc = 5, 3
    E = 8
    ec = jnp.asarray(rng.normal(size=(E, nc, nc, nc)))
    up = pmg.interp3(ec, jnp.asarray(pmg.gll_interp_matrix(nf, nc)))
    back = pmg.interp3(up, jnp.asarray(pmg.gll_interp_matrix(nc, nf)))
    np.testing.assert_allclose(np.asarray(back), np.asarray(ec),
                               rtol=0, atol=1e-13)


# ---------------------------------------------------------------------------
# Pallas interpolation kernel vs dense XLA reference (satellite 3)
# ---------------------------------------------------------------------------

def _assert_roundoff(got, ref, u, M):
    """|got - ref| within the fp64 round-off of three length-n sums: each
    partial sum is bounded by ||M||_inf^3 max|u|, and each of the 3n
    additions rounds by at most eps of it (both orders, hence 2x)."""
    M = np.asarray(M)
    n = max(M.shape)
    scale = np.abs(M).sum(axis=1).max() ** 3 * np.abs(np.asarray(u)).max()
    bound = 2 * 3 * n * np.finfo(np.float64).eps * scale
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=0,
                               atol=bound)


@pytest.mark.parametrize("sz", [1, 2, 4])
@pytest.mark.parametrize("nf,nc", [(5, 3), (3, 2), (10, 5)])
def test_interp_kernel_bitwise_vs_reference(x64, rng, sz, nf, nc):
    """Restriction AND prolongation directions, every slab split, against
    the dense XLA reference.  The kernel contracts layer by layer
    (Kronecker matmuls with the elements on lanes), so the two agree to
    fp64 round-off of the three length-n contractions, not bitwise."""
    ex, ey, ez = GRID
    E = ex * ey * ez
    u = jnp.asarray(rng.normal(size=(E, nf, nf, nf)))
    J = jnp.asarray(pmg.gll_interp_matrix(nf, nc))
    # restriction direction: contract fine axes with J's rows (mt = J)
    ref = pmg.interp3(u, J.T)
    got = ax_kernels.nekbone_interp_pallas(
        ax_kernels.to_lanes(u.reshape(E, nf ** 3), nf), J, nin=nf, nout=nc,
        grid=GRID, sz=sz, interpret=True)
    _assert_roundoff(ax_kernels.from_lanes(got, nc), ref.reshape(E, nc ** 3),
                     u, J)
    # prolongation direction (mt = J.T)
    ec = jnp.asarray(rng.normal(size=(E, nc, nc, nc)))
    refp = pmg.interp3(ec, J)
    gotp = ax_kernels.nekbone_interp_pallas(
        ax_kernels.to_lanes(ec.reshape(E, nc ** 3), nc), J.T, nin=nc,
        nout=nf, grid=GRID, sz=sz, interpret=True)
    _assert_roundoff(ax_kernels.from_lanes(gotp, nf),
                     refp.reshape(E, nf ** 3), ec, J)


def test_ops_nekbone_interp_wrapper(x64, rng):
    """The ops-layer wrapper takes the natural (n_out, n_in) matrix and
    natural-shape fields."""
    from repro.kernels.ops import nekbone_interp

    ex, ey, ez = GRID
    E = ex * ey * ez
    nf, nc = 5, 3
    u = jnp.asarray(rng.normal(size=(E, nf, nf, nf)))
    R = jnp.asarray(pmg.gll_interp_matrix(nf, nc)).T     # (nc, nf)
    got = nekbone_interp(u, R, GRID, interpret=True)
    ref = pmg.interp3(u, R)
    assert got.shape == (E, nc, nc, nc)
    _assert_roundoff(got, ref, u, R)


# ---------------------------------------------------------------------------
# spec construction + spellings
# ---------------------------------------------------------------------------

def test_make_preconditioner_pmg_spellings(x64):
    case = _case()
    spec = pc.make_preconditioner("pmg", D=case.D, g=case.g, grid=case.grid,
                                  mask=case.mask, c=case.c)
    assert isinstance(spec, pc.PMGPrecond)
    assert spec.ns == (5, 3, 2) and spec.k == pc.PMG_DEFAULT_K
    spec3 = pc.make_preconditioner("pmg[cheb3]", D=case.D, g=case.g,
                                   grid=case.grid, mask=case.mask, c=case.c)
    assert spec3.k == 3
    with pytest.raises(ValueError, match="pmg spellings"):
        pc.make_preconditioner("pmg[cheb]", D=case.D, g=case.g,
                               grid=case.grid, mask=case.mask, c=case.c)
    with pytest.raises(ValueError, match="pmg spellings"):
        pc.make_preconditioner("pmgX", D=case.D, g=case.g, grid=case.grid,
                               mask=case.mask, c=case.c)


def test_pmg_needs_coarsenable_degree(x64):
    case = _case(n=2)
    with pytest.raises(ValueError, match="n >= 3"):
        pc.make_preconditioner("pmg", D=case.D, g=case.g, grid=case.grid,
                               mask=case.mask, c=case.c)


# ---------------------------------------------------------------------------
# SPD contract + reference-cycle algebra
# ---------------------------------------------------------------------------

def test_vcycle_reference_symmetric_positive(x64, rng):
    case = _case()
    spec = case.precond_spec("pmg")
    M = pmg.pmg_vcycle_reference(spec, D=case.D, g=case.g, grid=case.grid,
                                 mask=case.mask, c=case.c)
    u = _masked_rhs(rng, case)
    v = _masked_rhs(rng, case)
    dot = case.dot()
    a1 = float(dot(u, M(v)))
    a2 = float(dot(M(u), v))
    assert abs(a1 - a2) <= 1e-12 * abs(a1)
    assert float(dot(u, M(u))) > 0.0


# ---------------------------------------------------------------------------
# fused driver parity + acceptance
# ---------------------------------------------------------------------------

def test_pcg_pmg_matches_reference_pcg(x64, rng):
    """Fused pmg-PCG trajectory == XLA reference V-cycle under reference
    PCG, to fp64 round-off (the Chebyshev driver's verification pattern)."""
    case = _case()
    f = _masked_rhs(rng, case)
    spec = case.precond_spec("pmg")
    M = pmg.pmg_vcycle_reference(spec, D=case.D, g=case.g, grid=case.grid,
                                 mask=case.mask, c=case.c)
    ref = cg_mod.cg(case.ax_full, f, dot=case.dot(), max_iter=8, tol=0.0,
                    precond=M)
    res = pc.pcg_fused_v2_fixed_iters(f, D=case.D, g=case.g, grid=case.grid,
                                      niter=8, precond=spec, mask=case.mask,
                                      c=case.c, interpret=True)
    hr = np.asarray(ref.rnorm_history)[:9]
    hf = np.asarray(res.rnorm_history)[:9]
    np.testing.assert_allclose(hf, hr, rtol=1e-10)
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(ref.x),
                               rtol=0, atol=1e-10)


@pytest.mark.parametrize("sz", [1, 2, 4])
def test_pcg_pmg_invariant_to_slab_split(x64, rng, sz):
    """The level-0 slab split only changes fp associations."""
    case = _case()
    f = _masked_rhs(rng, case)
    spec = case.precond_spec("pmg")
    base = pc.pcg_fused_v2_fixed_iters(
        f, D=case.D, g=case.g, grid=case.grid, niter=6, precond=spec,
        mask=case.mask, c=case.c, interpret=True, sz=4, cheb_sz=4)
    got = pc.pcg_fused_v2_fixed_iters(
        f, D=case.D, g=case.g, grid=case.grid, niter=6, precond=spec,
        mask=case.mask, c=case.c, interpret=True, sz=sz, cheb_sz=sz)
    np.testing.assert_allclose(np.asarray(got.rnorm_history),
                               np.asarray(base.rnorm_history), rtol=1e-10)


def test_pmg_beats_cheb4_iterations(x64, rng):
    """The headline: tolerance-driven pmg-PCG needs at most half the
    iterations of cheb4 on a shared (small) case.  The paper-scale
    E=1024/n=10 version of this check is benchmarks/pmg_smoke.py."""
    case = _case(n=7, grid=(2, 2, 4))
    f = _masked_rhs(rng, case)
    r0 = float(jnp.sqrt(jnp.abs(jnp.sum(f * case.c * f))))
    tol = 1e-8 * r0
    kw = dict(D=case.D, g=case.g, grid=case.grid, tol=tol, max_iter=200,
              mask=case.mask, c=case.c, interpret=True)
    chb = pc.cg_fused_tol(f, precond=case.precond_spec("cheb4"), **kw)
    pmgr = pc.cg_fused_tol(f, precond=case.precond_spec("pmg"), **kw)
    assert float(pmgr.rnorm) <= float(chb.rnorm) * 10
    assert int(pmgr.iters) <= int(chb.iters) // 2, (
        f"pmg {int(pmgr.iters)} vs cheb4 {int(chb.iters)}")


def test_case_solve_routes_pmg(x64):
    """precond='pmg' flows through the registry (v2 fixed-iter + tol) and
    the reference path on non-fused ax_impls."""
    case = _case()
    res, _ = case.solve_manufactured(niter=6, precond="pmg")
    assert res.precond == "pmg" and res.pipeline == "fused_v2"
    ref_case = NekboneCase(n=5, grid=GRID, dtype=jnp.float64,
                           ax_impl="fused")
    ref, _ = ref_case.solve_manufactured(niter=6, precond="pmg")
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(ref.x),
                               rtol=0, atol=1e-10)


# ---------------------------------------------------------------------------
# satellite 1: b>1 on an s-step case — explicit, warned fallback
# ---------------------------------------------------------------------------

def test_sstep_batched_falls_back_to_block_with_warning(x64, rng):
    from repro.core import solvers as solvers_mod

    case = NekboneCase(n=5, grid=GRID, dtype=jnp.float64,
                       ax_impl="pallas_sstep_v3")
    f1 = _masked_rhs(rng, case)
    f = jnp.stack([f1, 2.0 * f1])
    solvers_mod._SSTEP_BLOCK_WARNED = False
    with pytest.warns(UserWarning, match="no batched s-step kernel"):
        res = case.solve(f, niter=4)
    assert res.pipeline == "fused_v2_rhs2"
    assert res.x.shape == f.shape
    # one-time: a second batched solve stays silent
    import warnings as _warnings

    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        case.solve(f, niter=4)
