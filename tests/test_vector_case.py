"""The vector case (CEED BP6): three components under one scalar operator,
solved as one coupled system (core/cg_block.py coupled mode, the
``vector`` row of core/solvers.py).

Pins:

* at one component the coupled driver IS the single-RHS v2 driver, bit for
  bit in fp64: Jacobi PCG against ``cg_fused_tol``'s ``_pcg_jacobi``, plain
  CG against ``_cg_v2_tol``, and the fixed-iteration twin against its tol
  driver's trajectory;
* the coupling: one alpha and one beta for the whole field, so a field
  whose components are multiples of one scalar field takes the scalar
  solve's iterates;
* routing: a 3-component case goes to ``vector`` on the v2 pipeline (plain
  or Jacobi) and to the coupled ``reference`` otherwise; a batch of vector
  fields raises; ``auto`` resolves to v2;
* agreement with the benchmark's independent vector reference
  (chipbench/references/sem_vector_poisson.py) on seeded fields, through
  both routes.
"""
import importlib.util
import pathlib

import numpy as np
import pytest

import jax.numpy as jnp

import repro
from repro.core import solvers
from repro.core.cg_block import (cg_block_coupled_fixed_iters,
                                 cg_block_coupled_tol)
from repro.core.gs import ds_sum_local
from repro.core.nekbone import NekboneCase
from repro.core.precond import cg_fused_tol, pcg_fused_v2_fixed_iters

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _case(**kw):
    kw = {"n": 4, "grid": (2, 2, 4), "dtype": jnp.float64,
          "ax_impl": "pallas_fused_cg_v2", **kw}
    return NekboneCase(**kw)


def _field(rng, case, k=None):
    """Assembled, masked right-hand side(s): ``k`` components, or one."""
    shape = case.mask.shape if k is None else (k,) + case.mask.shape
    u = jnp.asarray(rng.normal(size=shape), case.dtype)
    if k is None:
        return ds_sum_local(u, case.grid) * case.mask
    return jnp.stack([ds_sum_local(u[j], case.grid) for j in range(k)]) \
        * case.mask


def _same(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# one component: the single-RHS drivers, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precond", ["jacobi", None])
def test_one_component_is_the_single_rhs_driver_bitwise(rng, x64, precond):
    case = _case()
    f = _field(rng, case)
    spec = case.precond_spec(precond) if precond else None
    kw = dict(D=case.D, g=case.box_fields(), grid=case.grid)
    ref = cg_fused_tol(f, tol=1e-9, max_iter=200, precond=spec, **kw)
    res = cg_block_coupled_tol(f[None], tol=1e-9, max_iter=200,
                               precond=spec, **kw)
    assert int(res.iters_taken) == int(ref.iters_taken)
    _same(res.x[0], ref.x)
    _same(res.history, ref.history)
    assert res.pipeline == "fused_v2_rhs1"
    assert res.precond == precond


def test_fixed_iteration_twin_is_the_tol_trajectory_continued(rng, x64):
    case = _case()
    f = _field(rng, case, 3)
    spec = case.precond_spec("jacobi")
    kw = dict(D=case.D, g=case.box_fields(), grid=case.grid, precond=spec)
    tol = cg_block_coupled_tol(f, tol=1e-6, max_iter=200, **kw)
    k = int(tol.iters_taken)
    fixed = cg_block_coupled_fixed_iters(f, niter=k, **kw)
    _same(fixed.x, tol.x)
    _same(fixed.history, tol.history[:k + 1])
    # and at one component it is the single-RHS fixed-iteration PCG
    solo = pcg_fused_v2_fixed_iters(f[0], niter=7, **kw)
    one = cg_block_coupled_fixed_iters(f[:1], niter=7, **kw)
    _same(one.x[0], solo.x)
    _same(one.history, solo.history)


def test_one_alpha_and_beta_for_the_whole_field(rng, x64):
    """Components that are multiples of one field span the scalar solve's
    Krylov space, so the coupled iterates are the scalar iterates scaled
    (independent per-lane scalars would give the same, but the stopping
    rule is the whole field's, which the iteration count shows)."""
    case = _case()
    f = _field(rng, case)
    spec = case.precond_spec("jacobi")
    kw = dict(D=case.D, g=case.box_fields(), grid=case.grid, precond=spec,
              niter=9)
    solo = cg_block_coupled_fixed_iters(f[None], **kw)
    vec = cg_block_coupled_fixed_iters(jnp.stack([f, 2 * f, -3 * f]), **kw)
    for j, s in enumerate((1.0, 2.0, -3.0)):
        np.testing.assert_allclose(np.asarray(vec.x[j]),
                                   s * np.asarray(solo.x[0]), rtol=1e-10,
                                   atol=1e-13)
    np.testing.assert_allclose(np.asarray(vec.history),
                               np.sqrt(14.0) * np.asarray(solo.history),
                               rtol=1e-10)


def test_coupled_driver_refuses_other_preconditioners(rng, x64):
    case = _case()
    with pytest.raises(ValueError, match="Jacobi"):
        cg_block_coupled_tol(_field(rng, case, 3), D=case.D,
                             g=case.box_fields(), grid=case.grid,
                             precond="cheb4")


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def test_a_vector_case_routes_to_the_coupled_row():
    vec = _case(components=3, dtype=jnp.float32, ax_impl="auto",
                precond="jacobi")
    assert vec.ax_impl == "pallas_fused_cg_v2"
    assert solvers.route_name(vec, pc_name="jacobi") == "vector"
    assert solvers.route_name(vec, pc_name=None, niter=10) == "vector"
    for pc in ("cheb", "pmg"):
        assert solvers.route_name(vec, pc_name=pc) == "reference"
    other = _case(components=3, dtype=jnp.float32, ax_impl="fused")
    assert solvers.route_name(other, pc_name="jacobi") == "reference"
    refined = _case(components=3, dtype=jnp.float32,
                    ax_impl="pallas_fused_cg_v2",
                    precision="bf16_ir")
    assert solvers.route_name(refined, pc_name="jacobi") == "reference"
    # a scalar Jacobi batch still runs per RHS
    assert solvers.route_name(_case(dtype=jnp.float32), b=3,
                              pc_name="jacobi") == \
        "block_loop"


def test_a_batch_of_vector_fields_raises(rng):
    vec = _case(components=3, dtype=jnp.float32)
    f = _field(rng, vec, 3)
    for call in (lambda: vec.solve(jnp.stack([f, f])),
                 lambda: vec.solve(f, b=2),
                 lambda: solvers.route_name(vec, b=2)):
        with pytest.raises(ValueError, match="batches of vector fields"):
            call()
    with pytest.raises(ValueError, match="3-component"):
        vec.solve(f[0])
    with pytest.raises(ValueError, match="components"):
        _case(components=2)


def test_the_manufactured_vector_problem_solves_through_repro_solve(x64):
    vec = _case(components=3, precond="jacobi")
    res = repro.solve(vec, None, tol=1e-9, max_iter=200)
    assert res.x.shape == (3,) + vec.mask.shape
    assert res.pipeline == "fused_v2_rhs3"
    _same(res.x[0], res.x[1])
    _same(res.x[1], res.x[2])


# ---------------------------------------------------------------------------
# against the benchmark's independent reference
# ---------------------------------------------------------------------------

def _reference_module():
    path = ROOT / "chipbench" / "references" / "sem_vector_poisson.py"
    spec = importlib.util.spec_from_file_location("sem_vector_poisson_test",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("ax_impl,route", [("pallas_fused_cg_v2", "vector"),
                                           ("fused", "reference")])
def test_both_routes_agree_with_the_vector_reference(ax_impl, route):
    """Seeded white noise on the interior nodes of each component, float32
    and Jacobi, stopped at rtol 1e-5 of the whole field's ``sqrt(r.M^-1
    r)``.  The answers agree to 2e-5 of the largest value (float32 CG's
    round-off after ~30 iterations of a condition number ~1e2 problem) and
    the iteration counts to one."""
    ref_mod = _reference_module()
    box = ref_mod.Box(4, (2, 2, 4))
    rng = np.random.default_rng(16)
    fu = rng.normal(size=box.gshape) * box.interior64
    w = box.precond_weight("jacobi") * box.interior64
    rtol = 1e-5
    tol = rtol * float(np.sqrt(np.sum(fu * w * fu)))
    x_ref, it_ref = box.solver(precond="jacobi")(
        jnp.asarray(fu, jnp.float32), rtol, niter=0, max_iter=500)
    case = _case(components=3, dtype=jnp.float32, ax_impl=ax_impl,
                 precond="jacobi")
    assert solvers.route_name(case, pc_name="jacobi") == route
    fe = jnp.asarray(box.to_elements(fu), jnp.float32)
    res = case.solve(fe, tol=tol, max_iter=500)
    assert abs(int(res.iters_taken) - int(it_ref)) <= 1
    xr = np.asarray(box.to_elements(np.asarray(x_ref)))
    diff = np.max(np.abs(np.asarray(res.x) - xr)) / np.max(np.abs(xr))
    assert diff < 2e-5
    assert box.residual64(fu, box.from_elements(np.asarray(res.x,
                                                           np.float64)),
                          w) < 2e-5


# ---------------------------------------------------------------------------
# the batched Jacobi-PCG update kernel
# ---------------------------------------------------------------------------

def test_pcg_update_block_kernel_matches_jnp_lane_by_lane(rng, x64):
    """``nekbone_pcg_update_block_pallas`` against the same update written
    in ``jnp`` for each lane: the plane stitch, ``x += alpha p``, ``z -=
    alpha invd w`` and the ``r.c.z``, ``r.c.r`` partials with ``r = z /
    invd``, one shared ``invd`` and weight for every lane."""
    from repro.core.geom import box_axis_factors, box_outer
    from repro.kernels import nekbone_ax as K

    n, grid, sz, b = 4, (2, 2, 4), 2, 3
    ex, ey, ez = grid
    E, n2, nblk = ex * ey * ez, n * n, ez // sz

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float64)

    x, p, z, w = (draw(b, n, n2, E) for _ in range(4))
    bot, top = draw(b, nblk, n2, ex * ey), draw(b, nblk, n2, ex * ey)
    addb, addt = K.shift_planes(bot, top)
    alpha = draw(1, b)
    invd = 1.0 / (1.0 + jnp.abs(draw(n, n2, E)))
    _, (cx, cy, cz) = box_axis_factors(grid, n)
    cx, cy, cz = (jnp.asarray(a, jnp.float64) for a in (cx, cy, cz))
    xo, zo, rtz, rcr = K.nekbone_pcg_update_block_pallas(
        x, p, z, w, addb, addt, alpha, invd, cx, cy, cz, n=n, grid=grid,
        sz=sz, interpret=True)
    assert rtz.shape == rcr.shape == (nblk, b)
    c = K.to_lanes(box_outer(cz, cy, cx).reshape(E, n ** 3), n)
    for j in range(b):
        a = alpha[0, j]
        v = K.stitch_planes(w[j], bot[j], top[j], grid, sz)
        zj = z[j] - a * invd * v
        np.testing.assert_allclose(np.asarray(xo[j]),
                                   np.asarray(x[j] + a * p[j]), rtol=1e-12,
                                   atol=1e-12)
        np.testing.assert_allclose(np.asarray(zo[j]), np.asarray(zj),
                                   rtol=1e-12, atol=1e-12)
        rj = zj / invd
        np.testing.assert_allclose(float(jnp.sum(rtz[:, j])),
                                   float(jnp.sum(rj * c * zj)), rtol=1e-12)
        np.testing.assert_allclose(float(jnp.sum(rcr[:, j])),
                                   float(jnp.sum(rj * c * rj)), rtol=1e-12)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_the_coupled_solve_is_spanned(rng):
    """``solve`` carries ``components``; the driver's host work runs in
    ``driver.prepare`` and its device loop in ``block.dispatch`` (``b``,
    ``coupled``); the case's checked fields open no ``driver.validate``."""
    from repro.obs import trace

    vec = _case(components=3, dtype=jnp.float32, precond="jacobi")
    f = _field(rng, vec, 3)
    vec.solve(f, tol=1e-3, max_iter=50)            # the case's field check
    with trace.recording() as rec:
        vec.solve(f, tol=1e-3, max_iter=50)
    spans = {r["name"]: r for r in rec.records if r["type"] == "span"}
    assert spans["solve"]["attrs"]["route"] == "vector"
    assert spans["solve"]["attrs"]["components"] == 3
    assert spans["block.dispatch"]["attrs"] == {"b": 3, "coupled": True}
    assert "driver.prepare" in spans
    assert "driver.validate" not in spans
    assert [r["depth"] for r in rec.records
            if r["name"] in ("driver.prepare", "block.dispatch")] == [1, 1]
