"""v2 fused-CG pipeline: slab gather-scatter + merged update (DESIGN.md §3.4).

Three layers are pinned:

* the slab dots kernel's in-block direct-stiffness summation (+ host plane
  stitch) against ``ds_sum_local`` over randomized element grids — the
  assembly must be *bitwise* the same pair sums;
* the merged vector-update kernel against the XLA axpy reference, including
  the cross-block plane corrections;
* the whole ``cg_fused_v2_fixed_iters`` against ``cg_fixed_iters`` to fp64
  round-off in interpret mode, plus fp32/bf16 behaviour through
  ``NekboneCase(ax_impl='pallas_fused_cg_v2')``.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import cg as cg_mod
from repro.core.ax import ax_local_fused
from repro.core.cg_fused import cg_fused_v2_fixed_iters
from repro.core.gs import ds_sum_local
from repro.core.nekbone import NekboneCase
from repro.kernels import ops


def _continuous_field(rng, case):
    """A continuous, masked field — the CG invariant the pap identity needs."""
    u = jnp.asarray(rng.normal(size=case.mask.shape), case.dtype)
    return ds_sum_local(u, case.grid) * case.mask


def _random_slab_setup(seed):
    """Randomized (EX, EY, EZ, n, sz) with sz a divisor of EZ."""
    r = np.random.default_rng(seed)
    grid = tuple(int(v) for v in r.integers(1, 4, size=3))
    n = int(r.integers(3, 7))
    divisors = [d for d in range(1, grid[2] + 1) if grid[2] % d == 0]
    sz = int(r.choice(divisors))
    return grid, n, sz


# ---------------------------------------------------------------------------
# Slab kernel: in-block assembly + plane stitch vs ds_sum_local
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_slab_assembly_matches_ds_sum_local(rng, x64, seed):
    grid, n, sz = _random_slab_setup(seed)
    case = NekboneCase(n=n, grid=grid, dtype=jnp.float64)
    p = _continuous_field(rng, case)

    # beta = 0 makes the kernel's direction p == r; the zeros passed as
    # p_prev must not leak through.
    p_out, w, pap = ops.nekbone_ax_dots_slab(
        jnp.zeros_like(p), p, case.D, case.g, grid, beta=0.0, sz=sz,
        interpret=True)

    np.testing.assert_array_equal(np.asarray(p_out), np.asarray(p))
    # the in-kernel gather-scatter performs the same pair sums as the
    # reference assembly; round-off tolerance only covers the operator's
    # matmul-vs-einsum contraction order.
    w_ref = ds_sum_local(ax_local_fused(p, case.D, case.g) * case.mask, grid)
    scale = float(np.abs(np.asarray(w_ref)).max()) + 1e-300
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref),
                               rtol=1e-12, atol=1e-12 * scale,
                               err_msg=f"{grid=} {n=} {sz=}")
    # continuity identity: pap partials (pre-assembly) sum to p·c·Ap.
    pap_ref = float(jnp.sum(p * case.c * w_ref))
    assert abs(float(pap) - pap_ref) <= 1e-12 * max(abs(pap_ref), 1e-30)


def test_slab_beta_folds_direction_update(rng, x64):
    """p = r + beta * p_prev inside the kernel, exactly."""
    grid, n, sz = (2, 2, 4), 4, 2
    case = NekboneCase(n=n, grid=grid, dtype=jnp.float64)
    p_prev = _continuous_field(rng, case)
    r = _continuous_field(rng, case)
    beta = 0.73
    p_out, w, pap = ops.nekbone_ax_dots_slab(
        p_prev, r, case.D, case.g, grid, beta=beta, sz=sz, interpret=True)
    p_ref = r + beta * p_prev
    np.testing.assert_allclose(np.asarray(p_out), np.asarray(p_ref),
                               rtol=1e-15, atol=1e-15)
    w_ref = ds_sum_local(ax_local_fused(p_ref, case.D, case.g) * case.mask,
                         grid)
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref),
                               rtol=1e-12, atol=1e-12)


# ---------------------------------------------------------------------------
# Merged vector-update kernel vs the XLA axpy reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("grid,n,sz", [((2, 3, 4), 4, 2), ((1, 2, 3), 5, 1),
                                       ((2, 2, 2), 3, 2)])
def test_update_kernel_vs_xla_reference(rng, x64, grid, n, sz):
    ex, ey, ez = grid
    case = NekboneCase(n=n, grid=grid, dtype=jnp.float64)
    E = case.mesh.nelt
    shp = (E, n, n, n)
    x, p, r, w = (jnp.asarray(rng.normal(size=shp), jnp.float64)
                  for _ in range(4))
    nblk = ez // sz
    pln = ey * ex * n * n
    addb = jnp.asarray(rng.normal(size=(nblk, pln)), jnp.float64)
    addt = jnp.asarray(rng.normal(size=(nblk, pln)), jnp.float64)
    alpha = 0.37

    x2, r2, rtz = ops.nekbone_cg_update(x, p, r, w, alpha, grid,
                                        addb=addb, addt=addt, sz=sz,
                                        interpret=True)

    # reference: stitch the planes into w, then the two axpys + weighted norm
    vb = np.asarray(w).reshape(nblk, sz, ey, ex, n, n, n).copy()
    vb[:, 0, :, :, 0, :, :] += np.asarray(addb).reshape(nblk, ey, ex, n, n)
    vb[:, -1, :, :, -1, :, :] += np.asarray(addt).reshape(nblk, ey, ex, n, n)
    w_full = vb.reshape(shp)
    x_ref = np.asarray(x) + alpha * np.asarray(p)
    r_ref = np.asarray(r) - alpha * w_full
    rtz_ref = float(np.sum(r_ref * np.asarray(case.c) * r_ref))

    np.testing.assert_allclose(np.asarray(x2), x_ref, rtol=1e-15, atol=1e-15)
    np.testing.assert_allclose(np.asarray(r2), r_ref, rtol=1e-14, atol=1e-14)
    assert abs(float(rtz) - rtz_ref) <= 1e-12 * abs(rtz_ref)


# ---------------------------------------------------------------------------
# Solver parity: v2 fused CG vs cg_fixed_iters, fp64 interpret mode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,grid,niter", [
    (4, (2, 2, 2), 10),
    (5, (2, 3, 2), 8),
    (10, (2, 2, 4), 5),     # the paper's degree (n=10, E=1024-class) scaled
])
def test_cg_fused_v2_matches_fixed_iters_fp64(x64, n, grid, niter):
    case = NekboneCase(n=n, grid=grid, dtype=jnp.float64)
    _, f = case.manufactured()

    ref = cg_mod.cg_fixed_iters(case.ax_full, f, niter=niter, dot=case.dot())
    fused = cg_fused_v2_fixed_iters(f, D=case.D, g=case.g, grid=case.grid,
                                    niter=niter, mask=case.mask, c=case.c,
                                    interpret=True)

    h_ref = np.asarray(ref.rnorm_history)
    h_fus = np.asarray(fused.rnorm_history)
    assert h_fus.shape == h_ref.shape
    # rtol pins the different summation association to fp64 round-off; the
    # atol floor covers entries that already converged to machine epsilon
    # relative to the initial residual.
    np.testing.assert_allclose(h_fus, h_ref, rtol=1e-12,
                               atol=1e-13 * h_ref[0])
    xs = np.abs(np.asarray(ref.x)).max() + 1e-300
    np.testing.assert_allclose(np.asarray(fused.x), np.asarray(ref.x),
                               atol=1e-12 * xs)


@pytest.mark.parametrize("sz", [1, 2, 4])
def test_cg_fused_v2_invariant_to_slab_split_fp64(x64, sz):
    """The slab split changes only the partial-sum association."""
    case = NekboneCase(n=4, grid=(2, 2, 4), dtype=jnp.float64)
    _, f = case.manufactured()
    ref = cg_mod.cg_fixed_iters(case.ax_full, f, niter=6, dot=case.dot())
    fused = cg_fused_v2_fixed_iters(f, D=case.D, g=case.g, grid=case.grid,
                                    niter=6, sz=sz, interpret=True)
    np.testing.assert_allclose(np.asarray(fused.rnorm_history),
                               np.asarray(ref.rnorm_history), rtol=1e-12,
                               atol=1e-13 * float(ref.rnorm_history[0]))


def test_cg_fused_v2_through_case_fp32():
    """NekboneCase(ax_impl='pallas_fused_cg_v2') dispatches fixed-iter solves
    to the two-kernel pipeline and converges like the XLA path in fp32."""
    fused_case = NekboneCase(n=6, grid=(2, 2, 2), dtype=jnp.float32,
                             ax_impl="pallas_fused_cg_v2")
    res, u_ex = fused_case.solve_manufactured(niter=40)
    assert int(res.iters) == 40
    hist = np.asarray(res.rnorm_history)
    assert np.isfinite(hist).all()
    assert hist[-1] < hist[0] * 1e-3, "v2 fused CG must actually converge"

    xla_case = NekboneCase(n=6, grid=(2, 2, 2), dtype=jnp.float32,
                           ax_impl="fused")
    ref, _ = xla_case.solve_manufactured(niter=40)
    h_ref = np.asarray(ref.rnorm_history)
    # fp32 trajectories drift once round-off accumulates through alpha/beta;
    # early history must agree tightly (fp64 parity is pinned above).
    np.testing.assert_allclose(hist[:15], h_ref[:15], rtol=5e-3)
    np.testing.assert_allclose(hist, h_ref, rtol=0.5, atol=1e-4 * hist[0])
    err_f = float(fused_case.solution_error(res.x, u_ex))
    err_x = float(xla_case.solution_error(ref.x, u_ex))
    assert err_f <= err_x * 1.1 + 1e-6


def test_cg_fused_v2_bf16_runs_and_converges():
    """bf16 fields with f32 in-kernel accumulation (the TPU target dtype)."""
    case = NekboneCase(n=4, grid=(2, 2, 2), dtype=jnp.bfloat16,
                       ax_impl="pallas_fused_cg_v2")
    res, _ = case.solve_manufactured(niter=5)
    assert res.x.dtype == jnp.bfloat16
    hist = np.asarray(res.rnorm_history, np.float32)
    assert np.isfinite(hist).all()
    assert hist[-1] < hist[0]


# ---------------------------------------------------------------------------
# Guard rails: the v2 path must refuse non-box fields
# ---------------------------------------------------------------------------

def test_cg_fused_v2_rejects_foreign_mask():
    case = NekboneCase(n=4, grid=(2, 2, 2), dtype=jnp.float32)
    _, f = case.manufactured()
    bad_mask = case.mask.at[0, 1, 1, 1].set(0.0)   # interior node masked
    with pytest.raises(ValueError, match="structured box mask"):
        cg_fused_v2_fixed_iters(f, D=case.D, g=case.g, grid=case.grid,
                                niter=2, mask=bad_mask, interpret=True)


def test_cg_fused_v2_rejects_nondiagonal_metric(rng):
    from repro.core.geom import random_spd_metric

    case = NekboneCase(n=4, grid=(2, 2, 2), dtype=jnp.float32)
    _, f = case.manufactured()
    g_bad = jnp.asarray(random_spd_metric(rng, case.mesh.nelt, 4),
                        jnp.float32)
    with pytest.raises(ValueError, match="axis-aligned"):
        cg_fused_v2_fixed_iters(f, D=case.D, g=g_bad, grid=case.grid,
                                niter=2, interpret=True)


# ---------------------------------------------------------------------------
# Case routes: the box fields are checked once per case, not per solve
# ---------------------------------------------------------------------------

def _validate_spans(rec) -> int:
    return sum(1 for r in rec.records if r["type"] == "span"
               and r["name"] == "driver.validate")


def test_case_checks_its_box_fields_once():
    from repro.obs import trace

    case = NekboneCase(n=4, grid=(2, 2, 2), dtype=jnp.float32,
                       ax_impl="pallas_fused_cg_v2", precond="jacobi")
    _, f = case.manufactured()
    with trace.recording() as first:
        case.solve(f, tol=1e-3, max_iter=4)
    with trace.recording() as second:
        case.solve(f, tol=1e-3, max_iter=4)
    assert [r["attrs"]["route"] for r in second.records
            if r["name"] == "solve"] == ["v2_tol"]
    assert _validate_spans(first) == 1
    assert "driver.box_fields_reused" not in first.counters
    assert _validate_spans(second) == 0
    assert second.counters["driver.box_fields_reused"] == 1


@pytest.mark.parametrize("field", ["mask", "g"])
def test_case_checks_a_reassigned_field_on_the_next_solve(rng, field):
    from repro.core.geom import random_spd_metric

    case = NekboneCase(n=4, grid=(2, 2, 2), dtype=jnp.float32,
                       ax_impl="pallas_fused_cg_v2")
    _, f = case.manufactured()
    case.solve(f, niter=2)
    if field == "mask":
        case.mask = case.mask.at[0, 1, 1, 1].set(0.0)  # interior node masked
        match = "structured box mask"
    else:
        case.g = jnp.asarray(random_spd_metric(rng, case.mesh.nelt, 4),
                             jnp.float32)
        match = "axis-aligned"
    with pytest.raises(ValueError, match=match):
        case.solve(f, niter=2)


def test_slab_axis_factors_are_made_once_per_shape_and_dtype():
    f32 = ops.slab_axis_factors((2, 2, 3), 4, jnp.float32)
    assert ops.slab_axis_factors([2, 2, 3], 4, "float32") is f32
    bf16 = ops.slab_axis_factors((2, 2, 3), 4, jnp.bfloat16)
    assert bf16 is not f32
    assert {a.dtype for group in bf16 for a in group} == {
        jnp.dtype(jnp.bfloat16)}
    assert [a.shape for a in f32[0]] == [(2, 4), (2, 4), (3, 4)]
    # first asked for inside a jit trace: still concrete afterwards
    import jax

    jax.jit(lambda: ops.slab_axis_factors((5, 1, 3), 4, jnp.float32)[0][0]
            .sum())()
    mask_x = ops.slab_axis_factors((5, 1, 3), 4, jnp.float32)[0][0]
    assert not isinstance(mask_x, jax.core.Tracer)
    np.testing.assert_array_equal(np.asarray(mask_x)[0], [0, 1, 1, 1])


@pytest.mark.parametrize("route", ["v2", "v2_tol", "block", "ir", "sstep"])
def test_case_route_matches_the_direct_driver_bitwise(route):
    from repro.core import precond as precond_mod
    from repro.core import solvers
    from repro.core.cg_block import cg_block_fixed_iters
    from repro.core.cg_fused import cg_ir_fixed_iters
    from repro.core.cg_sstep import cg_sstep_fixed_iters

    case = NekboneCase(
        n=4, grid=(2, 2, 2), dtype=jnp.float32,
        precision="bf16_ir" if route == "ir" else None,
        ax_impl="pallas_sstep_v3" if route == "sstep"
        else "pallas_fused_cg_v2")
    _, f = case.manufactured()
    full = dict(D=case.D, g=case.g, grid=case.grid, mask=case.mask,
                c=case.c)
    kw = dict(niter=5)
    if route == "block":
        f = jnp.stack([f, 0.5 * f[::-1]])
    elif route == "v2_tol":
        kw = dict(tol=1e-3, max_iter=20, precond="jacobi")
    assert solvers.route_name(case, b=f.shape[0] if f.ndim == 5 else 1,
                              niter=kw.get("niter"),
                              pc_name=kw.get("precond")) == route
    res = case.solve(f, **kw)
    if route == "v2":
        direct = cg_fused_v2_fixed_iters(f, niter=5, **full)
    elif route == "v2_tol":
        direct = precond_mod.cg_fused_tol(
            f, tol=1e-3, max_iter=20, precond=case.precond_spec("jacobi"),
            **full)
    elif route == "block":
        direct = cg_block_fixed_iters(f, niter=5, **full)
    elif route == "ir":
        direct = cg_ir_fixed_iters(f, niter=5, precision="bf16_ir",
                                   variant="v2", **full)
    else:
        direct = cg_sstep_fixed_iters(f, niter=5, s=case.s,
                                      theta=case._sstep_theta, **full)
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(direct.x))
    np.testing.assert_array_equal(np.asarray(res.iters_taken),
                                  np.asarray(direct.iters_taken))


def test_cg_fused_v2_tol_and_precond_stay_fused():
    """tol-driven and preconditioned v2 solves route to the fused drivers
    (core/precond.py, DESIGN.md §9) — no fall-back to the XLA path."""
    case = NekboneCase(n=4, grid=(2, 2, 2), dtype=jnp.float32,
                       ax_impl="pallas_fused_cg_v2")
    res, _ = case.solve_manufactured(tol=1e-4, max_iter=100)
    assert int(res.iters) < 100
    assert float(res.rnorm) <= 1e-4
    assert res.rnorm_history.shape == (101,)      # padded to max_iter + 1
    res_pc, _ = case.solve_manufactured(niter=10, precond="jacobi")
    assert res_pc.rnorm_history.shape == (11,)
    assert np.isfinite(np.asarray(res_pc.rnorm_history,
                                  np.float64)).all()


# ---------------------------------------------------------------------------
# In-place contract: the v2 loop kernels alias their carried fields
# (kernels/nekbone_ax.SLAB_ALIASES / UPDATE_ALIASES, DESIGN.md §3.4)
# ---------------------------------------------------------------------------

_AL_GRID, _AL_N, _AL_SZ, _AL_B = (2, 2, 4), 4, 2, 3
ALIASED = {
    "nekbone_ax_slab_pallas": "slab",
    "nekbone_ax_slab_block_pallas": "slab",
    "nekbone_cg_update_pallas": "update",
    "nekbone_cg_update_block_pallas": "update",
    "nekbone_pcg_update_pallas": "update",
    "nekbone_pcg_update_block_pallas": "update",
}


def _aliased_call(name, rng):
    """(fn, operands) of one aliased wrapper on a small grid, interpret
    mode, random f32 operands of the wrapper's documented shapes."""
    from repro.kernels import nekbone_ax as K

    ex, ey, ez = _AL_GRID
    n, sz, E = _AL_N, _AL_SZ, ex * ey * ez
    lead = (_AL_B,) if "block" in name else ()
    b = _AL_B if lead else 1

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    field = lead + (n, n * n, E)
    axes = (arr(ex, n), arr(ey, n), arr(ez, n))
    if "slab" in name:
        D = arr(n, n)
        args = (arr(*field), arr(*field), D, D.T, arr(3, n, n * n, E),
                *axes, arr(1, b))
    else:
        planes = lead + (ez // sz, n * n, ex * ey)
        args = (arr(*field), arr(*field), arr(*field), arr(*field),
                arr(*planes), arr(*planes), arr(1, b))
        if "pcg" in name:
            args += (arr(n, n * n, E),)
        args += axes
    fn = getattr(K, name)
    return (lambda *a: fn(*a, n=n, grid=_AL_GRID, sz=sz, interpret=True),
            args)


@pytest.mark.parametrize("name", sorted(ALIASED))
def test_aliased_wrapper_leaves_its_inputs_unchanged(name):
    fn, args = _aliased_call(name, np.random.default_rng(7))
    before = [np.array(a) for a in args]
    first = [np.array(o) for o in fn(*args)]
    second = [np.array(o) for o in fn(*args)]
    for a, b in zip(args, before):
        np.testing.assert_array_equal(np.asarray(a), b)
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a, b)


def _pallas_eqns(jaxpr):
    """Every ``pallas_call`` equation of a (closed) jaxpr, nested ones too."""
    import jax

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_eqns(sub)


@pytest.mark.parametrize("name", sorted(ALIASED))
def test_wrapper_pallas_call_carries_the_alias_map(name):
    import jax

    from repro.kernels import nekbone_ax as K

    fn, args = _aliased_call(name, np.random.default_rng(0))
    (eqn,) = list(_pallas_eqns(jax.make_jaxpr(fn)(*args).jaxpr))
    want = K.SLAB_ALIASES if ALIASED[name] == "slab" else K.UPDATE_ALIASES
    assert dict(eqn.params["input_output_aliases"]) == want


@pytest.mark.parametrize("driver", ["jacobi", "coupled"])
def test_drivers_match_a_run_with_the_aliases_stripped(driver, monkeypatch):
    """In place or not, the loop computes the same iterates bitwise."""
    import jax

    from repro.core.cg_block import cg_block_coupled_tol
    from repro.core.precond import cg_fused_tol
    from repro.kernels import nekbone_ax as K

    case = NekboneCase(n=4, grid=(2, 2, 4), dtype=jnp.float32,
                       ax_impl="pallas_fused_cg_v2")
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(3,) + case.mask.shape), jnp.float32)
    f = jnp.stack([ds_sum_local(u[j], case.grid) for j in range(3)]) \
        * case.mask
    kw = dict(D=case.D, g=case.box_fields(), grid=case.grid, tol=1e-5,
              max_iter=60, precond=case.precond_spec("jacobi"), sz=2,
              interpret=True)

    def solve():
        if driver == "jacobi":
            return cg_fused_tol(f[0], **kw)
        return cg_block_coupled_tol(f, **kw)

    aliased = solve()
    jax.clear_caches()
    monkeypatch.setattr(K, "SLAB_ALIASES", {})
    monkeypatch.setattr(K, "UPDATE_ALIASES", {})
    try:
        plain = solve()
    finally:
        jax.clear_caches()
    assert 0 < int(aliased.iters_taken) < 60
    for a, b in ((aliased.x, plain.x), (aliased.iters_taken,
                                          plain.iters_taken),
                 (aliased.history, plain.history)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
