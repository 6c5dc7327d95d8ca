"""Mixed-precision fused CG (DESIGN.md §7): policy resolution, storage
parity against the fp64 oracle, kernel accumulation dtypes, and the
iterative-refinement floor — including the paper's E=1024, n=10 acceptance
case."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import cg as cg_mod
from repro.core.cg_fused import (cg_fused_fixed_iters, cg_fused_v2_fixed_iters,
                                 cg_ir_fixed_iters)
from repro.core.nekbone import NekboneCase
from repro.core.precision import POLICIES, PrecisionPolicy, resolve_policy


# ---------------------------------------------------------------------------
# policy resolution
# ---------------------------------------------------------------------------

def test_policy_registry_and_resolution():
    assert POLICIES["f64"].storage_dtype == jnp.dtype("float64")
    assert POLICIES["bf16"].storage_dtype == jnp.dtype(jnp.bfloat16)
    assert POLICIES["bf16"].accum_dtype == jnp.dtype("float32")
    assert POLICIES["bf16"].itemsize == 2
    assert not POLICIES["bf16"].refine and POLICIES["bf16_ir"].refine
    # the refined bf16 policy widens x and the operator data, NOT r/p/w
    assert POLICIES["bf16_ir"].x_storage_dtype == jnp.dtype("float32")
    assert POLICIES["bf16_ir"].op_storage_dtype == jnp.dtype("float32")
    assert POLICIES["bf16_ir"].storage_dtype == jnp.dtype(jnp.bfloat16)
    # unrefined policies keep x/op at the storage dtype
    assert POLICIES["bf16"].x_storage_dtype == jnp.dtype(jnp.bfloat16)
    assert POLICIES["f32"].op_storage_dtype == jnp.dtype("float32")

    # name, instance, and dtype-inference paths
    assert resolve_policy("bf16_ir") is POLICIES["bf16_ir"]
    pol = PrecisionPolicy("custom", "float32", "float64")
    assert resolve_policy(pol) is pol
    assert resolve_policy(None, jnp.float32) is POLICIES["f32"]
    assert resolve_policy(None, jnp.bfloat16) is POLICIES["bf16"]
    with pytest.raises(ValueError):
        resolve_policy("fp8")
    with pytest.raises(ValueError):
        resolve_policy(None)

    # eps is the storage dtype's machine epsilon (parity tolerance scale):
    # 8-bit significand -> 2^-7, 24-bit -> 2^-23
    assert POLICIES["bf16"].eps == pytest.approx(2.0 ** -7)
    assert POLICIES["f32"].eps == pytest.approx(2.0 ** -23)


# ---------------------------------------------------------------------------
# storage parity: low-precision pipelines track the fp64 oracle to a
# tolerance derived from the storage dtype's eps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("variant", ["v1", "v2"])
def test_storage_parity_vs_fp64_reference(x64, precision, variant):
    """bf16/f32-storage residual histories track the fp64 reference.

    Tolerance: 64 * storage-eps relative, compared only while the
    reference is above its own comparison floor (once fp64 CG converges to
    round-off on a tiny case, relative comparison to a stalled
    low-precision run is meaningless).
    """
    case = NekboneCase(n=5, grid=(2, 3, 2), dtype=jnp.float64)
    _, f = case.manufactured()
    niter = 8
    ref = np.asarray(cg_mod.cg_fixed_iters(case.ax_full, f, niter=niter,
                                           dot=case.dot()).rnorm_history)

    kw = dict(D=case.D, g=case.g, grid=case.grid, niter=niter,
              precision=precision)
    if variant == "v1":
        res = cg_fused_fixed_iters(f, mask=case.mask, c=case.c, **kw)
    else:
        res = cg_fused_v2_fixed_iters(f, **kw)

    pol = POLICIES[precision]
    assert res.x.dtype == pol.storage_dtype
    # the history lives in the accumulation dtype, not storage
    assert res.rnorm_history.dtype == pol.accum_dtype

    got = np.asarray(res.rnorm_history, np.float64)
    tol = 64.0 * pol.eps
    alive = ref / ref[0] > tol          # reference above the storage floor
    rel = np.abs(got[alive] - ref[alive]) / ref[alive]
    assert rel.max() <= tol, (precision, variant, rel.max(), tol)


def test_bf16_storage_is_deterministically_rounded(x64):
    """The v2 kernels round p/r through storage before partials: two runs
    must agree bitwise, and the returned fields must be genuine bf16."""
    case = NekboneCase(n=4, grid=(2, 2, 2), dtype=jnp.float64)
    _, f = case.manufactured()
    a = cg_fused_v2_fixed_iters(f, D=case.D, g=case.g, grid=case.grid,
                                niter=5, precision="bf16")
    b = cg_fused_v2_fixed_iters(f, D=case.D, g=case.g, grid=case.grid,
                                niter=5, precision="bf16")
    assert a.x.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(a.x, np.float32),
                                  np.asarray(b.x, np.float32))


# ---------------------------------------------------------------------------
# kernel accumulation dtype (the acc side of the policy)
# ---------------------------------------------------------------------------

def test_kernel_partials_in_accum_dtype(rng, x64):
    """bf16 operands with explicit acc dtypes: partials come back in acc,
    fields in storage, and wider accumulation is at least as accurate."""
    from repro.core.sem import derivative_matrix
    from repro.kernels import nekbone_ax as _ax

    E, n = 4, 4
    D64 = np.asarray(derivative_matrix(n))
    u64 = rng.normal(size=(E, n ** 3))
    g64 = rng.normal(size=(E, 6, n ** 3))

    u = jnp.asarray(u64, jnp.bfloat16)
    D = jnp.asarray(D64, jnp.bfloat16)
    g = jnp.asarray(g64, jnp.bfloat16)
    mask = jnp.ones((E, n ** 3), jnp.bfloat16)

    outs = {}
    for acc in ("float32", "float64"):
        w, pap = _ax.nekbone_ax_pap_pallas(
            _ax.to_lanes(u, n), D, D.T, _ax.metric_lanes(g, n),
            _ax.to_lanes(mask, n), n=n, block_e=2, interpret=True,
            acc_dtype=acc)
        assert w.dtype == jnp.bfloat16
        assert pap.dtype == jnp.dtype(acc)
        outs[acc] = float(jnp.sum(pap))

    # same bf16 operands, so both accumulations see identical inputs; the
    # f64 reference on the *rounded* operands is the exact answer.
    from repro.core.ax import ax_local_fused
    u_ref = jnp.asarray(np.asarray(u, np.float64)).reshape(E, n, n, n)
    g_ref = jnp.asarray(np.asarray(g, np.float64)).reshape(E, 6, n, n, n)
    D_ref = jnp.asarray(np.asarray(D, np.float64))
    w_ref = ax_local_fused(u_ref, D_ref, g_ref)
    pap_ref = float(jnp.sum(u_ref.reshape(E, n ** 3)
                            * w_ref.reshape(E, n ** 3)))
    err32 = abs(outs["float32"] - pap_ref)
    err64 = abs(outs["float64"] - pap_ref)
    assert err64 <= err32 + 1e-12 * abs(pap_ref)
    assert err32 <= 1e-5 * abs(pap_ref)      # f32 accumulation, not bf16


# ---------------------------------------------------------------------------
# iterative refinement: bf16-priced streams, fp64-class floors
# ---------------------------------------------------------------------------

def test_ir_recovers_f64_floor_small(x64):
    """bf16_ir on a small case: outer residuals reach the fp64 floor of
    the same fixed-iteration budget, and the refined solution is f64."""
    case = NekboneCase(n=6, grid=(2, 2, 4), dtype=jnp.float64)
    _, f = case.manufactured()
    niter = 40
    ref = cg_mod.cg_fixed_iters(case.ax_full, f, niter=niter,
                                dot=case.dot())
    rel_ref = float(ref.rnorm / ref.rnorm_history[0])

    ir = cg_ir_fixed_iters(f, D=case.D, g=case.g, grid=case.grid,
                           niter=niter, precision="bf16_ir")
    assert ir.x.dtype == jnp.float64    # refined solution in b's precision
    hist = np.asarray(ir.rnorm_history, np.float64)
    assert np.all(np.isfinite(hist))
    rel_ir = float(ir.rnorm / ir.rnorm_history[0])
    assert rel_ir <= rel_ref, (rel_ir, rel_ref)


def test_ir_monotone_outer_residuals(x64):
    """Each refinement sweep must not increase the true residual (the
    inner solves run full-length, past the CG residual transient)."""
    case = NekboneCase(n=5, grid=(2, 2, 2), dtype=jnp.float64)
    _, f = case.manufactured()
    ir = cg_ir_fixed_iters(f, D=case.D, g=case.g, grid=case.grid,
                           niter=30, precision="bf16_ir", outer_iters=3)
    hist = np.asarray(ir.rnorm_history, np.float64)
    assert hist.shape == (4,)
    assert np.all(hist[1:] <= hist[:-1] * 1.05), hist


def test_ir_f32_policy_reaches_f32_class_floor(x64):
    case = NekboneCase(n=5, grid=(2, 2, 2), dtype=jnp.float64)
    _, f = case.manufactured()
    ir = cg_ir_fixed_iters(f, D=case.D, g=case.g, grid=case.grid,
                           niter=30, precision="f32_ir")
    rel = float(ir.rnorm / ir.rnorm_history[0])
    assert rel < 1e-8                    # two f32 sweeps compound past 1e-8
    assert int(ir.iters) == 60


def test_ir_v1_variant(x64):
    """The refinement driver also runs over the v1 (flat-block) pipeline."""
    case = NekboneCase(n=4, grid=(2, 2, 2), dtype=jnp.float64)
    _, f = case.manufactured()
    ir = cg_ir_fixed_iters(f, D=case.D, g=case.g, grid=case.grid,
                           niter=20, precision="bf16_ir", outer_iters=2,
                           variant="v1", mask=case.mask, c=case.c)
    hist = np.asarray(ir.rnorm_history, np.float64)
    assert hist[-1] < hist[0]


# the acceptance case (ISSUE 3): paper protocol size, interpret mode
def test_ir_paper_case_matches_f64_floor(x64):
    """bf16_ir matches the fp64 ``cg_fixed_iters`` 100-iteration residual
    floor on the paper's E=1024, n=10 case (§V protocol), interpret mode.

    bf16 storage alone stalls ~50x above the fp64 floor on this case; the
    refinement loop's five full-length sweeps recover it (DESIGN.md §7).
    """
    case = NekboneCase(n=10, grid=(8, 8, 16), dtype=jnp.float64)
    _, f = case.manufactured()
    ref = cg_mod.cg_fixed_iters(case.ax_full, f, niter=100, dot=case.dot())
    rel_ref = float(ref.rnorm / ref.rnorm_history[0])

    ir = cg_ir_fixed_iters(f, D=case.D, g=case.g, grid=case.grid,
                           niter=100, precision="bf16_ir")
    rel_ir = float(ir.rnorm / ir.rnorm_history[0])
    assert rel_ir <= rel_ref, (rel_ir, rel_ref)


# ---------------------------------------------------------------------------
# NekboneCase precision field
# ---------------------------------------------------------------------------

def test_case_precision_field_storage_policies():
    case = NekboneCase(n=4, grid=(2, 2, 2), precision="bf16",
                       ax_impl="pallas_fused_cg_v2")
    assert jnp.dtype(case.dtype) == jnp.dtype(jnp.bfloat16)
    res, _ = case.solve_manufactured(niter=4)
    assert res.x.dtype == jnp.bfloat16
    assert np.all(np.isfinite(np.asarray(res.rnorm_history, np.float64)))


def test_case_precision_field_refined(x64):
    case = NekboneCase(n=4, grid=(2, 2, 2), dtype=jnp.float64,
                       precision="bf16_ir", ax_impl="pallas_fused_cg_v2")
    assert jnp.dtype(case.dtype) == jnp.dtype(jnp.float64)  # outer precision
    res, u_ex = case.solve_manufactured(niter=20)
    assert res.x.dtype == jnp.float64
    hist = np.asarray(res.rnorm_history, np.float64)
    assert hist[-1] < hist[0]


def test_config_precision_field():
    from repro.configs.nekbone import paper_case

    cfg = paper_case(64, precision="bf16_ir")
    assert cfg.precision == "bf16_ir"
    case = cfg.make_case(n=4, grid=(2, 2, 2))
    assert case.precision == "bf16_ir"
