"""obs/drift.py: jaxpr stream charging + the cost-model drift gate.

The full three-pipeline sweep lives in the obs-smoke CI leg
(benchmarks/obs_smoke.py); here the charging primitives are checked on
hand-counted programs and the gate semantics on one cheap pipeline.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import drift


# ---------------------------------------------------------------------------
# charge_streams / measure_* on hand-counted programs
# ---------------------------------------------------------------------------

def test_charge_streams_counts_leaf_operands():
    def f(a, b):
        return a + b  # one leaf eqn: reads both, writes one

    a = jnp.zeros((8,), jnp.float32)
    r, w = drift.measure_call_bytes(f, a, a)
    assert r == 2 * 8 * 4
    assert w == 8 * 4


def test_charge_streams_descends_structural_eqns():
    @jax.jit
    def inner(a):
        return a * 2.0

    def f(a):
        return inner(a) + 1.0

    a = jnp.zeros((4,), jnp.float32)
    r, w = drift.measure_call_bytes(f, a)
    # pjit boundary must not be double-charged: the mul inside plus the
    # add outside write 16 bytes each; reads are those two 16-byte
    # operands plus the scalar literals (4 bytes apiece)
    assert w == 2 * 16
    assert 2 * 16 <= r <= 2 * 16 + 16


def test_measure_iteration_bytes_charges_loop_body():
    def f(a):
        def body(c, _):
            return c + 1.0, None
        return jax.lax.scan(body, a, None, length=5)[0]

    a = jnp.zeros((16,), jnp.float32)
    r, w = drift.measure_iteration_bytes(f, a)
    # ONE iteration's body, not 5x: the add reads carry + scalar
    assert w == 16 * 4
    assert r >= 16 * 4
    assert r < 2 * 16 * 4 + 8  # carry + broadcast scalar, nothing else


def test_measure_iteration_bytes_requires_a_loop():
    with pytest.raises(ValueError):
        drift.measure_iteration_bytes(lambda a: a + 1.0,
                                      jnp.zeros((4,), jnp.float32))


# ---------------------------------------------------------------------------
# report / gate semantics
# ---------------------------------------------------------------------------

def test_unknown_pipeline_raises():
    with pytest.raises(ValueError):
        drift.check_bytes("made_up_pipeline")
    with pytest.raises(ValueError):
        drift.check_collectives("made_up_pipeline")


def test_report_to_dict_schema():
    row = drift.DriftRow(pipeline="p", check="c", measured=1, expected=1,
                         ok=True, ratio=1.0, band=(0.9, 1.1))
    rep = drift.DriftReport(rows=[row])
    assert rep.ok and rep.failures() == []
    d = rep.to_dict()
    assert d["schema"] == "model-drift/1"
    assert d["ok"] is True
    assert d["rows"][0]["pipeline"] == "p"
    assert "provenance" in d


def test_assert_no_drift_raises_on_failure():
    bad = drift.DriftRow(pipeline="p", check="c", measured=2, expected=1,
                         ok=False, detail="measured 2x the book")
    with pytest.raises(drift.ModelDriftError) as ei:
        drift.assert_no_drift(drift.DriftReport(rows=[bad]))
    assert "p/c" in str(ei.value)
    assert "measured 2x the book" in str(ei.value)


def test_assert_no_drift_passes_clean_report():
    good = drift.DriftRow(pipeline="p", check="c", measured=1, expected=1,
                          ok=True)
    rep = drift.assert_no_drift(drift.DriftReport(rows=[good]))
    assert rep.ok


# ---------------------------------------------------------------------------
# one real pipeline end to end (the other two + the byte bands run in the
# obs-smoke CI leg; collectives here are make_jaxpr-only and cheap)
# ---------------------------------------------------------------------------

def test_fused_v2_collective_contract():
    row = drift.check_collectives("fused_v2")
    assert row.ok, row.detail
    assert row.measured == {}  # single-device: collective-free


def test_sstep_collective_contract():
    row = drift.check_collectives("sstep_v3")
    assert row.ok, row.detail
    assert row.measured["cycle"] == {"ppermute": 2, "psum": 1}
    assert row.measured["update"] == {}


# ---------------------------------------------------------------------------
# the books still describe the compiled pipelines (tracing only)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pipeline", drift.DEFAULT_PIPELINES)
def test_pipeline_bytes_within_calibrated_band(pipeline):
    """The v2 loop bodies and the s-step cycle carry their state in the
    kernel layout: no per-iteration transpose pushes them out of band."""
    row = drift.check_bytes(pipeline)
    assert row.ok, row.detail


# ---------------------------------------------------------------------------
# the multi-RHS Jacobi books against what the block kernels' specs move
# ---------------------------------------------------------------------------

def _kernel_field_streams(fn, arg, n, E):
    """(reads, writes) in whole ``(n, n^2, E)`` fields of the
    ``pallas_call`` operands and results in ``fn``'s iteration loop: what
    the kernels' block specs stream from and to HBM."""
    closed = jax.make_jaxpr(fn)(arg)
    body = max((drift._loop_body(e) for e in drift._loops(closed.jaxpr,
                                                          [])),
               key=lambda b: sum(drift.charge_streams(b)))

    def fields(vs):
        out = 0
        for v in vs:
            shape = tuple(v.aval.shape)
            if shape[-3:] == (n, n * n, E):
                out += int(np.prod(shape[:-3], dtype=int))
        return out

    def pallas_calls(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e
            else:
                for sub in drift._subjaxprs(e):
                    yield from pallas_calls(sub)

    calls = list(pallas_calls(body))
    assert len(calls) == 2              # the slab and the update kernel
    return (sum(fields(e.invars) for e in calls),
            sum(fields(e.outvars) for e in calls))


@pytest.mark.parametrize("b", [1, 3])
def test_block_jacobi_kernels_move_the_booked_streams(b):
    """The coupled driver's two kernels per iteration read and write
    exactly the fields ``cost.multi_rhs_streams(b, "fused_v2_jacobi")``
    books (per RHS, so times b), and at b = 1 the single-RHS Jacobi
    driver's kernels move the same."""
    from repro.core import cost
    from repro.core.cg_block import cg_block_coupled_fixed_iters
    from repro.core.nekbone import NekboneCase
    from repro.core.precond import pcg_fused_v2_fixed_iters

    # E = 24 elements: no operator constant of shape (n, n^2, n^2) can pass
    # for a field
    case = NekboneCase(n=4, grid=(2, 2, 6), ax_impl="pallas_fused_cg_v2",
                       precond="jacobi")
    n, E = case.n, case.mask.shape[0]
    spec = case.precond_spec("jacobi")
    kw = dict(D=case.D, g=case.box_fields(), grid=case.grid, niter=2,
              precond=spec, sz=2, interpret=True)
    f = jnp.zeros((b,) + case.mask.shape, jnp.float32)
    r, w = _kernel_field_streams(
        lambda f: cg_block_coupled_fixed_iters(f, **kw).x, f, n, E)
    rb, wb = cost.multi_rhs_streams(b, "fused_v2_jacobi")
    assert (r, w) == (pytest.approx(b * rb), b * wb)
    if b == 1:
        assert _kernel_field_streams(
            lambda f: pcg_fused_v2_fixed_iters(f, **kw).x, f[0], n, E) == \
            (r, w)
