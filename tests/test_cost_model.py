"""The paper's cost model (Eq. 1-2) and our operator's adherence to it."""

import jax
import jax.numpy as jnp

from repro.core.cost import (CostModel, ax_local_flops, cg_iter_bytes,
                             cg_iter_flops, flops_per_dof, intensity,
                             roofline_gflops)


def test_eq1_values():
    # Paper §III-A with n = 10 (degree 9): 12*10 + 34 = 154 flops per DOF.
    assert flops_per_dof(10) == 154
    D = 1024 * 1000                       # 1024 elements at n=10
    assert cg_iter_flops(D, 10) == D * 154


def test_eq2_intensity():
    # I(10) = 154/240 ~= 0.6417 flop/byte in fp64 (paper Eq. 2).
    assert abs(intensity(10) - 154 / 240) < 1e-12
    # fp32 doubles it (DESIGN.md §5).
    assert abs(intensity(10, itemsize=4) - 154 / 120) < 1e-12


def test_paper_roofline_numbers():
    """§VI-B: theoretical peak BW gives 462 GF/s (P100) / 577 GF/s (V100)."""
    assert abs(roofline_gflops(720, 10) - 462) < 1.0
    assert abs(roofline_gflops(900, 10) - 577.5) < 1.0


def test_bytes_model():
    r, w = cg_iter_bytes(1000, itemsize=8)
    assert r == 24 * 1000 * 8 and w == 6 * 1000 * 8


def test_cost_model_dataclass():
    cm = CostModel(nelt=1024, n=10)
    assert cm.ndof == 1_024_000
    assert cm.cg_flops == 1_024_000 * 154
    assert abs(cm.intensity - 154 / 240) < 1e-12


def test_hlo_flops_match_cost_model():
    """Compiled local operator's dot flops ~= the 12n-term of Eq. 1.

    The contractions are 12n flops/DOF; the metric apply (elementwise, not
    dots) is the remaining 17.  Checks the implementation does not do
    redundant contraction work.
    """
    from repro.core.ax import ax_local_fused
    from repro.core.sem import derivative_matrix
    from repro.launch.hlo_analysis import analyze_hlo

    n, E = 10, 64
    u = jax.ShapeDtypeStruct((E, n, n, n), jnp.float32)
    g = jax.ShapeDtypeStruct((E, 6, n, n, n), jnp.float32)
    D = jnp.asarray(derivative_matrix(n), jnp.float32)
    compiled = jax.jit(lambda u, g: ax_local_fused(u, D, g)).lower(u, g).compile()
    got = analyze_hlo(compiled.as_text())["dot_flops"]
    want = E * n ** 3 * 12 * n            # 6 contractions x 2n flops
    assert 0.95 * want <= got <= 1.10 * want, (got, want)


def test_ax_local_flops_formula():
    assert ax_local_flops(1, 10) == 1000 * (120 + 17)


# ---------------------------------------------------------------------------
# v3 s-step stream accounting (DESIGN.md §8)
# ---------------------------------------------------------------------------

def test_sstep_s1_degenerates_to_v2():
    """4s+9 at s=1 is exactly the v2 budget — reads and writes separately,
    not just the total (the ISSUE's degeneracy acceptance)."""
    from repro.core.cost import (FUSED_V2_READ_STREAMS,
                                 FUSED_V2_WRITE_STREAMS, sstep_streams)

    r, w = sstep_streams(1)
    assert (r, w) == (FUSED_V2_READ_STREAMS, FUSED_V2_WRITE_STREAMS)


def test_sstep_cycle_budget():
    """Per cycle: powers kernel 5R + (2s-1)W, update (2s+2)R + 3W."""
    from repro.core.cost import sstep_cycle_streams, sstep_streams

    for s in (1, 2, 3, 4, 8):
        r, w = sstep_cycle_streams(s)
        assert (r, w) == (2 * s + 7, 2 * s + 2)
        ri, wi = sstep_streams(s)
        assert abs(ri - r / s) < 1e-12 and abs(wi - w / s) < 1e-12


def test_sstep_effective_streams_meets_target():
    """The acceptance number: <= 9 effective streams/iter at (s, sz) =
    (4, 4), halo side channel included; strictly below v2's 13."""
    from repro.core.cost import sstep_effective_streams, sstep_streams

    eff = sstep_effective_streams(4, 4)
    assert eff <= 9.0, eff
    assert sum(sstep_streams(4)) == 25 / 4
    # monotone in s at fixed sz: amortization only improves
    assert (sstep_effective_streams(4, 4) < sstep_effective_streams(2, 4)
            < sstep_effective_streams(1, 4))


def test_sstep_halo_streams_scaling():
    """Halo = 10/sz stream-equivalents per iteration (5 fields, 2s ghost
    slabs per block, amortized over s iterations — independent of s)."""
    from repro.core.cost import sstep_halo_streams

    assert abs(sstep_halo_streams(4, 4) - 2.5) < 1e-12
    assert abs(sstep_halo_streams(2, 8) - 1.25) < 1e-12
    assert sstep_halo_streams(2, 4) == sstep_halo_streams(8, 4)


def test_pcg_stream_budgets():
    """DESIGN.md §9: Jacobi = v2 + 1 (the fused diagonal stream);
    Chebyshev = v2 + 5 (the polynomial-apply kernel), k-independent."""
    from repro.core.cost import (CHEB_V2_READ_STREAMS,
                                 CHEB_V2_WRITE_STREAMS,
                                 FUSED_V2_READ_STREAMS,
                                 FUSED_V2_WRITE_STREAMS,
                                 JACOBI_V2_READ_STREAMS,
                                 JACOBI_V2_WRITE_STREAMS, PIPELINE_STREAMS)

    v2 = FUSED_V2_READ_STREAMS + FUSED_V2_WRITE_STREAMS
    jac = JACOBI_V2_READ_STREAMS + JACOBI_V2_WRITE_STREAMS
    chb = CHEB_V2_READ_STREAMS + CHEB_V2_WRITE_STREAMS
    assert jac == v2 + 1 == 14
    assert chb == v2 + 5 == 18
    assert PIPELINE_STREAMS["fused_v2_jacobi"] == (10, 4)
    assert PIPELINE_STREAMS["fused_v2_cheb"] == (13, 5)


def test_multi_rhs_jacobi_stream_books():
    """The coupled b-component Jacobi solve (CEED BP6) as its kernels are
    laid out: 6b + 4 reads and 4b writes per iteration, the 10 + 4 rung at
    b = 1; the metric and Jacobi diagonals are the shared streams."""
    import pytest

    from repro.core.cost import (JACOBI_V2_SHARED_STREAMS,
                                 PIPELINE_STREAMS, multi_rhs_streams)

    assert JACOBI_V2_SHARED_STREAMS == 4.0
    assert multi_rhs_streams(1, "fused_v2_jacobi") == \
        PIPELINE_STREAMS["fused_v2_jacobi"]
    for b in (1, 2, 3, 4, 8):
        r, w = multi_rhs_streams(b, "fused_v2_jacobi")
        assert b * r == pytest.approx(6 * b + 4)
        assert b * w == 4 * b
    # 34 streams for three components, against 3 x 14 = 42 solved apart
    assert 3 * sum(multi_rhs_streams(3, "fused_v2_jacobi")) == \
        pytest.approx(34)


def test_cheb_halo_and_flops_scaling():
    from repro.core.cost import (cheb_effective_streams, cheb_flops_per_dof,
                                 cheb_halo_streams)

    # 4 halo'd fields over 2k ghost slabs per sz-slab block, per iteration
    assert cheb_halo_streams(4, 4) == 8.0
    assert cheb_halo_streams(2, 4) == 4.0          # linear in k
    assert cheb_halo_streams(4, 8) == 4.0          # inverse in sz
    assert cheb_effective_streams(4, 4) == 18 + 8.0
    # each polynomial order adds one operator application's flops
    assert (cheb_flops_per_dof(10, 2) - cheb_flops_per_dof(10, 1)
            == 12 * 10 + 17 + 6)


def test_pcg_bytes_per_dof_iter():
    from repro.core.cost import bytes_per_dof_iter, fused_v2_plane_streams

    for pol, itemsize in (("f64", 8), ("f32", 4), ("bf16", 2)):
        assert bytes_per_dof_iter("fused_v2_jacobi", pol) == \
            (10 * itemsize, 4 * itemsize)
        assert bytes_per_dof_iter("fused_v2_cheb", pol) == \
            (13 * itemsize, 5 * itemsize)
    # bf16 is exactly half of f32 on both rungs (the gate's invariant)
    for pipe in ("fused_v2_jacobi", "fused_v2_cheb"):
        assert (sum(bytes_per_dof_iter(pipe, "bf16")) * 2
                == sum(bytes_per_dof_iter(pipe, "f32")))
    # exact books: both PCG rungs inherit the v2 plane channel; cheb adds
    # its per-iteration halo reads (8k/sz at the defaults)
    half = fused_v2_plane_streams(10, 4) / 2.0
    rj, wj = bytes_per_dof_iter("fused_v2_jacobi", "f32", exact=True)
    assert abs(rj - (10 + half) * 4) < 1e-9
    assert abs(wj - (4 + half) * 4) < 1e-9
    rc, wc = bytes_per_dof_iter("fused_v2_cheb", "f32", exact=True)
    assert abs(rc - (13 + half + 8.0) * 4) < 1e-9
    assert abs(wc - (5 + half) * 4) < 1e-9


def test_bytes_per_dof_iter_exact_mode():
    """exact=True folds in the side channels: v2 boundary planes (split
    evenly read/write), v3 halo (reads only); eq2/v1 are unchanged."""
    from repro.core.cost import (bytes_per_dof_iter, fused_v2_plane_streams,
                                 sstep_halo_streams)

    for pipeline in ("eq2", "fused_v1"):
        assert (bytes_per_dof_iter(pipeline, "f32", exact=True)
                == bytes_per_dof_iter(pipeline, "f32"))
    rb, wb = bytes_per_dof_iter("fused_v2", "f32")
    re_, we = bytes_per_dof_iter("fused_v2", "f32", exact=True, n=10, sz=4)
    half = fused_v2_plane_streams(10, 4) / 2 * 4
    assert abs(re_ - rb - half) < 1e-9 and abs(we - wb - half) < 1e-9
    rb, wb = bytes_per_dof_iter("sstep_v3", "f32")
    re_, we = bytes_per_dof_iter("sstep_v3", "f32", exact=True, sz=4)
    assert abs(re_ - rb - sstep_halo_streams(4, 4) * 4) < 1e-9
    assert we == wb


def test_sstep_bytes_strictly_below_v2_for_s_above_1():
    """The s-sweep acceptance: fewer bytes/DOF/iter than v2 at equal
    precision for every s > 1 (headline and exact books alike)."""
    from repro.core.cost import bytes_per_dof_iter

    for pol in ("f64", "f32", "bf16"):
        v2 = sum(bytes_per_dof_iter("fused_v2", pol))
        v2x = sum(bytes_per_dof_iter("fused_v2", pol, exact=True))
        for s in (2, 4, 8):
            assert sum(bytes_per_dof_iter("sstep_v3", pol, s=s)) < v2
            assert sum(bytes_per_dof_iter("sstep_v3", pol, s=s,
                                          exact=True)) < v2x
        assert sum(bytes_per_dof_iter("sstep_v3", pol, s=1)) == v2


def test_sstep_intensity_scales():
    from repro.core.cost import fused_v2_intensity, sstep_intensity

    assert abs(sstep_intensity(10, 1) - fused_v2_intensity(10)) < 1e-12
    assert sstep_intensity(10, 4) > 2 * fused_v2_intensity(10) * 0.95


# ---------------------------------------------------------------------------
# sharded collective accounting (DESIGN.md §10)
# ---------------------------------------------------------------------------

def test_collective_stream_values():
    """s-step: 8/ez_local per iteration, s-independent (the two s factors
    cancel — communication-avoidance shows up against a per-iteration
    exchange, not in this number); cheb: 4k/ez_local; v2 plane stitch:
    4/(n*ez_local)."""
    from repro.core.cost import (cheb_collective_streams,
                                 sstep_collective_streams,
                                 v2_plane_collective_streams)

    assert sstep_collective_streams(4, 4) == 2.0
    assert sstep_collective_streams(1, 4) == 2.0      # s-independent
    assert sstep_collective_streams(4, 8) == 1.0      # inverse in ez_local
    assert cheb_collective_streams(4, 4) == 4.0
    assert cheb_collective_streams(2, 4) == 2.0       # linear in k
    assert abs(v2_plane_collective_streams(10, 4) - 0.1) < 1e-12


def test_effective_streams_ndev1_identity():
    """ndev=1 is the exact single-device identity — with or without ez —
    and ndev>1 adds exactly the collective channel."""
    from repro.core.cost import (cheb_collective_streams,
                                 cheb_effective_streams,
                                 sstep_effective_streams,
                                 v2_plane_collective_streams)

    base = sstep_effective_streams(4, 4)
    assert sstep_effective_streams(4, 4, ndev=1) == base
    assert sstep_effective_streams(4, 4, ndev=1, ez=32) == base
    assert (sstep_effective_streams(4, 4, ndev=8, ez=32)
            == base + 2.0)                            # + 8/ez_local
    cbase = cheb_effective_streams(4, 4)
    assert cheb_effective_streams(4, 4, ndev=1, ez=32) == cbase
    assert (cheb_effective_streams(4, 4, ndev=8, ez=32, n=10)
            == cbase + cheb_collective_streams(4, 4)
            + v2_plane_collective_streams(10, 4))


def test_effective_streams_ndev_validation():
    import pytest

    from repro.core.cost import sstep_effective_streams

    with pytest.raises(ValueError, match="needs the global EZ"):
        sstep_effective_streams(4, 4, ndev=8)
    with pytest.raises(ValueError, match="not divisible"):
        sstep_effective_streams(4, 4, ndev=8, ez=30)


def test_bytes_per_dof_iter_ndev():
    """ndev threads through the exact books: the collective channel is
    split evenly read/write; ndev=1 stays the identity; eq2/fused_v1 and
    non-exact calls reject ndev>1 instead of lying."""
    import pytest

    from repro.core.cost import (bytes_per_dof_iter, cheb_collective_streams,
                                 sstep_collective_streams,
                                 v2_plane_collective_streams)

    assert (bytes_per_dof_iter("sstep_v3", "f32", exact=True, ndev=1, ez=32)
            == bytes_per_dof_iter("sstep_v3", "f32", exact=True))
    r1, w1 = bytes_per_dof_iter("sstep_v3", "f32", exact=True, sz=4)
    r8, w8 = bytes_per_dof_iter("sstep_v3", "f32", exact=True, sz=4,
                                ndev=8, ez=32)
    half = sstep_collective_streams(4, 32 // 8) / 2.0 * 4
    assert abs(r8 - r1 - half) < 1e-9 and abs(w8 - w1 - half) < 1e-9
    rc1, wc1 = bytes_per_dof_iter("fused_v2_cheb", "f32", exact=True)
    rc8, wc8 = bytes_per_dof_iter("fused_v2_cheb", "f32", exact=True,
                                  ndev=8, ez=32)
    halfc = (cheb_collective_streams(4, 4)
             + v2_plane_collective_streams(10, 4)) / 2.0 * 4
    assert abs(rc8 - rc1 - halfc) < 1e-9 and abs(wc8 - wc1 - halfc) < 1e-9
    with pytest.raises(ValueError, match="no sharded variant"):
        bytes_per_dof_iter("eq2", "f32", exact=True, ndev=8, ez=32)
    with pytest.raises(ValueError, match="no sharded variant"):
        bytes_per_dof_iter("fused_v1", "f32", exact=True, ndev=8, ez=32)
    with pytest.raises(ValueError, match="exact=True"):
        bytes_per_dof_iter("sstep_v3", "f32", ndev=8, ez=32)


def test_multi_rhs_stream_books():
    """DESIGN.md §12: per-RHS streams = vector + shared/b, strictly
    decreasing in b, approaching the vector floor; halo amortizes too;
    bf16 prices at exactly half of f32 on every rhs rung."""
    import pytest

    from repro.core.cost import (MULTI_RHS_BATCHES, MULTI_RHS_SHARED_STREAMS,
                                 PIPELINE_STREAMS, bytes_per_dof_iter,
                                 multi_rhs_halo_streams, multi_rhs_streams,
                                 streams_per_rhs)

    assert MULTI_RHS_SHARED_STREAMS == 3.0
    # b=1 degenerates to the single-RHS rungs
    assert streams_per_rhs(1, "fused_v2") == 13
    assert streams_per_rhs(1, "sstep_v3") == 6.25
    # the b=8 acceptance points
    assert streams_per_rhs(8, "fused_v2") == 10.375
    assert streams_per_rhs(8, "sstep_v3") == 5.59375 < 6.25
    for pipeline in ("fused_v2", "sstep_v3"):
        seq = [streams_per_rhs(b, pipeline) for b in (1, 2, 4, 8)]
        assert all(a > b for a, b in zip(seq, seq[1:]))
        # the shared streams vanish as b -> inf: the floor is vector-only
        floor = streams_per_rhs(1, pipeline) - (
            MULTI_RHS_SHARED_STREAMS if pipeline == "fused_v2"
            else MULTI_RHS_SHARED_STREAMS / 4)
        assert streams_per_rhs(10 ** 6, pipeline) == pytest.approx(floor)
        for b in MULTI_RHS_BATCHES:
            r, w = PIPELINE_STREAMS[f"{pipeline}_rhs{b}"]
            assert r + w == streams_per_rhs(b, pipeline)
            f32 = sum(bytes_per_dof_iter(f"{pipeline}_rhs{b}", "f32"))
            bf16 = sum(bytes_per_dof_iter(f"{pipeline}_rhs{b}", "bf16"))
            assert bf16 * 2 == f32
            ex32 = sum(bytes_per_dof_iter(f"{pipeline}_rhs{b}", "f32",
                                          exact=True))
            ex16 = sum(bytes_per_dof_iter(f"{pipeline}_rhs{b}", "bf16",
                                          exact=True))
            assert ex16 * 2 == pytest.approx(ex32)
    # halo side channel: (4 + 6/b)/sz per RHS — b=1 is the v3 10/sz
    assert multi_rhs_halo_streams(1, 4, 4) == pytest.approx(10 / 4)
    assert multi_rhs_halo_streams(8, 4, 4) == pytest.approx(4.75 / 4)
    with pytest.raises(ValueError):
        multi_rhs_streams(0)
    with pytest.raises(ValueError):
        multi_rhs_streams(2, "eq2")
