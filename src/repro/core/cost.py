"""The paper's cost model (Eq. 1-2) and exact operation counts.

Paper §III-A:  per CG iteration over ``D`` degrees of freedom with ``n`` GLL
points per direction,

    C(D, n) = D * (12 n + 34)                 flops            (Eq. 1)
    reads   = 24 D,   writes = 6 D            fp64 words
    I(n)    = (12 n + 34) / 240               flop/byte (fp64) (Eq. 2)

The 12n term is the six contractions (3 forward + 3 transposed, 2n flops
each per point); the constant covers the metric application and the CG
vector operations.  We keep the model exactly as published and additionally
expose dtype-general byte counts (the TPU build runs fp32/bf16, which doubles
/ quadruples I(n) — see DESIGN.md §5).
"""
from __future__ import annotations

import dataclasses

__all__ = ["flops_per_dof", "cg_iter_flops", "cg_iter_bytes", "intensity",
           "ax_local_flops", "ax_local_bytes", "roofline_gflops", "CostModel",
           "CG_READ_STREAMS", "CG_WRITE_STREAMS", "FUSED_CG_READ_STREAMS",
           "FUSED_CG_WRITE_STREAMS", "fused_cg_iter_bytes", "fused_intensity",
           "FUSED_V2_READ_STREAMS", "FUSED_V2_WRITE_STREAMS",
           "fused_v2_cg_iter_bytes", "fused_v2_intensity",
           "fused_v2_plane_streams", "PIPELINE_STREAMS", "PRECISION_ITEMSIZE",
           "precision_itemsize", "bytes_per_dof_iter", "pipeline_intensity",
           "pipeline_flops_per_dof",
           "ir_overhead_streams", "SSTEP_DEFAULT_S", "sstep_cycle_streams",
           "sstep_streams", "sstep_halo_streams", "sstep_effective_streams",
           "sstep_intensity", "JACOBI_V2_READ_STREAMS",
           "JACOBI_V2_WRITE_STREAMS", "JACOBI_V2_SHARED_STREAMS",
           "CHEB_V2_READ_STREAMS", "CHEB_V2_WRITE_STREAMS", "CHEB_DEFAULT_K",
           "cheb_halo_streams",
           "cheb_effective_streams", "cheb_flops_per_dof",
           "sstep_collective_streams", "cheb_collective_streams",
           "v2_plane_collective_streams",
           "PMG_DEFAULT_K", "PMG_COARSE_ITERS", "PMG_SMOOTH_RATIO",
           "pmg_degrees", "pmg_dof_fracs", "pmg_vcycle_streams",
           "pmg_streams", "pmg_halo_streams", "pmg_effective_streams",
           "pmg_flops_per_dof"]

# Eq. 2's stream counts: fp64 words moved per DOF per CG iteration when the
# operator, mask, and every inner product run as separate passes.
CG_READ_STREAMS = 24
CG_WRITE_STREAMS = 6

# The fused-iteration pipeline v1 (core/cg_fused.py, DESIGN.md §3.3) moves:
#   kernel:      reads p, 6 metric fields, mask        (8)    writes w (1)
#   vector pass: reads x, p, r, w, c                   (5)    writes x, r, p (3)
# The r·c·r reduction is carried through the loop state (it is XLA-fused
# into the vector pass that produces r), so the kernel reads no r/c — the
# original 10-read kernel accounting (15R + 4W = 19) drops to 13R + 4W = 17.
# The per-block dot partials are E/block_e scalars — charged as zero streams.
FUSED_CG_READ_STREAMS = 13
FUSED_CG_WRITE_STREAMS = 4

# The v2 pipeline (core/cg_fused.py, DESIGN.md §3.4) runs the whole
# iteration in two slab-resident Pallas kernels:
#   dots kernel:   reads p, r, 3 metric diagonals      (5)    writes p, w (2)
#   update kernel: reads x, p, r, w                    (4)    writes x, r (2)
# The direct-stiffness summation happens in-kernel (x/y and intra-block z)
# plus an O(E n^2) boundary-plane side channel (fused_v2_plane_streams);
# the Dirichlet mask and the weight c are rebuilt in VMEM from per-axis
# factors (O(E^{1/3} n) operands), and the axis-aligned box metric is
# diagonal, so only 3 of Eq. 2's 6 metric streams exist.
FUSED_V2_READ_STREAMS = 9
FUSED_V2_WRITE_STREAMS = 4

# The v3 pipeline (core/cg_sstep.py, DESIGN.md §8) runs s CG iterations per
# *cycle*: a matrix-powers slab kernel builds the 2s+1-vector Krylov basis
# {p, Ap..A^s p, r, Ar..A^{s-1} r} in one residency (re-reading the 3 metric
# diagonals, D, and the mask factors once per s operator applications) and
# emits the (2s+1)^2 Gram partials; a multi-axpy update kernel applies the
# whole s-step of x/r/p updates.  Per cycle:
#   powers kernel: reads p, r, 3 metric diagonals   (5)  writes 2s-1 basis
#   update kernel: reads x + the 2s+1 basis (incl.  (2s+2)  writes x, r, p (3)
#                  p and r, re-read)
# = (2s+7) reads + (2s+2) writes = 4s+9 streams per s iterations.  At s=1
# this is exactly the v2 budget (13); at the default s=4 it is 25/4 = 6.25
# streams/iter.  Redundant halo reads (the matrix-powers ghost region) are
# the side channel (:func:`sstep_halo_streams`); the effective total stays
# <= 9 streams/iter at (s, sz) = (4, 4) (:func:`sstep_effective_streams`).
SSTEP_DEFAULT_S = 4


def sstep_cycle_streams(s: int) -> tuple[int, int]:
    """(reads, writes) full-field streams per s-step *cycle* (s iterations)."""
    return 2 * s + 7, 2 * s + 2


def sstep_streams(s: int) -> tuple[float, float]:
    """(reads, writes) streams per DOF per CG *iteration* of the v3 s-step
    pipeline — the per-cycle budget amortized by 1/s.  ``sstep_streams(1)``
    equals the v2 budget exactly: (9, 4)."""
    r, w = sstep_cycle_streams(s)
    return r / float(s), w / float(s)


def sstep_halo_streams(s: int, sz: int) -> float:
    """Stream-equivalents of the v3 matrix-powers halo, per iteration.

    Chaining s operator applications in one residency needs ``s`` ghost
    slabs on each side of an ``sz``-slab block (each application pollutes
    one slab inward from the block edge); the kernel redundantly reads the
    5 halo'd fields (p, r, 3 metric diagonals) over ``2s`` extra slabs per
    block: ``5 * 2s / sz`` stream-fractions per cycle.  Amortized over the
    cycle's s iterations the two s factors cancel — ``10/sz`` per
    iteration whatever s is; ``s`` stays a parameter so the derivation is
    auditable (the halo *depth* does scale with s).  The analog of
    :func:`fused_v2_plane_streams` — charged as a side channel, not folded
    into the headline count."""
    return 2.0 * 5.0 * float(s) / (float(sz) * float(s))


def sstep_collective_streams(s: int, ez_local: int) -> float:
    """Per-device stream-equivalents of the sharded s-step halo exchange
    (DESIGN.md §10), per iteration.

    Per cycle each device sends its s top and s bottom slabs of *two*
    fields (p and r, stacked into one exchange) and receives the same from
    its neighbours: ``2 fields * s slabs * 2 directions`` slab transfers
    each way.  A slab is ``1/ez_local`` of a device-local field, and every
    transfer both reads the send buffer and writes the receive buffer, so
    the cycle costs ``2 * 2*2*s / ez_local`` stream-fractions —
    ``8/ez_local`` per iteration after the 1/s amortization (the two s
    factors cancel, exactly as in :func:`sstep_halo_streams`; the depth
    scales with s, the per-iteration cost does not).  This is the network
    side channel the single-device accounting has no slot for; compare
    one exchange *per iteration* (``8s/ez_local``-equivalent) to see the
    communication-avoiding win."""
    return 2.0 * 2.0 * 2.0 * float(s) / (float(ez_local) * float(s))


def cheb_collective_streams(k: int, ez_local: int) -> float:
    """Per-device stream-equivalents of the sharded Chebyshev apply's
    k-deep residual ghost exchange, per iteration: 1 field * k slabs * 2
    directions, sent and received, every iteration — ``4k/ez_local``
    (no 1/s amortization, like :func:`cheb_halo_streams`)."""
    return 2.0 * 2.0 * float(k) / float(ez_local)


def v2_plane_collective_streams(n: int, ez_local: int) -> float:
    """Per-device stream-equivalents of the sharded v2-family plane stitch
    (one boundary plane per direction per iteration, sent and received):
    ``4 / (n * ez_local)`` — the cross-shard slice of
    :func:`fused_v2_plane_streams`."""
    return 2.0 * 2.0 / (float(n) * float(ez_local))


def _local_ez(ndev: int, ez: int | None) -> int:
    if ndev == 1:
        return 0                      # unused: collective terms are zero
    if ez is None:
        raise ValueError("ndev > 1 needs the global EZ (ez=) to size the "
                         "per-device halo")
    if ez % ndev:
        raise ValueError(f"EZ {ez} not divisible by ndev {ndev}")
    return ez // ndev


def sstep_effective_streams(s: int, sz: int, ndev: int = 1,
                            ez: int | None = None) -> float:
    """Headline + halo side channel (+ the per-device collective channel
    when ``ndev > 1``): total effective streams/iteration of the v3
    pipeline.  <= 9 at the default (s, sz) = (4, 4): 6.25 + 2.5.
    ``ndev=1`` is the exact single-device identity (no collective term);
    ``ndev > 1`` needs the global ``ez`` and adds
    :func:`sstep_collective_streams` at ``ez_local = ez/ndev``."""
    r, w = sstep_streams(s)
    total = r + w + sstep_halo_streams(s, sz)
    ez_l = _local_ez(ndev, ez)
    if ndev > 1:
        total += sstep_collective_streams(s, ez_l)
    return total


def sstep_intensity(n: int, s: int, itemsize: int = 8) -> float:
    """Eq. 2 re-evaluated for the s-step pipeline (headline streams)."""
    r, w = sstep_streams(s)
    return flops_per_dof(n) / ((r + w) * float(itemsize))


# Preconditioned v2 pipelines (core/precond.py, DESIGN.md §9).
#
# Jacobi: the solver carries the *preconditioned* residual z = D^-1 r, so
# the slab front-half is the v2 kernel unchanged (reads p, z, 3 metric
# diagonals; writes p, w) and the merged PCG update kernel adds exactly one
# stream — the assembled operator diagonal:
#   update kernel: reads x, p, z, w, invdiag    (5)    writes x, z (2)
# = 10R + 4W = 14 streams/iter, one more than unpreconditioned v2.
JACOBI_V2_READ_STREAMS = 10
JACOBI_V2_WRITE_STREAMS = 4
# Of those reads, the operator-side ones a b-component block solve shares
# (core/cg_block.py coupled mode, CEED BP6): the 3 metric diagonals of the
# slab kernel and the diagonal of the update kernel.
JACOBI_V2_SHARED_STREAMS = 4.0

# Chebyshev(k): one extra kernel per iteration evaluates z = q_k(A) r in a
# single halo'd slab residency (the §8 matrix-powers machinery):
#   cheb kernel:   reads r, 3 metric diagonals  (4)    writes z (1)
#   slab kernel:   reads p, z, 3 metric         (5)    writes p, w (2)
#   update kernel: reads x, p, r, w             (4)    writes x, r (2)
# = 13R + 5W = 18 streams/iter regardless of k (the k chained operator
# applications stay in VMEM); the matrix-powers halo — 4 fields over 2k
# ghost slabs per block, every iteration — is the side channel
# (:func:`cheb_halo_streams`).  The win is the *iteration count*: the
# preconditioned solve trades 18 + 8k/sz effective streams/iter against a
# condition-number-driven iteration reduction (§9.3's bytes-to-solution
# accounting; the E=1024/n=10 acceptance case converges to 1e-8 in ~2x
# fewer iterations at k=4).
CHEB_V2_READ_STREAMS = 13
CHEB_V2_WRITE_STREAMS = 5
CHEB_DEFAULT_K = 4


def cheb_halo_streams(k: int, sz: int) -> float:
    """Stream-equivalents of the Chebyshev kernel's matrix-powers halo.

    k chained applications need k ghost slabs per block side (§8.2's
    pollution argument); the kernel redundantly reads its 4 halo'd fields
    (r + 3 metric diagonals) over ``2k`` extra slabs per ``sz``-slab
    block, *every* iteration: ``8k/sz`` stream-fractions — unlike the v3
    halo there is no 1/s amortization, so a deep polynomial wants large
    slabs.  Charged as a side channel, not the headline."""
    return 2.0 * 4.0 * float(k) / float(sz)


def cheb_effective_streams(k: int, sz: int, ndev: int = 1,
                           ez: int | None = None, n: int = 10) -> float:
    """Headline + halo: total effective streams/iter of Chebyshev-PCG.
    ``ndev > 1`` adds the per-device collective channel (residual ghosts
    + the v2 plane stitch at the given ``n``) at ``ez_local = ez/ndev``;
    ``ndev=1`` is the exact single-device identity."""
    total = (CHEB_V2_READ_STREAMS + CHEB_V2_WRITE_STREAMS
             + cheb_halo_streams(k, sz))
    ez_l = _local_ez(ndev, ez)
    if ndev > 1:
        total += cheb_collective_streams(k, ez_l)
        total += v2_plane_collective_streams(n, ez_l)
    return total


# p-multigrid V-cycle preconditioner (core/pmg.py, DESIGN.md §13): the
# degree ladder n -> ceil(n/2) -> ... -> 2, each fine level smoothed twice
# (pre + post) by the fused Chebyshev(k) apply kernel, a fixed-iteration CG
# base solve at n=2.  The books below are *exact per-V-cycle counts off the
# shipped implementation* (precond._pcg_pmg), scaled per level by the DOF
# fraction phi_l = (n_l / n)^3 — a level-l field is phi_l of one fine-grid
# stream.  Unlike every other rung this one deliberately *raises*
# streams/iter: it buys iteration count (>= 2x fewer than cheb4 on the
# acceptance case), which is what dominates time-to-solution once the
# per-iteration pipeline is at its traffic floor.
# Defaults tuned empirically on the paper acceptance case (E=1024, n=10,
# rtol 1e-8; sweep over k x ratio x coarse_iters, benchmarks/pmg_smoke.py
# re-checks in CI): k=3 @ ratio=24 reached 1e-8 in 13 iterations vs
# Chebyshev(4)'s 36 — k=2 @ ratio=8 needed 20, k=3 @ ratio=32 gave 12
# with less interval-safety margin; coarse_iters below 12 started costing
# iterations (14 at 6) while 40 bought nothing.
PMG_DEFAULT_K = 3
PMG_COARSE_ITERS = 12
PMG_SMOOTH_RATIO = 24.0

# Exact per-smoothed-level stream table of one symmetric V-cycle, in units
# of one *level-l* field (multiply by phi_l).  Derived line by line from
# precond._pcg_pmg — see DESIGN.md §13.4 for the audit:
#   pre-smooth (cheb kernel)      4R 1W   | prolong-add z+=m*e  3R 1W
#   A z #1     (v2 slab kernel)   5R 2W   | A z #2 (slab)       5R 2W
#   res1 = r - w                  2R 1W   | res2 = r - w        2R 1W
#   c-weight   t = c * res        2R 1W   | post-smooth (cheb)  4R 1W
#   restrict interp (fine side)   1R  -   | z += dz             2R 1W
_PMG_LEVEL_READS = 30.0
_PMG_LEVEL_WRITES = 12.0
# ... and per coarse-transition, in units of one *level-(l+1)* field: the
# restrict interp's output write, the gather-scatter+mask pass (2R 1W) and
# the prolong interp's input read.
_PMG_COARSE_SIDE_READS = 3.0
_PMG_COARSE_SIDE_WRITES = 2.0


def pmg_degrees(n: int) -> tuple[int, ...]:
    """The p-coarsening ladder ``n -> ceil(n/2) -> ... -> 2`` (HipBone's
    degree halving; GLL count n = degree + 1 so n=2 is the trilinear base).
    """
    if n < 2:
        raise ValueError(f"need n >= 2 GLL points, got {n}")
    ns = [int(n)]
    while ns[-1] > 2:
        ns.append((ns[-1] + 1) // 2)
    return tuple(ns)


def pmg_dof_fracs(n: int) -> tuple[float, ...]:
    """Per-level DOF fractions ``phi_l = (n_l / n)^3`` of the ladder."""
    return tuple((nl / float(n)) ** 3 for nl in pmg_degrees(n))


def pmg_vcycle_streams(n: int = 10,
                       coarse_iters: int = PMG_COARSE_ITERS
                       ) -> tuple[float, float]:
    """(reads, writes) full-*fine*-field streams of ONE symmetric V-cycle.

    Sum of the exact level table over the smoothed levels, the transition
    table over the level boundaries, and ``coarse_iters`` Eq.-2 CG
    iterations (the base solve is plain-XLA CG: 24R + 6W) at the base
    fraction.  k-independent like the cheb rung — the k chained operator
    applications of a smoother stay in VMEM; only the halo grows with k
    (:func:`pmg_halo_streams`)."""
    fr = pmg_dof_fracs(n)
    reads = sum(_PMG_LEVEL_READS * f for f in fr[:-1])
    reads += sum(_PMG_COARSE_SIDE_READS * f for f in fr[1:])
    reads += CG_READ_STREAMS * coarse_iters * fr[-1]
    writes = sum(_PMG_LEVEL_WRITES * f for f in fr[:-1])
    writes += sum(_PMG_COARSE_SIDE_WRITES * f for f in fr[1:])
    writes += CG_WRITE_STREAMS * coarse_iters * fr[-1]
    return reads, writes


def pmg_streams(n: int = 10, coarse_iters: int = PMG_COARSE_ITERS
                ) -> tuple[float, float]:
    """(reads, writes) streams per DOF per PCG iteration of the pmg rung:
    the v2 iteration (9 + 4) plus one V-cycle, exactly as the cheb rung is
    v2 plus one polynomial apply."""
    vr, vw = pmg_vcycle_streams(n, coarse_iters)
    return FUSED_V2_READ_STREAMS + vr, FUSED_V2_WRITE_STREAMS + vw


def pmg_halo_streams(n: int, k: int = PMG_DEFAULT_K,
                     sz: int = 4) -> tuple[float, float]:
    """(reads, writes) side-channel stream-equivalents of one V-cycle: per
    smoothed level, two Chebyshev-apply halos (:func:`cheb_halo_streams`,
    redundant reads) and two v2 slab plane stitches
    (:func:`fused_v2_plane_streams`, split evenly), each at the level's
    DOF fraction.  ``sz`` is applied at every level (the per-level
    autotuned splits differ; the books take one representative split —
    that is the *formula's* exactness boundary, stated here)."""
    fr = pmg_dof_fracs(n)
    ns = pmg_degrees(n)
    reads = writes = 0.0
    for nl, f in zip(ns[:-1], fr[:-1]):
        reads += 2.0 * cheb_halo_streams(k, sz) * f
        half = 2.0 * fused_v2_plane_streams(nl, sz) / 2.0
        reads += half * f
        writes += half * f
    return reads, writes


def pmg_effective_streams(n: int = 10, k: int = PMG_DEFAULT_K,
                          sz: int = 4,
                          coarse_iters: int = PMG_COARSE_ITERS) -> float:
    """Headline + halo/plane side channels: total effective streams per
    PCG iteration of the pmg rung (single-device; there is no sharded
    V-cycle yet)."""
    r, w = pmg_streams(n, coarse_iters)
    hr, hw = pmg_halo_streams(n, k, sz)
    return r + w + hr + hw


def pmg_flops_per_dof(n: int, k: int = PMG_DEFAULT_K,
                      coarse_iters: int = PMG_COARSE_ITERS) -> float:
    """Eq.-1 flops/DOF/iter of pmg-PCG: the v2 iteration plus, per
    smoothed level at its DOF fraction, two Chebyshev applies (k operator
    applications + recurrence axpys each), two explicit operator
    applications, the transfer contractions (3 directions x 2n_c flops
    per fine point, both directions of the transition) and ~8 glue axpys;
    plus the base-level CG iterations.  All of it free in the
    memory-bound regime — the V-cycle is paid for in streams, priced by
    :func:`pmg_streams`."""
    ns = pmg_degrees(n)
    fr = pmg_dof_fracs(n)
    total = float(flops_per_dof(n))
    for lev, (nl, f) in enumerate(zip(ns[:-1], fr[:-1])):
        level = 2.0 * k * (12 * nl + 17 + 6)      # pre+post smoother
        level += 2.0 * (12 * nl + 17)             # the two A z residuals
        level += 2.0 * 3.0 * 2.0 * ns[lev + 1]    # interp down + up
        level += 8.0                              # residual/correction glue
        total += f * level
    total += fr[-1] * coarse_iters * flops_per_dof(ns[-1])
    return total


def cheb_flops_per_dof(n: int, k: int = CHEB_DEFAULT_K) -> int:
    """Eq.-1 flops/DOF/iter of Chebyshev-PCG: the CG iteration plus k
    operator applications (12n + 17 each) and the 3-vector recurrence
    axpys (6 flops per application per point).  Free in the memory-bound
    regime (§1) — the polynomial raises intensity, not time."""
    return flops_per_dof(n) + k * (12 * n + 17 + 6)


def flops_per_dof(n: int) -> int:
    """Eq. 1 coefficient: flops per DOF per CG iteration."""
    return 12 * n + 34


def cg_iter_flops(ndof: int, n: int) -> int:
    """Eq. 1: C(D, n)."""
    return ndof * flops_per_dof(n)


def cg_iter_bytes(ndof: int, itemsize: int = 8) -> tuple[int, int]:
    """(read_bytes, write_bytes) per CG iteration: 24 D reads, 6 D writes."""
    return 24 * ndof * itemsize, 6 * ndof * itemsize


def intensity(n: int, itemsize: int = 8) -> float:
    """Eq. 2 generalized to dtype: I = (12n+34) / (30 * itemsize)."""
    return flops_per_dof(n) / (30.0 * itemsize)


def fused_cg_iter_bytes(ndof: int, itemsize: int = 8) -> tuple[int, int]:
    """(read_bytes, write_bytes) of the step-fused CG iteration (v1, with
    the carried r·c·r): 13 D reads, 4 D writes (vs Eq. 2's 24 + 6 — a
    30/17 ≈ 1.76x traffic cut)."""
    return (FUSED_CG_READ_STREAMS * ndof * itemsize,
            FUSED_CG_WRITE_STREAMS * ndof * itemsize)


def fused_intensity(n: int, itemsize: int = 8) -> float:
    """Eq. 2 re-evaluated for the fused pipeline: same flops over 17 streams."""
    return flops_per_dof(n) / (
        (FUSED_CG_READ_STREAMS + FUSED_CG_WRITE_STREAMS) * float(itemsize))


def fused_v2_cg_iter_bytes(ndof: int, itemsize: int = 8) -> tuple[int, int]:
    """(read_bytes, write_bytes) of the v2 two-kernel iteration: 9 D reads,
    4 D writes (vs Eq. 2's 24 + 6 — a 30/13 ≈ 2.31x traffic cut).  The
    boundary-plane side channel is excluded here; see
    :func:`fused_v2_plane_streams` for its (sub-stream) size."""
    return (FUSED_V2_READ_STREAMS * ndof * itemsize,
            FUSED_V2_WRITE_STREAMS * ndof * itemsize)


def fused_v2_intensity(n: int, itemsize: int = 8) -> float:
    """Eq. 2 re-evaluated for the v2 pipeline: same flops over 13 streams."""
    return flops_per_dof(n) / (
        (FUSED_V2_READ_STREAMS + FUSED_V2_WRITE_STREAMS) * float(itemsize))


def fused_v2_plane_streams(n: int, sz: int) -> float:
    """Stream-equivalents of the v2 boundary-plane side channel.

    Per slab block of ``sz`` slabs the dots kernel writes two
    ``EX*EY*n^2``-word planes and the update kernel reads them back:
    4 plane transfers per ``sz*EX*EY*n^3`` DOFs = ``4 / (n * sz)`` of one
    full stream (0.1 at the paper's n=10 with sz=4) — why the accounting
    charges them as ~zero."""
    return 4.0 / (float(n) * float(sz))


# ---------------------------------------------------------------------------
# multi-RHS (block) accounting (DESIGN.md §12): shared operator streams / b
# + per-RHS vector streams.  The serving amortization axis — the only way
# under the single-RHS floors.
# ---------------------------------------------------------------------------

# Full-field streams per iteration that are *operator-side* — read once per
# slab residency and shared across all b right-hand sides of a block solve:
# the 3 metric diagonals (rr, ss, tt).  D/D^T and the per-axis mask/weight
# factors are shared too but are sub-stream (n^2 and extent*n words) and
# charged as ~zero, exactly as in the single-RHS books.
MULTI_RHS_SHARED_STREAMS = 3.0

# The ladder's rung family: *_rhs{b} entries are pinned at these batches.
MULTI_RHS_BATCHES = (2, 4, 8)


def multi_rhs_streams(b: int, pipeline: str = "fused_v2", *,
                      s: int = SSTEP_DEFAULT_S) -> tuple[float, float]:
    """(reads, writes) full-field streams per DOF per iteration *per RHS*
    of a b-way block solve — the exact books of the amortization.

    ``fused_v2``: of the 9 read streams, 3 are the shared metric
    diagonals, 6 are per-RHS vectors (p, r, x carried via the update
    kernel's operands); all 4 write streams are per-RHS.  Per RHS:

        reads = 6 + 3/b,  writes = 4        (13 at b=1, down to 10.375
                                             at b=8 — floor 10)

    ``sstep_v3``: the same 3 shared streams sit inside the per-cycle
    budget (2s+7 reads, 2s+2 writes over s iterations), so composing the
    s-step cycle with a b-way block divides them by s*b:

        reads = (2s+4)/s + 3/(s*b),  writes = (2s+2)/s

    which recovers the 6.25-stream s=4 rung exactly at b=1 and drops
    below it for every b > 1 (5.59375 at b=8) — the pinned
    ``streams_per_rhs`` trajectory.

    ``fused_v2_jacobi``: the block slab kernel and the batched Jacobi
    update (``nekbone_pcg_update_block_pallas``) as laid out — per RHS
    p, z (slab) and x, p, z, w (update) read, p, w, x, z written; the 3
    metric diagonals and the Jacobi diagonal read once for the batch:

        reads = 6 + 4/b,  writes = 4        (6b + 4 and 4b in all; the
                                             10 + 4 rung at b=1)
    """
    b = float(b)
    if b < 1:
        raise ValueError(f"RHS batch must be >= 1, got {b}")
    if pipeline == "fused_v2":
        reads = (FUSED_V2_READ_STREAMS - MULTI_RHS_SHARED_STREAMS
                 + MULTI_RHS_SHARED_STREAMS / b)
        return reads, float(FUSED_V2_WRITE_STREAMS)
    if pipeline == "fused_v2_jacobi":
        reads = (JACOBI_V2_READ_STREAMS - JACOBI_V2_SHARED_STREAMS
                 + JACOBI_V2_SHARED_STREAMS / b)
        return reads, float(JACOBI_V2_WRITE_STREAMS)
    if pipeline == "sstep_v3":
        cr, cw = sstep_cycle_streams(s)
        reads = ((cr - MULTI_RHS_SHARED_STREAMS) / float(s)
                 + MULTI_RHS_SHARED_STREAMS / (float(s) * b))
        return reads, cw / float(s)
    raise ValueError(f"no multi-RHS books for pipeline {pipeline!r}")


def streams_per_rhs(b: int, pipeline: str = "fused_v2", *,
                    s: int = SSTEP_DEFAULT_S) -> float:
    """Total (reads + writes) streams per DOF per iteration per RHS —
    the single scalar the regression gate pins per (pipeline, b) row,
    strictly decreasing in b."""
    r, w = multi_rhs_streams(b, pipeline, s=s)
    return r + w


def multi_rhs_halo_streams(b: int, s: int, sz: int) -> float:
    """Per-RHS v3 matrix-powers halo of a b-way block solve.

    Of the 5 halo'd fields (:func:`sstep_halo_streams`), p and r are
    per-RHS while the 3 metric diagonals are read once for the whole
    batch: ``2s * (2 + 3/b) / (sz * s)`` = ``(4 + 6/b)/sz`` per
    iteration per RHS (the ``10/sz`` single-RHS channel at b=1)."""
    return 2.0 * float(s) * (2.0 + 3.0 / float(b)) / (float(sz) * float(s))


def _multi_rhs_rung(pipeline: str) -> tuple[str, int] | None:
    """Split a ``<base>_rhs<b>`` ladder rung into (base, b); None if the
    name is not a multi-RHS rung."""
    base, sep, tail = pipeline.rpartition("_rhs")
    if not sep or not tail.isdigit():
        return None
    return base, int(tail)


# ---------------------------------------------------------------------------
# dtype-aware accounting (DESIGN.md §7): the stream *counts* above are fixed
# per pipeline; the precision policy sets the bytes each stream carries.
# ---------------------------------------------------------------------------

# (reads, writes) full-field streams per DOF per CG iteration, per pipeline
# rung of the DESIGN.md §6 ladder.  The s-step rung is s-dependent
# (:func:`sstep_streams`); the registry entry carries the default s=4 point
# (fractional streams: the per-cycle budget amortized by 1/s).
PIPELINE_STREAMS = {
    "eq2": (CG_READ_STREAMS, CG_WRITE_STREAMS),
    "fused_v1": (FUSED_CG_READ_STREAMS, FUSED_CG_WRITE_STREAMS),
    "fused_v2": (FUSED_V2_READ_STREAMS, FUSED_V2_WRITE_STREAMS),
    "sstep_v3": sstep_streams(SSTEP_DEFAULT_S),
    # preconditioned rungs (DESIGN.md §9): same per-iteration accounting,
    # the Chebyshev one buys its extra 5 streams back in iteration count.
    "fused_v2_jacobi": (JACOBI_V2_READ_STREAMS, JACOBI_V2_WRITE_STREAMS),
    "fused_v2_cheb": (CHEB_V2_READ_STREAMS, CHEB_V2_WRITE_STREAMS),
    # the p-multigrid rung (DESIGN.md §13) at the paper point (n=10) and
    # the default base-solve depth: the one rung that *spends* streams per
    # iteration to buy iteration count.
    "fused_v2_pmg": pmg_streams(10, PMG_COARSE_ITERS),
}
# multi-RHS rung family (DESIGN.md §12): per-RHS streams of the b-way
# block solves, both standalone (batched v2) and composed with the s-step
# cycle.  Values are *per RHS* — the quantity that drops below every
# single-RHS floor.
PIPELINE_STREAMS.update({
    f"{base}_rhs{nb}": multi_rhs_streams(nb, base)
    for base in ("fused_v2", "sstep_v3") for nb in MULTI_RHS_BATCHES
})

# Storage-dtype bytes per word, per precision-policy name
# (core/precision.py).  The refined policies price like their storage: the
# refinement outer loop's high-precision pass is charged separately
# (:func:`ir_overhead_streams`), amortized over the inner iterations.
PRECISION_ITEMSIZE = {"f64": 8, "f32": 4, "bf16": 2,
                      "f32_ir": 4, "bf16_ir": 2}


def precision_itemsize(precision) -> int:
    """Storage bytes/word of a policy name or PrecisionPolicy instance."""
    itemsize = getattr(precision, "itemsize", None)
    if itemsize is not None:
        return int(itemsize)
    return PRECISION_ITEMSIZE[str(precision)]


def bytes_per_dof_iter(pipeline: str, precision, *, exact: bool = False,
                       n: int = 10, sz: int = 4,
                       s: int = SSTEP_DEFAULT_S,
                       k: int = CHEB_DEFAULT_K, ndev: int = 1,
                       ez: int | None = None) -> tuple[float, float]:
    """(read_bytes, write_bytes) per DOF per CG iteration for a pipeline
    rung under a precision policy — the ndof-independent quantity the CI
    regression gate diffs (benchmarks/check_regression.py).

    ``exact=True`` stops charging the sub-stream side channels as exactly
    zero: the v2 boundary-plane channel (:func:`fused_v2_plane_streams` at
    the given ``n``/``sz`` — 2 plane writes by the dots kernel, 2 plane
    reads by the update kernel, split evenly; the Jacobi and Chebyshev
    PCG rungs inherit it, they reuse those kernels), the v3 matrix-powers
    halo (:func:`sstep_halo_streams` — redundant *reads* only), and the
    Chebyshev apply kernel's per-iteration halo
    (:func:`cheb_halo_streams`, also reads) are folded in.  The eq2 and
    fused_v1 rungs have no modeled side channel (v1's uncounted assembly
    pass follows the original §3.3 books, see DESIGN.md §6), so their
    exact numbers equal the headline ones.

    ``ndev > 1`` (needs the global ``ez`` and ``exact=True``) adds the
    per-device collective channel of the *sharded* pipelines (DESIGN.md
    §10): the s-step ghost-slab exchange
    (:func:`sstep_collective_streams`), the Chebyshev residual ghosts
    (:func:`cheb_collective_streams`), and the v2-family plane stitch
    (:func:`v2_plane_collective_streams`), each split evenly into the
    send-buffer read and the receive-buffer write.  ``ndev=1`` is the
    exact single-device identity; pipelines without a sharded variant
    (eq2, fused_v1) reject ``ndev > 1`` rather than silently reporting
    single-device traffic.
    """
    reads, writes = PIPELINE_STREAMS[pipeline]
    if pipeline == "sstep_v3" and s != SSTEP_DEFAULT_S:
        reads, writes = sstep_streams(s)
    if pipeline == "fused_v2_pmg" and n != 10:
        reads, writes = pmg_streams(n)
    rhs_rung = _multi_rhs_rung(pipeline)
    if rhs_rung is not None and rhs_rung[0] == "sstep_v3" \
            and s != SSTEP_DEFAULT_S:
        reads, writes = multi_rhs_streams(rhs_rung[1], "sstep_v3", s=s)
    if ndev > 1 and pipeline not in ("sstep_v3", "fused_v2",
                                     "fused_v2_jacobi", "fused_v2_cheb"):
        raise ValueError(f"pipeline {pipeline!r} has no sharded variant; "
                         "ndev > 1 is not meaningful for it")
    if ndev > 1 and not exact:
        raise ValueError("ndev > 1 only affects the exact accounting; "
                         "pass exact=True")
    if exact:
        ez_l = _local_ez(ndev, ez)
        if pipeline in ("fused_v2", "fused_v2_jacobi", "fused_v2_cheb"):
            half = fused_v2_plane_streams(n, sz) / 2.0
            reads, writes = reads + half, writes + half
            if pipeline == "fused_v2_cheb":
                reads = reads + cheb_halo_streams(k, sz)
            if ndev > 1:
                half_c = v2_plane_collective_streams(n, ez_l) / 2.0
                reads, writes = reads + half_c, writes + half_c
                if pipeline == "fused_v2_cheb":
                    half_k = cheb_collective_streams(k, ez_l) / 2.0
                    reads, writes = reads + half_k, writes + half_k
        elif pipeline == "fused_v2_pmg":
            # the outer v2 iteration's plane stitch, then the V-cycle's
            # own per-level halo/plane channels (pmg uses the smoother's
            # default order, not the standalone-cheb k)
            half = fused_v2_plane_streams(n, sz) / 2.0
            hr, hw = pmg_halo_streams(n, PMG_DEFAULT_K, sz)
            reads, writes = reads + half + hr, writes + half + hw
        elif pipeline == "sstep_v3":
            reads = reads + sstep_halo_streams(s, sz)
            if ndev > 1:
                half_s = sstep_collective_streams(s, ez_l) / 2.0
                reads, writes = reads + half_s, writes + half_s
        elif rhs_rung is not None:
            base, nb = rhs_rung
            if base == "fused_v2":
                # the boundary-plane side channel is per-RHS (every RHS's
                # planes travel), so the per-RHS charge is the b=1 one.
                half = fused_v2_plane_streams(n, sz) / 2.0
                reads, writes = reads + half, writes + half
            else:  # sstep_v3_rhs{b}: metric halo shared across the batch
                reads = reads + multi_rhs_halo_streams(nb, s, sz)
    itemsize = precision_itemsize(precision)
    return reads * itemsize, writes * itemsize


def pipeline_intensity(n: int, pipeline: str, precision) -> float:
    """Eq. 2 arithmetic intensity of a (pipeline, precision) point:
    same (12n + 34) flops over the policy-priced streams."""
    return pipeline_flops_per_dof(n, pipeline) / float(
        sum(bytes_per_dof_iter(pipeline, precision)))


def pipeline_flops_per_dof(n: int, pipeline: str, *,
                           s: int = SSTEP_DEFAULT_S,
                           k: int = CHEB_DEFAULT_K) -> float:
    """Eq.-1 flops per DOF per CG *iteration* of a pipeline rung.

    The fusion ladder (eq2, fused_v1, fused_v2, sstep_v3) moves the same
    arithmetic through fewer streams, so every rung keeps Eq. 1's
    (12n + 34); Jacobi-PCG adds the diagonal scale + the extra rtz books
    (~3 flops/DOF/iter on the merged update); Chebyshev-PCG adds k
    operator applications per iteration (:func:`cheb_flops_per_dof`) —
    its win is the *iteration count*, not the per-iteration rate."""
    if pipeline in ("eq2", "fused_v1", "fused_v2", "sstep_v3"):
        return float(flops_per_dof(n))
    if _multi_rhs_rung(pipeline) is not None:
        # block solves amortize *streams*, not arithmetic: every RHS does
        # full Eq.-1 work per iteration.
        return float(flops_per_dof(n))
    if pipeline == "fused_v2_jacobi":
        return float(flops_per_dof(n) + 3)
    if pipeline == "fused_v2_cheb":
        return float(cheb_flops_per_dof(n, k))
    if pipeline == "fused_v2_pmg":
        return pmg_flops_per_dof(n)
    raise ValueError(f"unknown pipeline {pipeline!r}")


def ir_overhead_streams(inner_iters: int, hi_itemsize: int = 8,
                        itemsize: int = 2) -> float:
    """Storage-stream equivalents the refinement outer loop adds per inner
    iteration.

    Each sweep runs one high-precision pass — the operator refresh
    (7R + 1W), the residual/solution axpys (4R + 2W) — ~14 ``hi_itemsize``
    words/DOF, amortized over ``inner_iters`` low-precision iterations and
    expressed in units of one storage-dtype stream.  At the defaults
    (bf16 inner, f64 outer, 12 inner iters) that is ~4.7 extra bf16
    streams on the v2 budget's 13: ~35 bytes/DOF/iter against unrefined
    f32 v2's 52 — the refined pipeline still moves ~1.5x fewer bytes."""
    return 14.0 * float(hi_itemsize) / (float(itemsize) * float(inner_iters))


def ax_local_flops(nelt: int, n: int) -> int:
    """Exact flops of the local tensor-product operator (both stages).

    Per point: 3 forward contractions (2n each), metric apply
    (6 mul + ... = 15: 9 mul + 6 add), 3 transposed contractions (2n each)
    summed into w (2 adds) => 12n + 17.
    """
    return nelt * n ** 3 * (12 * n + 17)


def ax_local_bytes(nelt: int, n: int, itemsize: int = 8) -> tuple[int, int]:
    """Minimal HBM traffic of the fused local operator.

    Reads: u (1 field) + G (6 fields) (+ D, negligible); writes: w (1 field).
    """
    ndof = nelt * n ** 3
    return 7 * ndof * itemsize, 1 * ndof * itemsize


def roofline_gflops(bandwidth_gbs: float, n: int, itemsize: int = 8) -> float:
    """Memory-roofline performance bound: BW * I(n) (paper §VI-B)."""
    return bandwidth_gbs * intensity(n, itemsize)


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Cost model instance for a given case size."""

    nelt: int
    n: int
    itemsize: int = 8

    @property
    def ndof(self) -> int:
        return self.nelt * self.n ** 3

    @property
    def cg_flops(self) -> int:
        return cg_iter_flops(self.ndof, self.n)

    @property
    def cg_read_bytes(self) -> int:
        return cg_iter_bytes(self.ndof, self.itemsize)[0]

    @property
    def cg_write_bytes(self) -> int:
        return cg_iter_bytes(self.ndof, self.itemsize)[1]

    @property
    def intensity(self) -> float:
        return intensity(self.n, self.itemsize)
