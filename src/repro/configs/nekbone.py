"""Nekbone case configurations (the paper's own experiment grid).

Paper §V: polynomial degree 9 (n = 10 GLL points), 64 - 4096 elements per
GPU, 100 CG iterations.  Element grids are chosen to keep the box roughly
cubic, matching Nekbone's ``data.rea`` defaults.
"""
from __future__ import annotations

import dataclasses

__all__ = ["NekboneConfig", "PAPER_CASES", "paper_case"]


@dataclasses.dataclass(frozen=True)
class NekboneConfig:
    name: str
    n: int                               # GLL points per direction (= p + 1)
    grid: tuple[int, int, int]           # element grid (per device)
    niter: int = 100                     # paper: 100 CG iterations
    dtype: str = "float32"               # TPU target; fp64 on CPU oracle
    # "auto" resolves to the measured-fastest fused CG pipeline for the
    # case shape (kernels/autotune.pick_pipeline; E-threshold heuristic
    # off-TPU) — see NekboneCase.ax_impl for the full value list.
    ax_impl: str = "pallas"
    # Fused-pipeline precision policy (DESIGN.md §7, core/precision.py):
    # "f64" | "f32" | "bf16" | "bf16_ir" | "f32_ir", or None to leave the
    # solver dtype entirely to ``dtype`` (pre-policy behaviour — a
    # non-refined policy would otherwise *override* ``dtype`` with its
    # storage dtype).  "bf16_ir" is the mixed-precision target (bf16
    # storage streams, f32 accumulation, iterative-refinement outer loop).
    precision: str | None = None
    # s-step cycle length for ax_impl="pallas_sstep_v3" (DESIGN.md §8):
    # iterations per matrix-powers cycle.  s=1 reproduces the v2 stream
    # budget exactly; s=4 is the tuned default (6.25 streams/iter, <= 9
    # effective with the halo side channel).  Ignored by other ax_impls.
    s: int = 4
    # Preconditioner (DESIGN.md §9 and §13, core/precond.py): None (the
    # paper's unpreconditioned protocol), "jacobi" (diagonal — fused into
    # the v2 pipeline at 14 streams/iter), "cheb" (Chebyshev polynomial
    # of order ``cheb_k`` — 18 streams/iter, condition-number-driven
    # iteration reduction), or "pmg" / "pmg[cheb<k>]" (p-multigrid
    # V-cycle with fused Chebyshev smoothers, core/pmg.py — the highest
    # streams/iter and by far the fewest iterations; §13.4 books).  The
    # v2 fused pipeline dispatches to the fused PCG drivers; every other
    # ax_impl applies the reference (XLA) preconditioner through
    # core/cg.py.
    precond: str | None = None
    cheb_k: int = 4
    # Default RHS batch (DESIGN.md §12): b > 1 routes unpreconditioned
    # v2-family solves through the multi-RHS block kernels
    # (core/cg_block.py), amortizing the shared operator streams over the
    # batch (core/cost.multi_rhs_streams).  The solver service buckets
    # requests by (grid, n, precision, precond) and solves them at b up
    # to this value per dispatch.
    b: int = 1
    # Components of the solved field (core/nekbone.NekboneCase): 1 for a
    # scalar Poisson solve, 3 for a vector one that shares the scalar
    # operator (CEED BP6), solved as one coupled system.
    components: int = 1

    @property
    def nelt(self) -> int:
        ex, ey, ez = self.grid
        return ex * ey * ez

    @property
    def ndof(self) -> int:
        return self.nelt * self.n ** 3

    def make_case(self, **overrides):
        """Instantiate the runnable :class:`repro.core.nekbone.NekboneCase`
        for this configuration (keyword overrides win)."""
        from repro.core.nekbone import NekboneCase

        kwargs = dict(n=self.n, grid=self.grid,
                      dtype=jnp_dtype(self.dtype), ax_impl=self.ax_impl,
                      precision=self.precision, s=self.s,
                      precond=self.precond, cheb_k=self.cheb_k, b=self.b,
                      components=self.components)
        kwargs.update(overrides)
        return NekboneCase(**kwargs)


def jnp_dtype(name: str):
    import jax.numpy as jnp

    return jnp.dtype(name)


def _case(nelt: int, grid) -> NekboneConfig:
    return NekboneConfig(name=f"nekbone-e{nelt}", n=10, grid=grid)


# Element counts from the paper's sweep (Fig. 2/3), degree 9.
PAPER_CASES = {
    64: _case(64, (4, 4, 4)),
    128: _case(128, (4, 4, 8)),
    256: _case(256, (4, 8, 8)),
    512: _case(512, (8, 8, 8)),
    1024: _case(1024, (8, 8, 16)),
    2048: _case(2048, (8, 16, 16)),
    3584: _case(3584, (16, 16, 14)),     # Kebnekaise point (448*8)
    4096: _case(4096, (16, 16, 16)),
}


def paper_case(nelt: int = 1024, precision: str | None = None,
               precond: str | None = None) -> NekboneConfig:
    """A paper-grid case, optionally re-priced under a precision policy
    and/or preconditioned (DESIGN.md §9 — the beyond-paper PCG workload)."""
    cfg = PAPER_CASES[nelt]
    if precision != cfg.precision:
        cfg = dataclasses.replace(cfg, precision=precision)
    if precond != cfg.precond:
        cfg = dataclasses.replace(cfg, precond=precond)
    return cfg
