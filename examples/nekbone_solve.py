"""End-to-end Nekbone driver (the paper's application, §V protocol).

Runs the full benchmark the paper measures: SEM Poisson on a box of
elements at polynomial degree 9, 100 CG iterations, sweeping the element
count, reporting achieved GFLOP/s against the paper's cost model — plus a
correctness solve against the manufactured solution and the beyond-paper
extras (Jacobi preconditioning, mixed-precision iterative refinement).

  PYTHONPATH=src python examples/nekbone_solve.py [--elements 128]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.nekbone import PAPER_CASES
from repro.core.cost import cg_iter_flops
from repro.core.nekbone import NekboneCase


def run_case(nelt: int, niter: int = 100):
    nb = PAPER_CASES[nelt]
    case = NekboneCase(n=nb.n, grid=nb.grid, dtype=jnp.float32,
                       ax_impl="fused")
    u_ex, f = case.manufactured()

    solve = jax.jit(lambda f: case.solve(f, niter=niter))
    res = solve(f)
    jax.block_until_ready(res.x)
    t0 = time.time()
    res = solve(f)
    jax.block_until_ready(res.x)
    dt = time.time() - t0

    flops = cg_iter_flops(case.mesh.ndof, case.n) * niter
    err = float(case.solution_error(res.x, u_ex))
    print(f"E={nelt:5d}  ndof={case.mesh.ndof:9d}  {niter} CG iters in "
          f"{dt:6.2f}s  -> {flops / dt / 1e9:6.2f} GF/s   max-err {err:.2e}")
    return case, f, u_ex


def main():
    from repro.compile_cache import configure_caches

    configure_caches()
    ap = argparse.ArgumentParser()
    ap.add_argument("--elements", type=int, default=128,
                    choices=sorted(PAPER_CASES))
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--sweep", action="store_true",
                    help="paper's element sweep (64..1024; slow on CPU)")
    args = ap.parse_args()

    print("== Nekbone (paper §V: degree 9, 100 CG iterations) ==")
    sweep = (64, 128, 256) if args.sweep else (args.elements,)
    for E in sweep:
        case, f, u_ex = run_case(E, args.iters)

    print("\n== fused CG iteration (Pallas pipelines, DESIGN.md §3) ==")
    # v1: one multi-output Pallas call per iteration (masked Ax + the p·c·Ap
    # partial; r·c·r carried through the loop state).  v2: the whole
    # iteration in two slab-resident kernels — in-kernel gather-scatter with
    # O(n^2) boundary-plane side channels, merged vector updates, structural
    # mask/weight, diagonal metric.  Interpret mode off-TPU: correctness,
    # not speed — compare residual histories against the XLA path.
    from repro.core.cost import (CG_READ_STREAMS, CG_WRITE_STREAMS,
                                 FUSED_CG_READ_STREAMS,
                                 FUSED_CG_WRITE_STREAMS,
                                 FUSED_V2_READ_STREAMS,
                                 FUSED_V2_WRITE_STREAMS,
                                 sstep_effective_streams, sstep_streams)

    small = NekboneCase(n=6, grid=(2, 2, 2), dtype=jnp.float32,
                        ax_impl="fused")
    res_x, _ = small.solve_manufactured(niter=10)
    v3 = sum(sstep_streams(4))
    print(f"streams/iter: {CG_READ_STREAMS}R+{CG_WRITE_STREAMS}W (Eq. 2) -> "
          f"{FUSED_CG_READ_STREAMS}R+{FUSED_CG_WRITE_STREAMS}W (fused v1) -> "
          f"{FUSED_V2_READ_STREAMS}R+{FUSED_V2_WRITE_STREAMS}W (fused v2) -> "
          f"{v3:g} (s-step v3 @ s=4; "
          f"{sstep_effective_streams(4, 4):.2f} eff w/ halo)")
    for impl in ("pallas_fused_cg", "pallas_fused_cg_v2"):
        small.ax_impl = impl
        res_f, _ = small.solve_manufactured(niter=10)
        drift = float(jnp.nanmax(jnp.abs(res_f.rnorm_history -
                                         res_x.rnorm_history) /
                                 jnp.abs(res_x.rnorm_history)))
        print(f"residual-history drift vs XLA CG over 10 iters "
              f"({impl}): {drift:.2e}")

    print("\n== beyond-paper: s-step CG (matrix-powers pipeline, "
          "DESIGN.md §8) ==")
    # one matrix-powers cycle evaluates the whole s-vector Krylov basis in
    # a single slab residency (metric/D/mask loaded once per s operator
    # applications) and the s recurrence steps solve in f64 on (2s+1)-
    # coefficient coordinates — one host round-trip per s iterations.
    for s in (1, 2, 4):
        small.ax_impl = "pallas_sstep_v3"
        small.s = s
        res_s, _ = small.solve_manufactured(niter=8)
        drift = float(jnp.nanmax(jnp.abs(
            res_s.rnorm_history - res_x.rnorm_history[:9]) /
            jnp.abs(res_x.rnorm_history[:9])))
        print(f"  s={s}: {sum(sstep_streams(s)):5.2f} streams/iter "
              f"(eff {sstep_effective_streams(s, 4):5.2f}), history drift "
              f"vs XLA CG over 8 iters: {drift:.2e}")

    print("\n== beyond-paper: preconditioning + solve-to-tolerance "
          "(DESIGN.md §9) ==")
    # The precond subsystem (core/precond.py) is wired through the config:
    # NekboneConfig(precond=...) -> make_case() -> case.solve(tol=...).
    # On the v2 fused pipeline the Jacobi apply is fused into the update
    # kernel (14 streams/iter, one more than plain v2) and the Chebyshev
    # polynomial evaluates in one halo'd slab residency per iteration (18
    # streams/iter); tolerance-driven solves run the same bodies under a
    # while_loop, so each trajectory prefixes its fixed-iteration twin.
    from repro.configs.nekbone import NekboneConfig
    from repro.core.cost import (CHEB_V2_READ_STREAMS,
                                 CHEB_V2_WRITE_STREAMS,
                                 JACOBI_V2_READ_STREAMS,
                                 JACOBI_V2_WRITE_STREAMS,
                                 cheb_effective_streams)

    pcg_cfg = NekboneConfig(name="pcg-demo", n=6, grid=(2, 2, 4),
                            dtype="float32", ax_impl="pallas_fused_cg_v2")
    for pc_name in (None, "jacobi", "cheb"):
        pcase = pcg_cfg.make_case(precond=pc_name)
        r, _ = pcase.solve_manufactured(tol=1e-5, max_iter=300)
        streams = {"jacobi": JACOBI_V2_READ_STREAMS
                   + JACOBI_V2_WRITE_STREAMS,
                   "cheb": CHEB_V2_READ_STREAMS
                   + CHEB_V2_WRITE_STREAMS}.get(
                       pc_name, FUSED_V2_READ_STREAMS
                       + FUSED_V2_WRITE_STREAMS)
        eff = (f" (eff {cheb_effective_streams(pcase.cheb_k, 4):.1f} "
               "w/ halo)" if pc_name == "cheb" else "")
        print(f"  {pc_name or 'plain':>6}: {int(r.iters):3d} iters to "
              f"tol @ {streams} streams/iter{eff}")
    # per-call override by registry name works on any ax_impl (the old
    # boolean spelling precond=True|False finished its deprecation cycle
    # and now raises TypeError):
    r_plain, _ = case.solve_manufactured(tol=1e-6, max_iter=500)
    r_pc, _ = case.solve_manufactured(tol=1e-6, max_iter=500,
                                      precond="jacobi")
    print(f"  reference path, iterations to 1e-6: "
          f"plain={int(r_plain.iters)} jacobi={int(r_pc.iters)}")

    print("\n== beyond-paper: mixed-precision fused CG (DESIGN.md §7) ==")
    # bf16 storage halves every stream of the 13-stream v2 pipeline; the
    # iterative-refinement outer loop (cg_ir_fixed_iters) recovers the
    # caller-precision residual floor from the bf16-priced inner solves.
    # (true fp64 outer residuals need JAX_ENABLE_X64=1; the structure is
    # identical in fp32, demonstrated here on a small case.)
    from repro.core.cg_fused import cg_ir_fixed_iters
    from repro.core.cost import bytes_per_dof_iter, ir_overhead_streams

    for pol in ("f64", "f32", "bf16"):
        rb, wb = bytes_per_dof_iter("fused_v2", pol)
        print(f"  fused_v2 bytes/DOF/iter {pol:>4}: {rb + wb:3d} "
              f"({rb}R + {wb}W)")
    print(f"  bf16_ir outer-pass surcharge: "
          f"+{ir_overhead_streams(20):.2f} bf16-streams/iter @ 20-iter sweeps")

    mp = NekboneCase(n=6, grid=(2, 2, 2), dtype=jnp.float32)
    _, fmp = mp.manufactured()
    ir = cg_ir_fixed_iters(fmp, D=mp.D, g=mp.g, grid=mp.grid, niter=20,
                           precision="bf16_ir", outer_iters=3)
    print("bf16_ir outer residual norms:",
          [f"{float(v):.2e}" for v in ir.rnorm_history],
          f"({int(ir.iters)} bf16-priced inner iterations)")


if __name__ == "__main__":
    main()
