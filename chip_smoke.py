#!/usr/bin/env python3
"""Smoke run of the Nekbone solve on a TPU: the main path, checked.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded s-step path on four chips

One chip, in one process, through the normal entry points:

1. ``operator`` — the paper's Pallas operator (``ax_impl="pallas"``) on a
   random field of ``paper_case(4096)`` (n=10, grid 16x16x16, f32) against
   the XLA operator (``ax_impl="fused"``).
2. ``cg`` — ``NekboneCase.solve`` of the manufactured problem on the same
   case, 100 CG iterations, with ``ax_impl="pallas"`` and with
   ``"pallas_fused_cg_v2"``, each against the XLA solve and ``u_exact``.
3. ``service`` — a ``SolverService(max_b=8)`` on ``paper_case(1024)`` with
   ``"pallas_fused_cg_v2"``: ``warm_start`` (the autotune sweep runs here)
   and then 16 submitted requests, each against its own XLA solve.

``--chips 4`` runs only ``distributed.sstep.cg_sstep_sharded_fixed_iters``
on a 4-device mesh and the single-chip ``cg_sstep_fixed_iters`` of the same
problem (n=10, f32, s=4, 4096 elements per chip).

Every bound is an f32 round-off bound, stated next to its check; the XLA
references run with ``highest`` matmul precision.  Where round-off is
amplified past what a formula bounds usefully (the solution of a solve, the
s-step basis), the bound is measured: the reference solve is rerun on the
right-hand side perturbed by one ulp, and the answer under test may differ
from the reference by at most 10x that response.  Any failed check or
exception exits non-zero.  Times printed are smoke times (compile
included), not benchmark results.  The last line of standard output is the
JSON object ``{"ok": true, "device": {...}}``.  Without a TPU the script
exits non-zero before any work.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
EPS32 = 2.0 ** -23          # f32 unit round-off (machine epsilon)


def _log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=float), flush=True)


def _op_gamma(n: int) -> float:
    """Round-off factor of one f32 operator application and its f32
    reference: each output is a sum over a length-n contraction, 3 metric
    products and a 3n-term transposed contraction (4n+6 rounded steps), and
    both computations round, hence the factor 2."""
    return 2 * (4 * n + 6) * EPS32


# Condition number of the paper cases' assembled operator, from the Ritz
# values of 150 f64 CG iterations (Lanczos): 2.0e4 for paper_case(1024),
# 2.5e4 for paper_case(4096).  The larger one bounds every case run here.
KAPPA = 2.5e4


def _hist_bound(n: int, j: int) -> float:
    """Relative bound on CG residual-history entry j of two f32 solves.

    Each of the j+1 operator applications so far perturbs the iterates by
    ``_op_gamma`` relative; CG passes a perturbation of its operator on to
    the residual amplified by up to sqrt(kappa), the factor its
    convergence rate depends on.  Hence ``(j+1) * gamma * sqrt(KAPPA)``:
    at n=10, 1.7e-3 for j=0 and 1.7e-2 for j=9.
    """
    return (j + 1) * _op_gamma(n) * KAPPA ** 0.5


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"check failed: {what}")


def _ulp_noise(solve, f, *, patterns: int = 2, seed: int = 0):
    """Response of ``solve`` to one-ulp perturbations of its rhs ``f``.

    Reruns ``solve(f * (1 + delta))`` with ``delta`` a random +-eps sign
    pattern and returns ``(ref, hist_noise, x_noise)``: the unperturbed
    result, the running maximum over entries j of the relative history
    change, and the largest change of the solution.  An answer computed
    with another summation order perturbs the solve by O(eps) at every
    step, not once, so the checks allow 10x this envelope.
    """
    import jax.numpy as jnp
    import numpy as np

    ref = solve(f)
    hr = np.asarray(ref.history, np.float64)
    xr = np.asarray(ref.x, np.float64)
    hist_noise = np.zeros_like(hr)
    x_noise = 0.0
    rng = np.random.default_rng(seed)
    for _ in range(patterns):
        sign = rng.choice([-EPS32, EPS32], f.shape).astype(np.float32)
        pert = solve(f * (1 + jnp.asarray(sign, f.dtype)))
        hp = np.asarray(pert.history, np.float64)
        hist_noise = np.maximum(hist_noise, np.abs(hp - hr) / np.abs(hr))
        x_noise = max(x_noise, float(np.max(np.abs(
            np.asarray(pert.x, np.float64) - xr))))
    return ref, np.maximum.accumulate(hist_noise), x_noise


def _x_bound(x_noise: float, xr, n: int) -> float:
    """Allowed max |x - x_ref|: 10x the reference's one-ulp response plus
    the final rounding of x itself (gamma * max|x_ref|)."""
    import numpy as np

    return 10 * x_noise + _op_gamma(n) * float(np.max(np.abs(xr)))


def operator_phase(nelt: int = 4096, *, grid=None, n: int = 10,
                   seed: int = 0) -> dict:
    """Pallas operator vs the XLA operator on a random field."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.nekbone import paper_case
    from repro.core.ax import ax_local_fused

    cfg = paper_case(nelt) if grid is None else dataclasses.replace(
        paper_case(64), grid=tuple(grid), n=n)
    case = cfg.make_case(ax_impl="pallas")
    n = case.n
    u = jnp.asarray(np.random.default_rng(seed).standard_normal(
        case.mask.shape), jnp.float32)
    t0 = time.perf_counter()
    w = jax.block_until_ready(case.ax_local(u))
    dt = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        ref = ax_local_fused(u, case.D, case.g)
        # |D|^T |G| |D| |u|: the scale each output's round-off is relative to
        scale = ax_local_fused(jnp.abs(u), jnp.abs(case.D), jnp.abs(case.g))
    err = np.abs(np.asarray(w, np.float64) - np.asarray(ref, np.float64))
    ratio = float(np.max(err / (_op_gamma(n) * np.asarray(scale) + 1e-30)))
    rel = float(np.max(err) / np.max(np.abs(np.asarray(ref))))
    out = {"shape": list(u.shape), "max_rel_err": rel,
           "err_over_roundoff_bound": ratio,
           "smoke_seconds_incl_compile": dt}
    # |Ax - Ax_ref| <= 2 (4n+6) eps |D|^T|G||D||u|, elementwise
    _check(ratio <= 1.0, f"operator error {ratio:.3g} x the f32 bound")
    return out


def cg_phase(ax_impl: str, nelt: int = 4096, *, grid=None, n: int = 10,
             niter: int = 100) -> dict:
    """Manufactured solve through ``NekboneCase.solve`` vs the XLA solve."""
    import jax
    import numpy as np

    from repro.configs.nekbone import paper_case

    cfg = paper_case(nelt) if grid is None else dataclasses.replace(
        paper_case(64), grid=tuple(grid), n=n)
    case = cfg.make_case(ax_impl=ax_impl)
    n = case.n
    u_ex, f = case.manufactured()
    t0 = time.perf_counter()
    res = case.solve(f, niter=niter)
    jax.block_until_ready(res.x)
    dt = time.perf_counter() - t0
    ref_case = cfg.make_case(ax_impl="fused")
    with jax.default_matmul_precision("highest"):
        ref, _, x_noise = _ulp_noise(
            lambda rhs: ref_case.solve(rhs, niter=niter), f)
    h = np.asarray(res.history, np.float64)
    hr = np.asarray(ref.history, np.float64)
    k = min(10, len(h))
    rel = np.abs(h[:k] - hr[:k]) / np.abs(hr[:k])
    bounds = np.array([_hist_bound(n, j) for j in range(k)])
    err = float(case.solution_error(res.x, u_ex))
    err_ref = float(ref_case.solution_error(ref.x, u_ex))
    xr = np.asarray(ref.x, np.float64)
    x_diff = float(np.max(np.abs(np.asarray(res.x, np.float64) - xr)))
    x_tol = _x_bound(x_noise, xr, n)
    out = {"ax_impl": ax_impl, "pipeline": res.pipeline, "grid": case.grid,
           "niter": niter, "hist_rel_diff_first10": rel.tolist(),
           "final_rnorm": float(h[-1]), "final_rnorm_ref": float(hr[-1]),
           "err_vs_u_exact": err, "err_ref_vs_u_exact": err_ref,
           "x_max_abs_diff": x_diff, "x_one_ulp_noise": x_noise,
           "x_bound": x_tol, "smoke_seconds_incl_compile": dt}
    _check(bool(np.all(rel <= bounds)),
           f"{ax_impl}: history {rel.tolist()} exceeds {bounds.tolist()}")
    # the solution itself, against the XLA solution: a fault in the x
    # path (the update kernel's x axpy, the final layout conversion)
    # leaves the residual history untouched and shows only here
    _check(x_diff <= x_tol,
           f"{ax_impl}: max|x - x_xla| {x_diff:.3g} > {x_tol:.3g}")
    return out


def service_phase(nelt: int = 1024, *, grid=None, n: int = 10,
                  requests: int = 16, max_b: int = 8,
                  niter: int = 100, seed: int = 0) -> dict:
    """SolverService warm start + ``requests`` solves vs XLA solves."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.nekbone import paper_case
    from repro.core.gs import ds_sum_local
    from repro.kernels import autotune
    from repro.launch.solver_service import SolverService, SolveRequest

    cfg = paper_case(nelt) if grid is None else dataclasses.replace(
        paper_case(64), grid=tuple(grid), n=n)
    cfg = dataclasses.replace(cfg, ax_impl="pallas_fused_cg_v2")
    svc = SolverService(max_b=max_b)
    stats0 = autotune.cache_stats()
    t0 = time.perf_counter()
    svc.warm_start([cfg], batches=[max_b], niter=niter)
    t_warm = time.perf_counter() - t0
    stats1 = autotune.cache_stats()
    case = cfg.make_case()
    n = case.n
    rng = np.random.default_rng(seed)
    rhs = [ds_sum_local(jnp.asarray(rng.standard_normal(case.mask.shape),
                                    jnp.float32), case.grid) * case.mask
           for _ in range(requests)]
    for f in rhs:
        svc.submit(SolveRequest(f=f, config=cfg, niter=niter))
    t0 = time.perf_counter()
    results = svc.drain()
    jax.block_until_ready([r.x for r in results])
    t_drain = time.perf_counter() - t0
    _check(len(results) == requests, "every request answered")
    ref_case = cfg.make_case(ax_impl="fused")
    worst = worst_x = 0.0
    bounds = np.array([_hist_bound(n, j) for j in range(10)])
    for f, r in zip(rhs, results):
        with jax.default_matmul_precision("highest"):
            ref, _, x_noise = _ulp_noise(
                lambda b: ref_case.solve(b, niter=niter), f, patterns=1)
        h = np.asarray(r.history, np.float64)[:10]
        hr = np.asarray(ref.history, np.float64)[:10]
        rel = np.abs(h - hr) / np.abs(hr)
        worst = max(worst, float(np.max(rel / bounds)))
        # each served answer against its own XLA solve (as in cg_phase)
        xr = np.asarray(ref.x, np.float64)
        x_diff = float(np.max(np.abs(np.asarray(r.x, np.float64) - xr)))
        worst_x = max(worst_x, x_diff / _x_bound(x_noise, xr, n))
    picks = {str(k): v for k, v in autotune.cache_info().items()}
    out = {"grid": case.grid, "requests": requests, "max_b": max_b,
           "dispatches": len(svc.dispatch_log),
           "batch_sizes": [d.batch_size for d in svc.dispatch_log],
           "autotune_picks": picks,
           "autotune_hits": stats1["hits"] - stats0["hits"],
           "autotune_misses": stats1["misses"] - stats0["misses"],
           "history_diff_over_bound": worst,
           "x_diff_over_bound": worst_x,
           "warm_start_smoke_seconds": t_warm,
           "drain_smoke_seconds": t_drain}
    _check(worst <= 1.0, f"service history diff {worst:.3g} x the bound")
    _check(worst_x <= 1.0, f"service solution diff {worst_x:.3g} x the bound")
    return out


def sharded_phase(ndev: int = 4, *, grid=(8, 8, 256), n: int = 10,
                  s: int = 4, niter: int = 100) -> dict:
    """Sharded s-step CG on ``ndev`` devices vs the one-device s-step CG.

    The default global grid 8x8x256 puts 4096 elements on each of four
    chips; the s-step window of a 16x16 cross-section does not fit VMEM.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.nekbone import paper_case
    from repro.core.cg_sstep import cg_sstep_fixed_iters, estimate_theta
    from repro.distributed.sharding import solver_mesh
    from repro.distributed.sstep import cg_sstep_sharded_fixed_iters

    devices = jax.devices()[:ndev]
    _check(len(devices) == ndev, f"{ndev} devices present")
    cfg = dataclasses.replace(paper_case(64), grid=tuple(grid), n=n)
    case = cfg.make_case(ax_impl="pallas_sstep_v3", s=s)
    u_ex, f = case.manufactured()
    theta = estimate_theta(case.D, case.g, case.grid, case.mask)
    mesh = solver_mesh(ndev, devices=devices)
    kw = dict(D=case.D, g=case.g, grid=case.grid, niter=niter, s=s,
              mask=case.mask, c=case.c, theta=theta)
    t0 = time.perf_counter()
    res = cg_sstep_sharded_fixed_iters(f, mesh=mesh, **kw)
    jax.block_until_ready(res.x)
    dt = time.perf_counter() - t0
    shards = res.x.addressable_shards
    placement = [str(sh.device) for sh in shards]
    own_rows = [sh.data.shape[0] for sh in shards
                if sh.data.devices() == {sh.device}]
    with jax.default_device(devices[0]):
        # The monomial s-step basis amplifies round-off far beyond plain CG
        # (DESIGN.md §8.4), so its f32 noise is measured, not modelled: the
        # one-chip solve of the rhs perturbed by one ulp (4 sign patterns)
        # gives the history's and the solution's response to one O(eps)
        # perturbation.  The sharded run perturbs by O(eps) again every
        # cycle (its Gram sums round in another order): allow 10x that
        # envelope, plus (j+1) gamma where the envelope is still 0.
        ref, noise, x_noise = _ulp_noise(
            lambda b: cg_sstep_fixed_iters(b, **kw),
            jax.device_put(f, devices[0]), patterns=4)
        hr = np.asarray(ref.rnorm_history, np.float64)
        xr = np.asarray(ref.x, np.float64)
        err_ref = float(case.solution_error(jnp.asarray(xr, f.dtype), u_ex))
    h = np.asarray(res.rnorm_history, np.float64)
    rel = np.abs(h - hr) / np.abs(hr)
    bounds = 10 * noise + _op_gamma(n) * np.arange(1, len(h) + 1)
    x = np.asarray(res.x, np.float64)
    x_diff = float(np.max(np.abs(x - xr)))
    x_tol = _x_bound(x_noise, xr, n)
    err = float(case.solution_error(jnp.asarray(x, f.dtype), u_ex))
    k = min(10, len(h))
    out = {"global_grid": case.grid, "elements_per_device":
           case.mesh.nelt // ndev, "s": s, "niter": niter,
           "devices": placement,
           "hist_rel_diff_first10": rel[:k].tolist(),
           "hist_rel_diff_max": float(np.max(rel)),
           "hist_diff_over_bound_max": float(np.max(rel / bounds)),
           "hist_one_ulp_noise_every10": noise[::10].tolist(),
           "x_max_abs_diff": x_diff, "x_one_ulp_noise": x_noise,
           "x_bound": x_tol, "err_vs_u_exact": err,
           "err_one_chip_vs_u_exact": err_ref,
           "smoke_seconds_incl_compile": dt}
    # one shard of E/ndev elements on each device, each held by its own
    _check(len(set(placement)) == ndev
           and own_rows == [case.mesh.nelt // ndev] * ndev,
           f"solution shards {placement} / {own_rows} rows, one per device")
    _check(bool(np.all(rel <= bounds)),
           f"sharded history exceeds 10x the one-ulp envelope at entries "
           f"{np.nonzero(rel > bounds)[0].tolist()}")
    _check(x_diff <= x_tol,
           f"sharded solution: max|x - x_1chip| {x_diff:.3g} > {x_tol:.3g}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found the {dev.platform!r} backend "
              f"({dev.device_kind}); nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import configure_caches
    from repro.kernels.ops import default_interpret

    cache = configure_caches(ROOT)
    _check(default_interpret() is False, "kernels compile for the chip")
    _log("device", kind=dev.device_kind, count=len(jax.devices()),
         compile_cache=cache)
    if args.chips == 4:
        _log("sharded", **sharded_phase(4))
    else:
        _log("operator", **operator_phase())
        for impl in ("pallas", "pallas_fused_cg_v2"):
            _log("cg", **cg_phase(impl))
        _log("service", **service_phase())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
