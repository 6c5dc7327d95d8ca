#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python chipbench/control.py --workload <cell> --seeds 1,2,... \
        --control-seeds 7,8,9 [--tol-scales 3,10] --seconds 3 \
        [--out <file.jsonl>]

For each of ``--seeds`` the program is driven through a short window of
the cell's own traffic, on that seed's right-hand sides, and its sampled
answers are compared with the reference, as a run does.  For each of
``--control-seeds`` the same is done with the control in the program's
place: the plain reference computed one precision below the
configuration's (float32 products at ``high``, bfloat16 three-pass,
instead of ``highest``).  With ``--tol-scales``, the program is read once
more on the control seeds with its absolute ``tol`` multiplied by each
scale: a stop looser than the configuration states.  The program is built
once, so the dozen seeds
share one set-up.  One JSON line per reading goes to standard output and
to ``--out``.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run as harness

import check
import loadgen
import spec as spec_mod


class ControlSystem:
    """The reference at the precision below the configuration's, behind
    the calls a loop makes."""

    def __init__(self, checker: check.Checker, precision: str = "high"):
        self.checker = checker
        self.precision = precision

    def warm(self) -> None:
        self.solve(0)

    def solve(self, k: int):
        import jax
        import jax.numpy as jnp

        x, it = self.checker.solve(k, self.precision)
        x = jax.block_until_ready(self.checker.box.to_elements(x, jnp))
        return x, it

    def info(self) -> dict:
        return {"entry": "control", "precision": self.precision}


def reading(shape, system, pool_e, checker, traffic, seconds, seed,
            kind) -> dict:
    sample = loadgen.Reservoir(int(traffic["sample"]), seed)
    loop = loadgen.window(shape, system, pool_e, traffic, seconds, seed,
                          sample)
    attempted, failed = harness.outcome(loop, checker.cfg)
    nums = checker.numbers(sample.items)
    iters = [r["iters"] for r in loop["solves"] if "iters" in r]
    pairs = [[it, checker.reference(k)[1]] for k, _, it in sample.items]
    return {"kind": kind, "seed": seed, "attempted": attempted,
            "failed": failed, "iters_min": min(iters),
            "iters_max": max(iters), "iters_vs_ref": pairs, **nums}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--tol-scales", default="",
                    help="also read the program with its absolute tol "
                    "multiplied by each of these, on the control seeds")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    scales = [float(s) for s in args.tol_scales.split(",") if s]

    sp = spec_mod.Spec()
    cell = sp.cell(args.workload)
    cfg = sp.config(cell["config"])
    traffic = sp.traffic(cell["traffic"])
    solver = {**cfg["solver"], **traffic.get("solver", {})}
    sys.path.insert(0, str(sp.root / "src"))
    from repro.compile_cache import configure_caches

    import jax

    configure_caches(sp.root)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    ref = sp.reference(cfg["reference"])
    box = ref.Box(cfg["n"], cfg["grid"])
    shape = sp.loop(traffic["loop"])
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        rec = {"workload": args.workload, **rec}
        print(json.dumps(rec), flush=True)
        if out:
            out.write(json.dumps(rec) + "\n")
            out.flush()

    def inputs(seed):
        pool_u, pool_e = harness.make_pool(box, int(traffic["pool"]), seed)
        rtols, tols = harness.rtols_and_tols(box, cfg, solver, pool_u)
        return pool_e, tols, check.Checker(ref, cfg, solver, pool_u, rtols)

    system = None
    runs = [(seed, 1.0, "program") for seed in seeds] + [
        (seed, scale, f"loosened_stop_x{scale:g}")
        for scale in scales for seed in cseeds]
    for seed, scale, kind in runs:
        pool_e, tols, checker = inputs(seed)
        if system is None:
            system = sp.system(solver["entry"]).build(cfg, solver, traffic,
                                                      pool_e, tols)
            system.warm()
            emit({"kind": "resolved", **system.info()})
        system.use(pool_e, [t if t is None else t * scale for t in tols])
        emit(reading(shape, system, pool_e, checker, traffic, args.seconds,
                     seed, kind))
    del system
    gc.collect()
    for seed in cseeds:
        pool_e, _, checker = inputs(seed)
        ctl = ControlSystem(checker)
        ctl.warm()
        emit(reading(shape, ctl, pool_e, checker, traffic, args.seconds,
                     seed, "control"))
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
