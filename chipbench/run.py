#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic mix, the mix's loop, the
system under test and the reference are looked up by name (``spec.py``).
In order, a run:

1. checks that JAX sees TPUs, as many as the cell asks for, or exits 2
   with no result;
2. points the compile and autotune caches into the checkout
   (``repro.compile_cache.configure_caches``);
3. makes the pool of right-hand sides on the device from ``--seed``;
4. builds the system, warms up the cell's own shapes, and counts all that
   as ``setup_s``;
5. drives the load for ``--seconds`` (with ``--trace 1``, a traced
   sub-window of ``trace_seconds`` instead, read from the profiler's
   trace);
6. reads the device's peak memory, frees the program's state, and checks
   a seeded sample of the window's answers against the plain reference.

Earlier lines of standard output are JSON records of what the run
resolved (route, pipeline, autotune picks), and of the window: the
compilations inside it and whatever the loop reports.  The last lines of standard error give each compared number with
its limit; the last line of standard output is the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import check  # noqa: E402
import loadgen  # noqa: E402
import spec as spec_mod  # noqa: E402

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


def _say(kind: str, **fields) -> None:
    print(json.dumps({"record": kind, **fields}, default=str), flush=True)


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads while armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, *_, **__):
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1


def make_pool(box, size: int, seed: int):
    """``size`` random right-hand sides on the unique interior nodes, made
    on the device in one call: their unique-node and element forms."""
    import jax
    import jax.numpy as jnp

    interior = jnp.asarray(box.interior64, jnp.float32)
    key = jax.random.key(int(loadgen.rng_for(seed, 0).integers(2**31)))

    @jax.jit
    def make(key):
        f = jax.random.normal(key, (size,) + box.gshape, jnp.float32)
        f = f * interior
        return f, box.to_elements(f, jnp)

    fu, fe = make(key)
    return [fu[i] for i in range(size)], [fe[i] for i in range(size)]


def rtols_and_tols(box, cfg: dict, solver: dict, pool_u: list):
    """Per right-hand side: the relative stop and the program's absolute
    ``tol`` on its residual measure ``sqrt(r . z)``, ``z = M^-1 r``, with
    ``M^-1`` as the reference weighs it (``Box.precond_weight``)."""
    import jax.numpy as jnp

    stop = cfg["stop"]
    if "niter" in stop:
        return [0.0] * len(pool_u), [None] * len(pool_u)
    rtol = float(stop["rtol"])
    w = box.precond_weight(solver.get("precond")) * box.interior64
    w = jnp.asarray(w, jnp.float32)
    tols = [rtol * float(jnp.sqrt(jnp.sum(f * w * f))) for f in pool_u]
    return [rtol] * len(pool_u), tols


def traced_window(loop, system, pool, traffic, seconds, seed, sample,
                  out: Path):
    import jax

    import devtrace

    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(str(out))
    t0 = time.perf_counter()
    try:
        result = loadgen.window(loop, system, pool, traffic, seconds, seed,
                                sample)
    finally:
        window_ns = (time.perf_counter() - t0) * 1e9
        jax.profiler.stop_trace()
    summary = devtrace.summarize(devtrace.load(devtrace.latest_xplane(
        str(out)), window_ns))
    shutil.rmtree(out, ignore_errors=True)
    return result, summary


def outcome(loop: dict, cfg: dict) -> tuple[int, int]:
    """``(attempted, failed)``: a solve fails if it never came back or
    stopped at ``max_iter``."""
    items = loop["solves"]
    cap = cfg["stop"].get("max_iter")
    failed = sum(1 for r in items if "iters" not in r
                 or (cap is not None and r["iters"] >= cap))
    return len(items), failed


def main(argv=None, *, root=spec_mod.ROOT, bench_dir=spec_mod.BENCH_DIR,
         require_tpu: bool = True, make_system=None) -> int:
    """One run; ``make_system(cfg, traffic, pool, tols, checker)`` puts
    something else in the program's place (the control does)."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sp = spec_mod.Spec(root, bench_dir)
    cell = sp.cell(args.workload)
    cfg = sp.config(cell["config"])
    traffic = sp.traffic(cell["traffic"])
    solver = {**cfg["solver"], **traffic.get("solver", {})}

    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell["chips"]):
        print(f"chipbench: {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform!r} "
              f"device(s) ({devices[0].device_kind}); nothing was run",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(root) / "src"))
    from repro.compile_cache import configure_caches

    cache_dir = configure_caches(root)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import roofline

    peak = roofline.peaks(devices[0].device_kind) if require_tpu else None
    ref = sp.reference(cfg["reference"])
    box = ref.Box(cfg["n"], cfg["grid"])
    pool_u, pool_e = make_pool(box, int(traffic["pool"]), args.seed)
    rtols, tols = rtols_and_tols(box, cfg, solver, pool_u)
    checker = check.Checker(ref, cfg, solver, pool_u, rtols)
    if make_system is None:
        system = sp.system(solver["entry"]).build(cfg, solver, traffic,
                                                  pool_e, tols)
    else:
        system = make_system(cfg, traffic, pool_e, tols, checker)
    system.warm()
    counter = CompileCounter()
    setup_s = time.perf_counter() - T_START
    _say("resolved", cell=args.workload, compile_cache=cache_dir,
         **system.info())

    sample = loadgen.Reservoir(int(traffic["sample"]), args.seed)
    shape = sp.loop(traffic["loop"])
    counter.armed = True
    if args.trace:
        seconds = min(args.seconds, float(traffic["trace_seconds"]))
        loop, tsum = traced_window(shape, system, pool_e, traffic, seconds,
                                   args.seed, sample, HERE / "out" / "trace")
    else:
        loop = loadgen.window(shape, system, pool_e, traffic, args.seconds,
                              args.seed, sample)
        tsum = None
    counter.armed = False
    _say("window", compilations=counter.count, window_s=loop["window_s"],
         **loop.get("report", {}))

    used = devices[:cell["chips"]] if require_tpu else devices[:1]
    mem = [d.memory_stats() or {} for d in used]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(m.get("peak_bytes_in_use", 0)
                                       for m in mem)}
    attempted, failed = outcome(loop, cfg)
    run = types.SimpleNamespace(
        cell=cell, cfg=cfg, traffic=traffic, setup_s=setup_s, loop=loop,
        window_s=loop["window_s"], trace=tsum, n=int(cfg["n"]),
        dofs=box.nelt * int(cfg["n"]) ** 3, peak=peak)
    metrics = {}
    for m in sp.metrics_for(args.workload, bool(args.trace)):
        value = sp.metric(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    del system
    pool_e.clear()
    gc.collect()
    checks = check.verdict(checker.numbers(sample.items, list(cfg["check"])),
                           cfg["check"])
    correct = bool(attempted and not failed
                   and all(c["ok"] for c in checks))
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if tsum is not None:
        device["busy_s"] = tsum["mean"]["busy_ns"] / 1e9
        device["window_s"] = tsum["window_ns"] / 1e9
        result["breakdown"] = {
            "device_ops": [[k, v / 1e9] for k, v in tsum["top_ops"]],
            "idle_gaps": [[k, v / 1e9] for k, v in tsum["idle_gaps"]]}
    checks.append({"name": "failed_solves", "value": failed, "limit": 0})
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    sys.stdout.flush()
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
