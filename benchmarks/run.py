# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark driver: paper Fig. 2/3 (version ladder), Fig. 4 (measured
roofline), §III-A Eq. 1-2 (cost-model adherence).

  PYTHONPATH=src python -m benchmarks.run

Besides the CSV on stdout, the full result set is written as
``BENCH_<tag>.json`` (machine readable: rows + the stream-per-iteration
ladder + the per-precision bytes/DOF/iter table + us/call) under
``$REPRO_BENCH_DIR`` (default ``benchmarks/out``), with ``tag`` from
``$REPRO_BENCH_TAG`` (default ``local``) — CI uploads it as an artifact
and ``benchmarks/check_regression.py`` diffs it against the committed
``benchmarks/baseline/BENCH_baseline.json`` so the ladder cannot silently
regress.

The JSON is written atomically (tmp + rename): a crash mid-write can
never leave a corrupt ``BENCH_<tag>.json`` for the regression gate (or a
later run) to trip over, and an unwritable ``$REPRO_BENCH_DIR`` degrades
to a clear one-line error after the CSV instead of a traceback.
"""
from __future__ import annotations

import json
import os
import pathlib
import sys


def _bench_json_path() -> pathlib.Path:
    out_dir = pathlib.Path(os.environ.get("REPRO_BENCH_DIR",
                                          "benchmarks/out"))
    tag = os.environ.get("REPRO_BENCH_TAG", "local")
    return out_dir / f"BENCH_{tag}.json"


def write_json_atomic(path: pathlib.Path, payload: dict) -> bool:
    """Atomically (tmp + rename) write ``payload`` as JSON to ``path``.

    Returns False — after printing a clear one-line error to stderr —
    instead of raising when the directory is unwritable, the path is
    occupied by a directory, or any other OSError fires; the rename is
    atomic, so a stale ``BENCH_<tag>.json`` is either fully replaced or
    untouched, never half-written.
    """
    tmp = None
    try:
        path = pathlib.Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(path.name + f".tmp.{os.getpid()}")
        tmp.write_text(json.dumps(payload, indent=1))
        tmp.replace(path)
        return True
    except OSError as e:
        print(f"# ERROR: could not write bench json {path}: {e} "
              "(CSV above is complete; set $REPRO_BENCH_DIR to a writable "
              "directory to keep the machine-readable copy)",
              file=sys.stderr)
        if tmp is not None:
            try:
                tmp.unlink(missing_ok=True)
            except OSError:
                pass
        return False


def _precision_table() -> dict:
    """The ndof-independent bytes/DOF/iter table the regression gate holds.

    Every (pipeline rung, precision policy) point of DESIGN.md §6-7:
    stream counts are pipeline constants, the policy prices the bytes —
    bf16 is exactly half of f32 on every rung, which
    check_regression.py asserts.  Each entry carries both books: the
    headline ``read``/``write`` (side channels charged as zero, the §6
    convention) and the ``read_exact``/``write_exact`` column that folds
    in the modeled side channels (v2 boundary planes, v3 matrix-powers
    halo — ``cost.bytes_per_dof_iter(exact=True)`` at the paper's n=10
    with the default slab split).

    The ``<pipeline>_d8`` rows (schema v5, DESIGN.md §10) price the
    *sharded* pipelines at the 8-device strong-scaling point of the paper
    grid (EZ=32, ez_local=4): exact books only — ``read``/``write`` are
    ``bytes_per_dof_iter(exact=True, ndev=8, ez=32)``, the per-device
    collective channel folded in and split evenly — since a headline
    column that ignores the network would be meaningless for a
    distributed rung.  The bf16 == f32/2 invariant holds there too (every
    channel scales with the storage itemsize).
    """
    from repro.core import cost

    table = {}
    for pipeline in cost.PIPELINE_STREAMS:
        table[pipeline] = {}
        for pol in ("f64", "f32", "bf16"):
            rb, wb = cost.bytes_per_dof_iter(pipeline, pol)
            re_, we = cost.bytes_per_dof_iter(pipeline, pol, exact=True)
            table[pipeline][pol] = {"read": rb, "write": wb,
                                    "read_exact": round(re_, 4),
                                    "write_exact": round(we, 4)}
    for pipeline in ("fused_v2", "fused_v2_jacobi", "fused_v2_cheb",
                     "sstep_v3"):
        entry = {}
        for pol in ("f64", "f32", "bf16"):
            re_, we = cost.bytes_per_dof_iter(pipeline, pol, exact=True,
                                              ndev=8, ez=32)
            entry[pol] = {"read": round(re_, 4), "write": round(we, 4)}
        table[pipeline + "_d8"] = entry
    return table


def _streams_ladder() -> dict:
    """The Eq.-2 fusion ladder (reads+writes per DOF per CG iteration) —
    the cross-PR perf-trajectory headline the gate matches *exactly*.

    The s-step rung is amortized per iteration (4s+9 streams per s
    iterations, DESIGN.md §8); its s=1 point must stay exactly the v2
    number.  The PCG rungs (DESIGN.md §9) are per-iteration too: Jacobi is
    v2 + 1 (the fused diagonal stream), Chebyshev is v2 + 5 (the
    polynomial apply kernel) with the win booked in iteration count.  The
    ``*_sharded_d8`` rungs (DESIGN.md §10) are *effective* per-device
    streams of the z-sharded drivers at the 8-device strong-scaling point
    (EZ=32): headline + halo + the per-device collective channel.
    """
    from repro.core import cost

    return {
        "eq2": cost.CG_READ_STREAMS + cost.CG_WRITE_STREAMS,
        "fused_v1": (cost.FUSED_CG_READ_STREAMS
                     + cost.FUSED_CG_WRITE_STREAMS),
        "fused_v2": (cost.FUSED_V2_READ_STREAMS
                     + cost.FUSED_V2_WRITE_STREAMS),
        "sstep_v3": sum(cost.sstep_streams(cost.SSTEP_DEFAULT_S)),
        "sstep_v3_s1": sum(cost.sstep_streams(1)),
        "fused_v2_jacobi": (cost.JACOBI_V2_READ_STREAMS
                            + cost.JACOBI_V2_WRITE_STREAMS),
        "fused_v2_cheb": (cost.CHEB_V2_READ_STREAMS
                          + cost.CHEB_V2_WRITE_STREAMS),
        "sstep_v3_sharded_d8": cost.sstep_effective_streams(
            cost.SSTEP_DEFAULT_S, 4, ndev=8, ez=32),
        "fused_v2_jacobi_sharded_d8": (
            cost.JACOBI_V2_READ_STREAMS + cost.JACOBI_V2_WRITE_STREAMS
            + cost.v2_plane_collective_streams(10, 32 // 8)),
        "fused_v2_cheb_sharded_d8": cost.cheb_effective_streams(
            cost.CHEB_DEFAULT_K, 4, ndev=8, ez=32, n=10),
        # p-multigrid rung (schema v8, DESIGN.md §13): the full symmetric
        # V-cycle's per-iteration budget at the paper's n=10 ladder —
        # deliberately the most streams/iter of any rung; the win is the
        # iteration count (pcg_iters_tol rows).
        "fused_v2_pmg": sum(cost.pmg_streams(10)),
        # multi-RHS rungs (schema v7, DESIGN.md §12): per-RHS streams of
        # the batched block pipeline — the shared operator streams divide
        # by b, the per-RHS vector streams stay put.
        **{f"{base}_rhs{b}": cost.streams_per_rhs(b, base)
           for base in ("fused_v2", "sstep_v3")
           for b in cost.MULTI_RHS_BATCHES},
    }


def _streams_per_rhs_table() -> dict:
    """Per-RHS streams vs batch (schema v7, DESIGN.md §12) — the
    amortization curve check_regression.py holds exactly AND requires to
    be strictly decreasing in b on every pipeline (the whole point of the
    block solver: a bigger batch must never cost more per RHS)."""
    from repro.core import cost

    return {base: {str(b): cost.streams_per_rhs(b, base)
                   for b in (1,) + cost.MULTI_RHS_BATCHES}
            for base in ("fused_v2", "sstep_v3")}


def _solver_service_section(quick: bool) -> dict:
    """Latency/throughput rows from the solver-service bench (schema v7).

    Measured (wall-clock) — gated like the us/iter table: presence is
    checked when the baseline pins it, values are never hard-gated.  The
    quick profile keeps the interpret-mode CI leg to seconds.
    """
    from repro.launch.solver_service import bench_service

    if quick:
        return bench_service(nelt=64, n=4, requests=4, max_b=2,
                             niter=3, repeats=1)
    return bench_service(nelt=64, requests=16, max_b=8, niter=25)


def _us_per_iter_table(sections: list) -> dict:
    """Measured wall-clock (us) of the per-iteration pipeline rungs.

    Extracted from the version-ladder section's ``*_iter_*`` rows — the
    fused v1/v2 iterations, the s-step cycles, and the PCG rungs — keyed
    by row name.  check_regression.py holds each entry within a relative
    band against the baseline *when the reference backend matches*
    (DESIGN.md §11): wall time is only comparable measured on the same
    backend kind, so the table travels with a ``reference_backend``
    record and cross-backend comparisons degrade to warnings.
    """
    table = {}
    for sec in sections:
        if not sec["module"].endswith("bench_ax_versions"):
            continue
        for row in sec["rows"]:
            if "_iter_" in row["name"] and row["us_per_call"] > 0.0:
                table[row["name"]] = row["us_per_call"]
    return table


def _reference_backend() -> str:
    import jax

    return jax.default_backend()


def _telemetry_section() -> dict:
    """Observability summary travelling with the bench (schema v9).

    The cost-model drift check (obs/drift.py) re-measured at bench time:
    per-pipeline measured-vs-book byte ratios and collective contracts.
    Summary-only (ok flag + per-row ratios) — the full report lives in
    the obs-smoke CI leg; here it stamps the bench JSON so a drifting
    model is visible next to the numbers it prices.  Never value-gated
    by check_regression.py; a failure of the check itself fails the run.
    """
    from repro.obs import drift

    report = drift.check()
    return {
        "drift": {
            "ok": report.ok,
            "rows": [{"pipeline": r.pipeline, "check": r.check,
                      "ok": r.ok, "ratio": r.ratio}
                     for r in report.rows],
        },
    }


def main() -> None:
    from benchmarks import bench_ax_versions, bench_cost_model, bench_roofline
    from repro.compile_cache import configure_caches
    from repro.obs import trace

    configure_caches()
    sections = []
    print("name,us_per_call,derived")
    # one env var away from a named profiler timeline (DESIGN.md §14):
    # $REPRO_PROFILE_DIR wraps the whole ladder in jax.profiler traces.
    with trace.profiling(os.environ.get("REPRO_PROFILE_DIR")):
        for mod, title in ((bench_ax_versions, "Fig2/3: Ax version ladder"),
                           (bench_roofline, "Fig4: measured roofline"),
                           (bench_cost_model, "Eq1-2: cost model")):
            print(f"# --- {title} ---", file=sys.stderr)
            rows = []
            for name, us, derived in mod.run():
                print(f"{name},{us:.1f},{derived}")
                rows.append({"name": name, "us_per_call": round(us, 1),
                             "derived": derived})
            sections.append({"title": title, "module": mod.__name__,
                             "rows": rows})

    quick = bool(os.environ.get("REPRO_BENCH_QUICK"))
    payload = {
        "schema": "repro-bench/9",
        # monotone int for forward-compat decisions (check_regression.py
        # warns on version skew instead of failing on unknown tables).
        # v5: sharded rungs — *_sharded_d8 ladder entries and the
        # <pipeline>_d8 per-device byte rows (DESIGN.md §10).
        # v6: measured-time rows — the us_per_iter table + the
        # reference_backend record it is only comparable under
        # (DESIGN.md §11); the gate holds each entry within a relative
        # band alongside the exact stream ladder.
        # v7: multi-RHS rungs — *_rhs{b} ladder entries + byte rows, the
        # streams_per_rhs amortization table (exact + strictly decreasing
        # in b), and the measured solver_service latency/throughput
        # section (DESIGN.md §12).
        # v8: p-multigrid rung — fused_v2_pmg ladder entry + byte rows
        # (headline and exact V-cycle books, DESIGN.md §13) and the
        # pcg_pmg_iter / extended pcg_iters_tol measured rows; baseline
        # refreshed for the new rows.
        # v9: observability — a full ``provenance`` record (machine tag,
        # python/jax versions, backend, x64 flag; DESIGN.md §14) that
        # check_regression.py uses to *explain* reference_backend
        # mismatches, and a ``telemetry`` section carrying the
        # cost-model drift summary (never value-gated).
        "schema_version": 9,
        "tag": os.environ.get("REPRO_BENCH_TAG", "local"),
        "quick": quick,
        "reference_backend": _reference_backend(),
        "provenance": trace.provenance(),
        "telemetry": _telemetry_section(),
        "streams_per_iter": _streams_ladder(),
        # the second axis of the ladder (DESIGN.md §7): bytes each stream
        # carries under each precision policy, per DOF per iteration.
        "bytes_per_dof_iter": _precision_table(),
        "streams_per_rhs": _streams_per_rhs_table(),
        "us_per_iter": _us_per_iter_table(sections),
        "solver_service": _solver_service_section(quick),
        "sections": sections,
    }
    path = _bench_json_path()
    if write_json_atomic(path, payload):
        print(f"# wrote {path}", file=sys.stderr)


if __name__ == '__main__':
    main()
