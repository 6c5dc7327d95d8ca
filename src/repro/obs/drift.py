"""Cost-model drift detection — do the ``cost.py`` books still describe
the compiled programs? (DESIGN.md §14.3)

The repo's performance story is an *exact* stream/byte ledger
(:mod:`repro.core.cost`) pinned against measured benches.  Nothing so
far checked the ledger against the **programs**: a kernel that grows an
extra operand, a driver that re-materializes a window per iteration, or
a sharded cycle that picks up a second psum would silently invalidate
every pinned byte row.  This module closes that loop:

* **bytes/iter** — trace the public driver of each pipeline
  (``jax.make_jaxpr``; no execution), walk the jaxpr, and charge array
  traffic at the *stream boundaries*: ``pallas_call`` equations and
  leaf equations (no sub-jaxpr) get their operands/results billed;
  structural equations (pjit/while/scan) are descended into.  For the
  loop-driven v2 family the per-iteration cost is the body of the
  **max-traffic loop** (the CG iteration — inner coarse/smoother loops
  charge less); for s-step the two per-cycle launches come from
  :func:`repro.core.cg_sstep.sstep_cycle_traceables` and are divided
  by ``s``.  The measured bytes/DOF/iter are compared against
  ``cost.bytes_per_dof_iter(..., exact=True)`` as a **ratio** held in a
  per-pipeline calibrated band (:data:`STREAM_BYTE_BANDS`): the jaxpr
  boundary deliberately over-counts the book wherever a pipeline
  materializes halo windows at the XLA level (the book charges those as
  redundant *kernel reads*, not separate gather writes), so the fused
  v2 family sits at ratio ~1.03 while s-step's per-cycle p/r window
  extensions put it at ~2.2.  The band *is* the pin: a kernel or book
  change that moves real traffic lands outside it.

* **collectives** — the jaxpr collective-primitive walk
  (:func:`repro.distributed.sstep.count_collectives`) against the
  pinned contracts: the single-device v2 family is collective-free and
  the sharded s-step cycle is exactly ``{"ppermute": 2, "psum": 1}``
  with a collective-free update (DESIGN.md §10).

``check()`` returns a :class:`DriftReport` (JSON-able ``model_drift``
payload with provenance); ``assert_no_drift()`` raises
:class:`ModelDriftError` with the offending rows — the loud failure the
``obs-smoke`` CI leg runs on fused_v2, fused_v2_jacobi, and sstep_v3.
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["DriftRow", "DriftReport", "ModelDriftError",
           "DEFAULT_PIPELINES", "STREAM_BYTE_BANDS",
           "EXPECTED_COLLECTIVES", "charge_streams",
           "measure_call_bytes", "measure_iteration_bytes",
           "check_bytes", "check_collectives", "check", "assert_no_drift"]


#: Pipelines the drift gate covers by default (the acceptance set).
DEFAULT_PIPELINES = ("fused_v2", "fused_v2_jacobi", "sstep_v3")

#: Calibrated (lo, hi) bands for measured/model *total* bytes/DOF/iter.
#: Calibration (CPU, jax 0.9.0, n=10, grid=(8,8,16), sz=2, f32, the
#: (n, n^2, E) kernel layout): fused_v2 1.08, fused_v2_jacobi 1.07 — the
#: jaxpr boundary matches the book up to the XLA-side plane shift (~3%)
#: and the O(n^4) operator constants (Kronecker and face matrices) each
#: call reads; sstep_v3 2.00 — the per-cycle p/r window extensions are
#: XLA gathers the book prices as redundant kernel reads only.  A
#: transpose of the state inside the loop would add ~2 streams per
#: operand and leave the band.  The band width absorbs jax-version jaxpr
#: differences; real kernel/book changes move the ratio far more than
#: the slack.
STREAM_BYTE_BANDS = {
    "fused_v2": (0.90, 1.15),
    "fused_v2_jacobi": (0.90, 1.15),
    "sstep_v3": (1.90, 2.60),
}

#: Pinned collective contracts per pipeline (single-device trace for the
#: v2 family; the DESIGN.md §10 sharded cycle/update contract for v3).
EXPECTED_COLLECTIVES = {
    "fused_v2": {},
    "fused_v2_jacobi": {},
    "sstep_v3": {"cycle": {"ppermute": 2, "psum": 1}, "update": {}},
}

# The drift case: paper degree (n=10) on the paper_case(1024) grid.  The
# kernels' operator constants (Kronecker and face matrices, O(n^4) words)
# do not scale with E, so the grid must hold enough elements for the
# per-DOF streams the books price to dominate (at 2x2x4 the constants
# alone exceed the book).  Tracing only: no solve runs.
_DRIFT_N = 10
_DRIFT_GRID = (8, 8, 16)
_DRIFT_SZ = 2
_DRIFT_S = 4
_DRIFT_PRECISION = "f32"


# ---------------------------------------------------------------------------
# jaxpr stream-byte charging
# ---------------------------------------------------------------------------

def _nbytes(var) -> int:
    try:
        return int(np.prod(var.aval.shape)) * var.aval.dtype.itemsize
    except Exception:
        return 0                        # tokens / abstract units


def _subjaxprs(eqn):
    """Sub-jaxprs of an equation, duck-typed across jax versions
    (ClosedJaxpr has ``.jaxpr``, Jaxpr has ``.eqns``; they hide under
    different param keys — same convention as the collective walk in
    :mod:`repro.distributed.sstep`)."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if hasattr(v, "eqns"):
                yield v
            elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                yield v.jaxpr


def charge_streams(jaxpr) -> tuple[int, int]:
    """(read_bytes, write_bytes) charged at the stream boundaries of a
    jaxpr: ``pallas_call`` and leaf equations bill their operands and
    results; structural equations are descended into (their boundary
    arrays are not traffic — the kernels inside are)."""
    r = w = 0
    for eqn in jaxpr.eqns:
        subs = list(_subjaxprs(eqn))
        if eqn.primitive.name == "pallas_call" or not subs:
            r += sum(_nbytes(v) for v in eqn.invars)
            w += sum(_nbytes(v) for v in eqn.outvars)
        else:
            for sub in subs:
                sr, sw = charge_streams(sub)
                r += sr
                w += sw
    return r, w


def _loops(jaxpr, out: list) -> list:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("while", "scan"):
            out.append(eqn)
        for sub in _subjaxprs(eqn):
            _loops(sub, out)
    return out


def _loop_body(eqn):
    body = eqn.params.get("body_jaxpr") or eqn.params.get("jaxpr")
    return body.jaxpr if hasattr(body, "jaxpr") else body


def measure_call_bytes(fn, *args) -> tuple[int, int]:
    """Stream-boundary (read, write) bytes of one call of ``fn``."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    return charge_streams(closed.jaxpr)


def measure_iteration_bytes(fn, *args) -> tuple[int, int]:
    """Per-iteration (read, write) bytes of ``fn``'s main loop.

    Traces ``fn(*args)``, collects every while/scan (at any depth), and
    charges the body of the **max-traffic** one — the CG iteration
    dominates any inner coarse-solve or smoother loop.  Raises if the
    program has no loop (use :func:`measure_call_bytes`).
    """
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    cands = _loops(closed.jaxpr, [])
    if not cands:
        raise ValueError("traced program has no while/scan loop")
    bodies = [charge_streams(_loop_body(e)) for e in cands]
    return max(bodies, key=sum)


# ---------------------------------------------------------------------------
# report types
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DriftRow:
    """One pipeline x one check."""

    pipeline: str
    check: str                          # "bytes_per_dof_iter"|"collectives"
    measured: object                    # bytes: [r, w]; collectives: dict
    expected: object
    ok: bool
    ratio: float | None = None          # bytes only: measured/model total
    band: tuple | None = None
    detail: str = ""

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class DriftReport:
    """The ``model_drift`` payload: one row per (pipeline, check)."""

    rows: list

    @property
    def ok(self) -> bool:
        return all(row.ok for row in self.rows)

    def failures(self) -> list:
        return [row for row in self.rows if not row.ok]

    def to_dict(self) -> dict:
        from repro.obs import trace

        return {"schema": "model-drift/1", "ok": self.ok,
                "provenance": trace.provenance(),
                "rows": [row.to_dict() for row in self.rows]}


class ModelDriftError(RuntimeError):
    """The cost books no longer describe the compiled program."""


# ---------------------------------------------------------------------------
# per-pipeline checks
# ---------------------------------------------------------------------------

def _drift_case(precision: str):
    from repro.core.nekbone import NekboneCase

    return NekboneCase(n=_DRIFT_N, grid=_DRIFT_GRID, ax_impl="fused",
                       precision=precision)


def _v2_driver(case, pipeline: str, precision: str, sz: int, niter: int):
    """The public fused-v2 driver closed over the drift case's operator
    (sz pinned so no measured autotune sweep runs)."""
    from repro.core.precond import pcg_fused_v2_fixed_iters

    spec = (case.precond_spec("jacobi")
            if pipeline == "fused_v2_jacobi" else None)

    def drv(f):
        return pcg_fused_v2_fixed_iters(
            f, D=case.D, g=case.g, grid=case.grid, niter=niter,
            precond=spec, mask=case.mask, c=case.c, precision=precision,
            interpret=True, sz=sz)

    return drv


def check_bytes(pipeline: str, *, precision: str = _DRIFT_PRECISION,
                sz: int = _DRIFT_SZ, s: int = _DRIFT_S) -> DriftRow:
    """Measured vs modeled bytes/DOF/iter for one pipeline."""
    from repro.core import cost

    if pipeline not in STREAM_BYTE_BANDS:
        raise ValueError(
            f"no calibrated drift band for pipeline {pipeline!r} "
            f"(known: {sorted(STREAM_BYTE_BANDS)})")
    case = _drift_case(precision)
    ndof = case.mesh.nelt * _DRIFT_N ** 3
    if pipeline == "sstep_v3":
        from repro.core.cg_sstep import sstep_cycle_traceables

        (pw, pa), (up, ua) = sstep_cycle_traceables(
            case.D, case.g, _DRIFT_GRID, s=s, sz=sz, precision=precision)
        pr, pww = measure_call_bytes(pw, *pa)
        ur, uw = measure_call_bytes(up, *ua)
        meas_r = (pr + ur) / s / ndof
        meas_w = (pww + uw) / s / ndof
        rm, wm = cost.bytes_per_dof_iter(pipeline, precision, exact=True,
                                         n=_DRIFT_N, sz=sz, s=s)
    else:
        _, f = case.manufactured()
        drv = _v2_driver(case, pipeline, precision, sz, niter=3)
        r, w = measure_iteration_bytes(drv, f)
        meas_r, meas_w = r / ndof, w / ndof
        rm, wm = cost.bytes_per_dof_iter(pipeline, precision, exact=True,
                                         n=_DRIFT_N, sz=sz)
    ratio = (meas_r + meas_w) / (rm + wm)
    lo, hi = STREAM_BYTE_BANDS[pipeline]
    ok = lo <= ratio <= hi
    return DriftRow(
        pipeline=pipeline, check="bytes_per_dof_iter",
        measured=[round(meas_r, 3), round(meas_w, 3)],
        expected=[round(rm, 3), round(wm, 3)], ok=ok,
        ratio=round(ratio, 4), band=(lo, hi),
        detail=(f"measured/model total ratio {ratio:.3f} "
                f"{'within' if ok else 'OUTSIDE'} [{lo}, {hi}] "
                f"(n={_DRIFT_N}, grid={_DRIFT_GRID}, sz={sz})"))


def check_collectives(pipeline: str, *,
                      precision: str = _DRIFT_PRECISION,
                      sz: int = _DRIFT_SZ, s: int = _DRIFT_S) -> DriftRow:
    """Measured vs pinned collective counts for one pipeline."""
    if pipeline not in EXPECTED_COLLECTIVES:
        raise ValueError(
            f"no pinned collective contract for pipeline {pipeline!r} "
            f"(known: {sorted(EXPECTED_COLLECTIVES)})")
    expected = EXPECTED_COLLECTIVES[pipeline]
    if pipeline == "sstep_v3":
        from repro.distributed.sstep import cycle_collective_counts

        measured = cycle_collective_counts(grid=_DRIFT_GRID, n=_DRIFT_N,
                                           s=s, sz=sz, ndev=1,
                                           precision=precision)
        where = "sharded cycle/update at ndev=1"
    else:
        from repro.distributed.sstep import count_collectives

        case = _drift_case(precision)
        _, f = case.manufactured()
        drv = _v2_driver(case, pipeline, precision, sz, niter=3)
        measured = count_collectives(drv, f)
        where = "single-device driver"
    ok = measured == expected
    return DriftRow(
        pipeline=pipeline, check="collectives", measured=measured,
        expected=expected, ok=ok,
        detail=(f"{where}: {'matches' if ok else 'DRIFTED from'} "
                f"the pinned contract"))


def check(pipelines=DEFAULT_PIPELINES, *,
          precision: str = _DRIFT_PRECISION) -> DriftReport:
    """Run both drift checks over ``pipelines``; never raises on drift —
    inspect ``report.ok`` / call :func:`assert_no_drift`."""
    rows = []
    for pipeline in pipelines:
        rows.append(check_bytes(pipeline, precision=precision))
        rows.append(check_collectives(pipeline, precision=precision))
    return DriftReport(rows=rows)


def assert_no_drift(report: DriftReport | None = None,
                    pipelines=DEFAULT_PIPELINES) -> DriftReport:
    """Run (or take) a drift report and fail loudly on any drifted row."""
    if report is None:
        report = check(pipelines)
    if not report.ok:
        lines = [f"  {row.pipeline}/{row.check}: measured={row.measured} "
                 f"expected={row.expected} ({row.detail})"
                 for row in report.failures()]
        raise ModelDriftError(
            "cost-model drift detected — core/cost.py books no longer "
            "describe the compiled pipelines:\n" + "\n".join(lines))
    return report
