"""Solver service: queued solve requests, bucketed onto batched solves.

The serving story for the multi-RHS fast path (DESIGN.md §12): clients
submit single right-hand sides; the service groups compatible requests —
same (grid, n, dtype, precision, precond, stopping rule) — into buckets
and dispatches each bucket as ONE multi-RHS block solve of batch up to
``max_b`` through the driver registry (:func:`repro.core.solvers.
solve_case`).  The batched v2 kernels amortize the shared operator
streams over the batch (:func:`repro.core.cost.multi_rhs_streams`), so a
full bucket is strictly cheaper per RHS than ``b`` sequential solves.

Rules (pinned by tests/test_solver_service.py):
  * requests in *different* buckets are never co-scheduled — a dispatch
    contains one bucket only;
  * a bucket with more than ``max_b`` pending requests splits into
    ceil(k / max_b) dispatches (overflow never silently truncates);
  * ``drain()`` on an empty queue returns ``[]`` and dispatches nothing;
  * results come back in submission order, each carrying its request id.

Warm start: :meth:`SolverService.warm_start` runs one solve of each
expected (bucket, batch) shape through its route, so the route's driver
populates the autotune cache with the key it reads (``$REPRO_CACHE_DIR`` —
the JSON layer persists across processes, so a deploy can ship a
pre-baked cache) and the solver is compiled, taking the measuring sweep
and the XLA compile off the first request's latency.

Bench: ``python -m repro.launch.solver_service --requests 32 --max-b 8``
emits latency/throughput rows (consumed by benchmarks/run.py, schema v7).
"""
from __future__ import annotations

import argparse
import dataclasses
import itertools
import time
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.cg import SolveResult

__all__ = ["SolveRequest", "ServiceResult", "DispatchRecord",
           "SolverService", "bench_service"]


@dataclasses.dataclass
class SolveRequest:
    """One queued solve: a right-hand side plus its case/stopping params.

    ``config`` is a :class:`repro.configs.nekbone.NekboneConfig` (the
    case is instantiated once per distinct case key and cached).
    ``precond=None`` inherits the config's preconditioner; pass a
    registry name to override (the boolean spellings are deprecated at
    the solve layer and not accepted here).
    """

    f: Any                                  # (E, n, n, n) rhs
    config: Any                             # NekboneConfig
    niter: int | None = None
    tol: float = 1e-8
    max_iter: int = 1000
    precond: str | None = None
    request_id: int = -1                    # assigned by submit()


@dataclasses.dataclass
class ServiceResult:
    """Per-request outcome of a dispatched bucket solve."""

    request_id: int
    x: Any
    history: Any
    iters_taken: Any
    achieved_rtol: Any
    rnorm: Any
    pipeline: str | None
    precond: str | None
    bucket: tuple                           # the bucket key it ran under
    batch_size: int                         # b of the dispatch it rode in
    batch_index: int                        # its lane in that dispatch


@dataclasses.dataclass(eq=False)
class DispatchRecord:
    """One dispatched batch: the audit row of ``SolverService.dispatch_log``.

    Promoted from the ad-hoc ``(bucket, request_ids)`` tuple; the typed
    fields feed :class:`repro.obs.metrics.ServiceMetrics` and the trace.

    Deprecation shim: the old tuple shape still works — iterating or
    indexing a record yields ``(bucket, request_ids)`` and records
    compare equal to that tuple (pinned by tests/test_solver_service.py)
    — but new code should use the named fields.
    """

    bucket: tuple
    request_ids: list
    batch_size: int = 0
    wall_us: float = 0.0
    pipeline: str | None = None

    def __post_init__(self):
        if not self.batch_size:
            self.batch_size = len(self.request_ids)

    # -- legacy (bucket, request_ids) tuple protocol --------------------
    def __iter__(self):
        return iter((self.bucket, self.request_ids))

    def __len__(self) -> int:
        return 2

    def __getitem__(self, i):
        return (self.bucket, self.request_ids)[i]

    def __eq__(self, other):
        if isinstance(other, tuple):
            return (self.bucket, self.request_ids) == other
        if isinstance(other, DispatchRecord):
            return ((self.bucket, self.request_ids)
                    == (other.bucket, other.request_ids))
        return NotImplemented

    def __hash__(self):
        return hash((self.bucket, tuple(self.request_ids)))


def _bucket_key(req: SolveRequest) -> tuple:
    """Compatibility key: everything that must match for two requests to
    share one batched solve (same compiled case + same stopping rule)."""
    cfg = req.config
    pc = req.precond if req.precond is not None else cfg.precond
    stop = (("niter", req.niter) if req.niter is not None
            else ("tol", float(req.tol), req.max_iter))
    return (tuple(cfg.grid), cfg.n, str(cfg.dtype), cfg.ax_impl,
            cfg.precision, pc, cfg.s, cfg.cheb_k, stop)


def _case_key(cfg) -> tuple:
    return (tuple(cfg.grid), cfg.n, str(cfg.dtype), cfg.ax_impl,
            cfg.precision, cfg.precond, cfg.s, cfg.cheb_k)


class SolverService:
    """Request queue + bucketed batch dispatch over the driver registry."""

    def __init__(self, *, max_b: int = 8):
        if max_b < 1:
            raise ValueError(f"max_b must be >= 1, got {max_b}")
        from repro.obs.metrics import ServiceMetrics

        self.max_b = max_b
        self._queue: list[SolveRequest] = []
        self._next_id = itertools.count()
        self._cases: dict[tuple, Any] = {}
        # One DispatchRecord per dispatched batch, in dispatch order —
        # the audit trail the scheduling tests pin (records still
        # unpack/compare as the legacy (bucket, request_ids) tuples).
        self.dispatch_log: list[DispatchRecord] = []
        # always-on queue/dispatch metrics (DESIGN.md §14.2): a handful
        # of host floats per dispatch, JSON-snapshot-able.
        self.metrics = ServiceMetrics()

    # ------------------------------------------------------------------
    @property
    def pending(self) -> int:
        return len(self._queue)

    def submit(self, req: SolveRequest) -> int:
        """Enqueue one request; returns its assigned request id."""
        rid = next(self._next_id)
        req.request_id = rid
        self._queue.append(req)
        self.metrics.observe_submit(len(self._queue))
        return rid

    # ------------------------------------------------------------------
    def _case_for(self, cfg):
        key = _case_key(cfg)
        case = self._cases.get(key)
        if case is None:
            case = cfg.make_case()
            self._cases[key] = case
        return case

    def _dispatch(self, bucket: tuple, chunk: list[SolveRequest]
                  ) -> list[ServiceResult]:
        from repro.core import solvers as solvers_mod

        from repro.kernels.timing import stopwatch
        from repro.obs import trace as _trace

        case = self._case_for(chunk[0].config)
        first = chunk[0]
        f = jnp.stack([jnp.asarray(r.f) for r in chunk])
        sw = stopwatch()
        with _trace.span("service.dispatch", batch=len(chunk),
                         max_b=self.max_b):
            res: SolveResult = solvers_mod.solve_case(
                case, f, b=len(chunk), niter=first.niter, tol=first.tol,
                max_iter=first.max_iter, precond=first.precond)
            jax.block_until_ready(res.x)
        wall = sw.us()
        self.dispatch_log.append(DispatchRecord(
            bucket=bucket, request_ids=[r.request_id for r in chunk],
            batch_size=len(chunk), wall_us=wall, pipeline=res.pipeline))
        self.metrics.observe_dispatch(bucket, len(chunk), self.max_b, wall)

        def lane(arr, j):
            a = jnp.asarray(arr)
            return a[j] if a.ndim and a.shape[0] == len(chunk) else a

        return [ServiceResult(
            request_id=r.request_id, x=res.x[j],
            history=lane(res.history, j),
            iters_taken=lane(res.iters_taken, j),
            achieved_rtol=lane(res.achieved_rtol, j),
            rnorm=lane(res.rnorm, j), pipeline=res.pipeline,
            precond=res.precond, bucket=bucket, batch_size=len(chunk),
            batch_index=j) for j, r in enumerate(chunk)]

    def drain(self) -> list[ServiceResult]:
        """Dispatch everything queued; results in submission order.

        Buckets are formed over the *current* queue contents; each bucket
        splits into chunks of at most ``max_b`` (in submission order) and
        each chunk is one batched solve.
        """
        if not self._queue:
            return []
        queue, self._queue = self._queue, []
        self.metrics.observe_depth(0)
        buckets: dict[tuple, list[SolveRequest]] = {}
        for req in queue:
            buckets.setdefault(_bucket_key(req), []).append(req)
        out: dict[int, ServiceResult] = {}
        for bucket, reqs in buckets.items():
            for lo in range(0, len(reqs), self.max_b):
                for sr in self._dispatch(bucket, reqs[lo:lo + self.max_b]):
                    out[sr.request_id] = sr
        return [out[r.request_id] for r in queue]

    # ------------------------------------------------------------------
    def warm_start(self, configs, *, batches=None, niter: int = 1) -> int:
        """Pre-tune and pre-compile the expected (case, batch) shapes.

        For every config × batch size: traces one ``niter``-iteration
        batched solve through the route a request of that batch takes, so
        the XLA executable is resident before the first real request and
        the route's driver has made the autotune pick it reads (in memory
        and in the ``$REPRO_CACHE_DIR`` JSON cache — ship that file to
        skip the measuring sweep entirely).  Returns the number of
        (case, b) combinations warmed.
        """
        from repro.core import solvers as solvers_mod

        batches = sorted(set(batches or (1, self.max_b)))
        warmed = 0
        for cfg in configs:
            case = self._case_for(cfg)
            _, f1 = case.manufactured()
            for b in batches:
                f = f1[None] if b == 1 else jnp.stack([f1] * b)
                res = solvers_mod.solve_case(case, f, b=b, niter=niter)
                jax.block_until_ready(res.x)
                warmed += 1
        return warmed


# ---------------------------------------------------------------------------
# latency / throughput bench (schema v7 `solver_service` rows)
# ---------------------------------------------------------------------------

def bench_service(*, nelt: int = 64, n: int | None = None,
                  requests: int = 16, max_b: int = 8,
                  niter: int = 25, warm: bool = True,
                  repeats: int = 3) -> dict:
    """Measure request latency and drain throughput at several batches.

    Submits ``requests`` manufactured-RHS requests and drains with
    ``max_b`` in {1, ..., max_b}: b=1 is the sequential baseline (one
    solve per request), larger b amortizes the operator streams.  Returns
    a payload row set ``{str(b): {latency_ms_per_request,
    throughput_req_s, dispatches}}`` plus the environment.
    """
    from repro.configs.nekbone import paper_case

    cfg = paper_case(nelt)
    if n is not None:
        cfg = dataclasses.replace(cfg, n=n)
    cfg = dataclasses.replace(cfg, ax_impl="pallas_fused_cg_v2")
    case = cfg.make_case()
    _, f1 = case.manufactured()
    rows: dict[str, dict] = {}
    bs = sorted({b for b in (1, 2, 4, 8) if b <= max_b} | {max_b})
    for b in bs:
        svc = SolverService(max_b=b)
        svc._cases[_case_key(cfg)] = case
        if warm:
            svc.warm_start([cfg], batches=[min(b, requests)], niter=niter)
        best = float("inf")
        dispatches = 0
        for _ in range(repeats):
            for _ in range(requests):
                svc.submit(SolveRequest(f=f1, config=cfg, niter=niter))
            t0 = time.perf_counter()
            results = svc.drain()
            jax.block_until_ready([r.x for r in results])
            dt = time.perf_counter() - t0
            best = min(best, dt)
            dispatches = len(svc.dispatch_log)
            svc.dispatch_log.clear()
        rows[str(b)] = {
            "latency_ms_per_request": best * 1e3 / requests,
            "throughput_req_s": requests / best,
            "dispatches": dispatches,
        }
    return {"nelt": cfg.nelt, "n": cfg.n, "niter": niter,
            "requests": requests, "backend": jax.default_backend(),
            "rows": rows}


def main():
    from repro.compile_cache import configure_caches

    configure_caches()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nelt", type=int, default=64)
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-b", type=int, default=8)
    ap.add_argument("--niter", type=int, default=25)
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()
    payload = bench_service(nelt=args.nelt, n=args.n,
                            requests=args.requests, max_b=args.max_b,
                            niter=args.niter, repeats=args.repeats)
    print(f"[solver-service] E={payload['nelt']} n={payload['n']} "
          f"niter={payload['niter']} requests={payload['requests']} "
          f"({payload['backend']})")
    for b, row in payload["rows"].items():
        print(f"  b<={b:>2}: {row['latency_ms_per_request']:8.2f} "
              f"ms/request  {row['throughput_req_s']:8.2f} req/s  "
              f"({row['dispatches']} dispatches)")


if __name__ == "__main__":
    main()
