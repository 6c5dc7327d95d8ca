"""Solver service (launch/solver_service.py): queue, buckets, dispatch.

The scheduling rules the serving layer promises (DESIGN.md §12):

* an empty queue drains to ``[]`` with zero dispatches;
* requests in different buckets — any difference in (grid, n, dtype,
  pipeline, precision, precond, stopping rule) — are NEVER co-scheduled;
* a bucket with more pending requests than ``max_b`` splits into
  ceil(k/max_b) dispatches, none exceeding ``max_b``;
* results return in submission order with correct request ids, and each
  answer equals the equivalent direct registry solve (parity through the
  batching layer).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.configs.nekbone import NekboneConfig
from repro.launch.solver_service import SolveRequest, SolverService


def _cfg(**over):
    base = dict(name="svc", n=4, grid=(2, 2, 2), dtype="float32",
                ax_impl="pallas_fused_cg_v2")
    base.update(over)
    return NekboneConfig(**base)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    case = cfg.make_case()
    _, f = case.manufactured()
    return cfg, case, f


def test_empty_queue_drains_empty():
    svc = SolverService(max_b=4)
    assert svc.drain() == []
    assert svc.dispatch_log == []
    assert svc.pending == 0


def test_mixed_buckets_never_co_scheduled(setup):
    cfg, case, f = setup
    cfg_pc = _cfg(precond="jacobi")
    cfg_tol = cfg                       # same case, different stopping rule
    svc = SolverService(max_b=8)
    ids_a = [svc.submit(SolveRequest(f=f, config=cfg, niter=4))
             for _ in range(2)]
    ids_b = [svc.submit(SolveRequest(f=f, config=cfg_pc, niter=4))]
    ids_c = [svc.submit(SolveRequest(f=f, config=cfg_tol, tol=1e-6))]
    results = svc.drain()
    assert [r.request_id for r in results] == ids_a + ids_b + ids_c
    assert len(svc.dispatch_log) == 3
    groups = [set(rids) for _, rids in svc.dispatch_log]
    assert set(ids_a) in groups
    assert set(ids_b) in groups
    assert set(ids_c) in groups
    # bucket keys of the three dispatches are pairwise distinct
    assert len({k for k, _ in svc.dispatch_log}) == 3


def test_bucket_overflow_splits(setup):
    cfg, case, f = setup
    svc = SolverService(max_b=3)
    ids = [svc.submit(SolveRequest(f=f, config=cfg, niter=3))
           for _ in range(7)]
    results = svc.drain()
    assert [r.request_id for r in results] == ids
    sizes = [len(rids) for _, rids in svc.dispatch_log]
    assert sizes == [3, 3, 1]           # ceil(7/3) chunks, order kept
    assert all(s <= svc.max_b for s in sizes)
    assert [r.batch_size for r in results] == [3, 3, 3, 3, 3, 3, 1]


def test_batched_answers_match_direct_solve(setup):
    cfg, case, f = setup
    svc = SolverService(max_b=4)
    rng = np.random.default_rng(1)
    fs = [f, jnp.asarray(rng.standard_normal(f.shape),
                         jnp.float32) * case.mask]
    ids = [svc.submit(SolveRequest(f=fi, config=cfg, niter=6))
           for fi in fs]
    results = svc.drain()
    assert len(svc.dispatch_log) == 1   # one bucket, one dispatch
    # f32: the batched and the direct solve run the same arithmetic in two
    # compiled programs, which may round differently (operation fusion);
    # each of the 6 iterations can perturb x by one operator's round-off,
    # (4n+6) eps relative, so the lanes agree to 6 (4n+6) eps max|x|.
    eps = np.finfo(np.float32).eps
    for r, fi in zip(results, fs):
        direct = case.solve(fi, niter=6)
        x = np.asarray(direct.x, np.float64)
        bound = 6 * (4 * case.n + 6) * eps * np.abs(x).max()
        np.testing.assert_allclose(np.asarray(r.x, np.float64), x, rtol=0,
                                   atol=bound)
        assert r.pipeline == "fused_v2_rhs2"
        assert int(r.iters_taken) == 6


def test_warm_start_populates_caches(setup, tmp_path, monkeypatch):
    cfg, case, f = setup
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    from repro.kernels import autotune

    autotune.clear_cache()
    cfg_pc = _cfg(precond="jacobi")
    svc = SolverService(max_b=2)
    warmed = svc.warm_start([cfg, cfg_pc], batches=[1, 2], niter=1)
    assert warmed == 4
    # the cases are cached for subsequent dispatches
    assert len(svc._cases) == 2
    # the warm-up holds exactly the keys the routes' drivers read: a
    # preconditioned batch goes per RHS, so it reads no "rhs:2" key
    plain = ("cfg", "slab", 4, 2, 2, 2, "float32", "float32",
             autotune.jax.default_backend())
    assert set(autotune.cache_info()) == {
        plain, plain + ("rhs:2",), plain + ("pc:jacobi",)}
    # and a solve of each warmed batch misses the autotune cache nowhere
    for c in (cfg, cfg_pc):
        for b in (1, 2):
            misses = autotune.cache_stats()["misses"]
            for _ in range(b):
                svc.submit(SolveRequest(f=f, config=c, niter=2))
            svc.drain()
            assert autotune.cache_stats()["misses"] == misses, (c, b)
    autotune.clear_cache(disk=False)


def test_rejects_bad_max_b():
    with pytest.raises(ValueError, match="max_b"):
        SolverService(max_b=0)


# ---------------------------------------------------------------------------
# DispatchRecord: the tuple shim (ISSUE 10 satellite).  dispatch_log used
# to hold (bucket, request_ids) tuples; the dataclass must keep every
# legacy access pattern working — the asserts above (`!= []`, iteration
# unpacking, `{k for k, _ in ...}`, len) already exercise most of it,
# this pins the rest explicitly so a refactor cannot silently drop it.
# ---------------------------------------------------------------------------

def test_dispatch_record_tuple_shim():
    from repro.launch.solver_service import DispatchRecord

    rec = DispatchRecord(bucket=("bk",), request_ids=[1, 2, 3],
                         wall_us=5.0, pipeline="fused_v2_rhs3")
    # legacy tuple protocol: 2-tuple of (bucket, request_ids)
    assert len(rec) == 2
    assert rec[0] == ("bk",) and rec[1] == [1, 2, 3]
    bucket, rids = rec
    assert bucket == ("bk",) and rids == [1, 2, 3]
    assert rec == (("bk",), [1, 2, 3])
    assert rec != (("other",), [1, 2, 3])
    # equality against another record compares the same 2-tuple view
    assert rec == DispatchRecord(bucket=("bk",), request_ids=[1, 2, 3])
    # hashable (bucket keys land in sets in the tests above)
    assert isinstance(hash(rec), int)
    # batch_size fills from request_ids when not given
    assert rec.batch_size == 3


def test_dispatch_log_records_carry_telemetry(setup):
    cfg, case, f = setup
    from repro.launch.solver_service import DispatchRecord

    svc = SolverService(max_b=2)
    for _ in range(3):
        svc.submit(SolveRequest(f=f, config=cfg, niter=2))
    svc.drain()
    assert len(svc.dispatch_log) == 2
    for rec in svc.dispatch_log:
        assert isinstance(rec, DispatchRecord)
        assert rec.wall_us > 0
        assert rec.pipeline is not None
    assert [r.batch_size for r in svc.dispatch_log] == [2, 1]
    snap = svc.metrics.snapshot()
    assert snap["dispatches"] == 2
    assert snap["requests_served"] == 3
    assert snap["queue_high_water"] == 3
    assert snap["latency_ms"]["count"] == 2
