"""CPU tests of the benchmark's vector cell (CEED BP6): its reference, its
system module, its two readers, and whole runs of a tiny copy.

The tiny copy is the cell's configuration at n=4 on a 2x2x4 grid, driven
through ``run.main`` with ``require_tpu=False`` as in
``test_chipbench_runs.py``.
"""
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import metriclib  # noqa: E402
import run as harness  # noqa: E402
import spec as spec_mod  # noqa: E402

CELL = "ceed_bp6_p7_e8192.jacobi_rtol"
V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _reader(name):
    return spec_mod.Spec().metric(name)


def _run(trace, solves, components=3):
    return types.SimpleNamespace(
        cfg={"components": components}, loop={"solves": list(solves)},
        trace=trace, n=8, dofs=8192 * 512, peak=V5E, window_s=1.0)


def _trace(pallas_ns, busy_ns):
    dev = {"busy_ns": busy_ns, "pallas_ns": pallas_ns}
    return {"n_devices": 1, "window_ns": 4e9, "devices": {"d": dev},
            "mean": dict(dev)}


# -- readers -----------------------------------------------------------------

def test_block_hbm_share_is_the_least_bytes_over_pallas_time():
    # 2 solves of 600 iterations, 1.2 ms of Pallas time per iteration: one
    # iteration's least bytes are 6 x 3 x 4,194,304 x 4 B = 302 MB, 0.3687
    # ms at 819 GB/s, so the share reads 30.7%.
    run = _run(_trace(1.2e6 * 1200, 2e9), [{"iters": 600}] * 2)
    share = _reader("kernels.block_hbm_share.solve").read(run)
    least_s = 6 * 3 * 8192 * 512 * 4 / 819e9
    assert share == pytest.approx(100 * least_s / 1.2e-3)
    assert share == pytest.approx(30.72, abs=0.01)
    assert _reader("kernels.block_hbm_share.solve").read(
        _run(None, [{"iters": 600}])) is None


def test_vector_roofline_counts_every_component():
    run = _run(_trace(1e9, 2e9), [{"iters": 600}] * 2)
    vec = _reader("nekbone_roofline.vector.solve").read(run)
    one = metriclib.roofline_pct(run)
    assert vec == pytest.approx(3 * one)
    assert 0 < vec < 100
    assert _reader("nekbone_roofline.vector.solve").read(
        _run(None, [{"iters": 600}])) is None


# -- the reference -----------------------------------------------------------

def test_the_vector_reference_is_the_scalar_reference_per_component():
    sp = spec_mod.Spec()
    vec = sp.reference("sem_vector_poisson").Box(4, (2, 2, 4))
    one = sp.reference("sem_poisson").Box(4, (2, 2, 4))
    rng = np.random.default_rng(5)
    u = rng.normal(size=vec.gshape) * vec.interior64
    assert vec.gshape == (3,) + one.gshape
    for j in range(3):
        np.testing.assert_array_equal(vec.apply64(u)[j], one.apply64(u[j]))
    el = vec.to_elements(u)
    assert el.shape == (3, one.nelt, 4, 4, 4)
    np.testing.assert_array_equal(vec.from_elements(el), u)
    w = vec.precond_weight("jacobi")
    np.testing.assert_array_equal(w[2], one.precond_weight("jacobi"))
    # a field with one nonzero component is the scalar solve: the same
    # Krylov space, the same stop
    import jax.numpy as jnp

    f = np.zeros(vec.gshape)
    f[1] = u[1]
    xv, kv = vec.solver(precond="jacobi")(jnp.asarray(f, jnp.float32),
                                          1e-6, niter=0, max_iter=200)
    xs, ks = one.solver(precond="jacobi")(jnp.asarray(u[1], jnp.float32),
                                          1e-6, niter=0, max_iter=200)
    assert int(kv) == int(ks)
    np.testing.assert_allclose(np.asarray(xv[1]), np.asarray(xs),
                               rtol=1e-5, atol=1e-6)
    assert not np.any(np.asarray(xv[0])) and not np.any(np.asarray(xv[2]))
    assert vec.residual64(f, np.asarray(xv, np.float64), w) == \
        pytest.approx(one.residual64(u[1], np.asarray(xs, np.float64),
                                     w[1]), rel=0.05)


# -- the system and whole runs -----------------------------------------------

@pytest.fixture(scope="module")
def tiny_bp6(tmp_path_factory):
    """The benchmark with the vector cell's configuration at n=4 on 2x2x4,
    and the process's cache settings restored afterwards."""
    import os

    import jax

    root = tmp_path_factory.mktemp("tiny_bp6")
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in data["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg.update(n=4, grid=[2, 2, 4])
        c["file"] = f"{c['name']}.json"
        (root / c["file"]).write_text(json.dumps(cfg))
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs,
             os.environ.get("REPRO_CACHE_DIR"))
    yield root
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
    if saved[2] is None:
        os.environ.pop("REPRO_CACHE_DIR", None)
    else:
        os.environ["REPRO_CACHE_DIR"] = saved[2]


def _main(root, capsys, make_system=None, seed=2**33 + 16):
    rc = harness.main(["--workload", CELL, "--seed", str(seed),
                       "--seconds", "0.5", "--trace", "0"],
                      root=root, require_tpu=False, make_system=make_system)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    records = [json.loads(ln) for ln in lines[:-1]]
    return records, json.loads(lines[-1])


def test_a_tiny_vector_run_is_correct_on_the_coupled_route(tiny_bp6,
                                                           capsys):
    records, res = _main(tiny_bp6, capsys)
    resolved = [r for r in records if r["record"] == "resolved"][0]
    assert resolved["route"] == "vector"
    assert resolved["pipeline"] == "fused_v2_rhs3"
    assert resolved["components"] == 3
    assert res["correct"], res
    assert set(res["checks"]) == {"x_rel_diff", "residual", "iters_rel_gap",
                                  "failed_solves"}


def test_a_3x_looser_tol_in_the_program_is_not_correct(tiny_bp6, capsys):
    sp = spec_mod.Spec(tiny_bp6)

    def make(cfg, traffic, pool, tols, checker):
        solver = {**cfg["solver"], **traffic.get("solver", {})}
        return sp.system(solver["entry"]).build(
            cfg, solver, traffic, pool, [3.0 * t for t in tols])

    _, res = _main(tiny_bp6, capsys, make_system=make, seed=2026)
    assert not res["correct"], res["checks"]
    gap = res["checks"]["iters_rel_gap"]
    assert gap["value"] > gap["limit"], gap


def test_the_system_refuses_a_program_that_solves_the_field_otherwise(
        tiny_bp6, monkeypatch):
    """A program that routes the field anywhere but the coupled row (the
    per-RHS ``block_loop`` of a program without it) fails in ``build``,
    before any solve."""
    from repro.core import solvers

    sp = spec_mod.Spec(tiny_bp6)
    cfg = sp.config(sp.cell(CELL)["config"])
    monkeypatch.setattr(solvers, "route_name",
                        lambda *a, **k: "block_loop")
    with pytest.raises(RuntimeError, match="'vector'"):
        sp.system("vector").build(cfg, cfg["solver"], {}, [], [1.0])


@pytest.mark.parametrize("scale", [3.0, 10.0],
                         ids=["stop_3x_looser", "stop_10x_looser"])
def test_a_looser_stop_stops_every_field_of_the_pool_early(tiny_bp6, scale):
    """Over the whole pool, not a timed sample: the program under its
    stated ``tol`` takes the reference's iterations on every field, and
    under a ``tol`` ``scale`` times looser stops at least one iteration
    early on every field and, on the worst field, by the 5% that the
    scalar cell reads, while the true residual stays where a sound
    solve's is.  (At n=4 on 2x2x4 half of the pool's fields stop two of
    34 iterations early under the 3x stop, half only one.)"""
    import check

    sp = spec_mod.Spec(tiny_bp6)
    cfg = sp.config(sp.cell(CELL)["config"])
    traffic = sp.traffic(sp.cell(CELL)["traffic"])
    solver = {**cfg["solver"], **traffic.get("solver", {})}
    ref = sp.reference(cfg["reference"])
    box = ref.Box(cfg["n"], cfg["grid"])
    pool_u, pool_e = harness.make_pool(box, int(traffic["pool"]), 2026)
    rtols, tols = harness.rtols_and_tols(box, cfg, solver, pool_u)
    checker = check.Checker(ref, cfg, solver, pool_u, rtols)
    weight = box.precond_weight(solver["precond"]) * box.interior64

    def iters(s):
        system = sp.system(solver["entry"]).build(
            cfg, solver, traffic, pool_e, [t * s for t in tols])
        out = [system.solve(k) for k in range(len(pool_e))]
        return [it for _, it in out], [x for x, _ in out]

    sound, _ = iters(1.0)
    loose, xs = iters(scale)
    refs = [checker.reference(k)[1] for k in range(len(pool_e))]
    assert sound == refs
    assert all(it < r for it, r in zip(loose, refs)), (loose, refs)
    assert max((r - it) / r for it, r in zip(loose, refs)) >= 0.05
    for k, x in enumerate(xs):
        x64 = box.from_elements(np.asarray(x, np.float64))
        assert box.residual64(pool_u[k], x64, weight) < 1e-4
