"""Whole runs of the benchmark on the CPU at a tiny size.

``run.main`` is driven with ``require_tpu=False`` (the one step these runs
skip is the look for a chip) on copies of the configurations shrunk to
n=4 on a 2x2x4 grid; everything else is the run as the chip makes it.  A
fault planted in the timed path, where the program produces its answer,
has to turn ``correct`` false.
"""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import run as harness  # noqa: E402


def test_without_a_tpu_the_run_exits_nonzero_and_prints_no_result():
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", cell["name"],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "metrics" not in proc.stdout
    assert "TPU" in proc.stderr


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """The benchmark with every configuration at n=4 on a 2x2x4 grid, and
    the process's cache settings restored afterwards."""
    import jax

    root = tmp_path_factory.mktemp("tiny")
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in data["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg.update(n=4, grid=[2, 2, 4])
        if "niter" in cfg["stop"]:
            cfg["stop"] = {"niter": 12}
        c["file"] = f"{c['name']}.json"
        (root / c["file"]).write_text(json.dumps(cfg))
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs,
             os.environ.get("REPRO_CACHE_DIR"))
    yield root, data
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
    if saved[2] is None:
        os.environ.pop("REPRO_CACHE_DIR", None)
    else:
        os.environ["REPRO_CACHE_DIR"] = saved[2]


def _result(root, cell, capsys, seed=2**33 + 5, trace=0):
    rc = harness.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", "0.5", "--trace", str(trace)],
                      root=root, require_tpu=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def _plant(monkeypatch, fault):
    """Break the answer where the program produces it: every solve the
    registry routes returns ``fault(x)`` in place of its ``x``."""
    from repro.core import solvers

    clean = solvers.solve_case

    def broken(*args, **kwargs):
        res = clean(*args, **kwargs)
        return dataclasses.replace(res, x=fault(res.x))

    monkeypatch.setattr(solvers, "solve_case", broken)


def _unchanged(x):
    """A solve that leaves its state as it started: x = 0."""
    import jax.numpy as jnp

    return jnp.zeros_like(x)


def _altered(x):
    """One node of one answer off by 1% of the answer's largest value."""
    import jax.numpy as jnp

    idx = (0,) * (x.ndim - 3) + (1, 1, 1)
    return x.at[idx].add(0.01 * jnp.max(jnp.abs(x)))


def _cells(data):
    return [w["name"] for w in data["workloads"]]


def test_sound_runs_are_correct(tiny_root, capsys):
    root, data = tiny_root
    for cell in _cells(data):
        res = _result(root, cell, capsys)
        assert res["correct"], (cell, res)
        assert res["failed"] == 0 and res["attempted"] > 0
        assert list(res)[-1] == "checks"
        assert set(res["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("fault", [_unchanged, _altered],
                         ids=["state_unchanged", "answer_altered"])
def test_a_fault_in_the_timed_path_is_not_correct(tiny_root, capsys,
                                                  monkeypatch, fault):
    root, data = tiny_root
    _plant(monkeypatch, fault)
    for cell in _cells(data):
        res = _result(root, cell, capsys)
        assert not res["correct"], (cell, res)


def _rtol_cells(root, data):
    """The cells whose configuration stops on a relative tolerance."""
    out = []
    for w in data["workloads"]:
        c = [c for c in data["configs"] if c["name"] == w["config"]][0]
        if "rtol" in json.loads((root / c["file"]).read_text())["stop"]:
            out.append(w["name"])
    return out


def _gap_reading(root, data, cell, scale):
    """``control.reading`` of the program with its absolute ``tol``
    multiplied by ``scale``, on the tiny configuration."""
    import check
    import control
    import spec as spec_mod

    sp = spec_mod.Spec(root)
    cfg = sp.config(sp.cell(cell)["config"])
    traffic = sp.traffic(sp.cell(cell)["traffic"])
    solver = {**cfg["solver"], **traffic.get("solver", {})}
    ref = sp.reference(cfg["reference"])
    box = ref.Box(cfg["n"], cfg["grid"])
    pool_u, pool_e = harness.make_pool(box, int(traffic["pool"]), 2026)
    rtols, tols = harness.rtols_and_tols(box, cfg, solver, pool_u)
    checker = check.Checker(ref, cfg, solver, pool_u, rtols)
    system = sp.system(solver["entry"]).build(
        cfg, solver, traffic, pool_e, [t * scale for t in tols])
    system.warm()
    return control.reading(sp.loop(traffic["loop"]), system, pool_e,
                           checker, traffic, 0.5, 2026, "test")


@pytest.mark.parametrize("scale", [3.0, 10.0],
                         ids=["stop_3x_looser", "stop_10x_looser"])
def test_a_looser_stop_reads_as_an_iteration_gap(tiny_root, scale):
    """A stop looser than the configuration states takes fewer iterations
    than the reference under the stated stop: ``iters_rel_gap``, which
    ``control.py`` reads, grows with the loosening from a sound run's 0,
    while the true residual stays where a sound run's is."""
    root, data = tiny_root
    cells = _rtol_cells(root, data)
    assert cells
    for cell in cells:
        sound = _gap_reading(root, data, cell, 1.0)
        loose = _gap_reading(root, data, cell, scale)
        assert sound["iters_rel_gap"] == 0.0, sound
        assert loose["iters_rel_gap"] >= 0.05, loose
        assert all(it < ref for it, ref in loose["iters_vs_ref"])
        assert loose["residual"] < 1e-4


@pytest.mark.parametrize("scale", [3.0, 10.0],
                         ids=["stop_3x_looser", "stop_10x_looser"])
def test_a_looser_stop_than_the_configuration_states_is_not_correct(
        tiny_root, capsys, scale):
    """The program itself, with its absolute ``tol`` multiplied by
    ``scale``, put where the run drives the program: answers to a weaker
    guarantee than stated, which the iteration gap refuses."""
    import spec as spec_mod

    root, data = tiny_root
    sp = spec_mod.Spec(root)

    def make(cfg, traffic, pool, tols, checker):
        solver = {**cfg["solver"], **traffic.get("solver", {})}
        return sp.system(solver["entry"]).build(
            cfg, solver, traffic, pool, [t * scale for t in tols])

    cells = _rtol_cells(root, data)
    assert cells
    for cell in cells:
        rc = harness.main(["--workload", cell, "--seed", "2026",
                           "--seconds", "0.5", "--trace", "0"],
                          root=root, require_tpu=False, make_system=make)
        res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rc == 0
        assert not res["correct"], (cell, res["checks"])
        gap = res["checks"]["iters_rel_gap"]
        assert gap["value"] > gap["limit"], gap


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """The configurations at the degree they state on a 4x4x4 (n=10) or
    4x4x8 (n=8) grid: large enough that the control's error shows as it
    does at the cells' own size, small enough for the CPU."""
    root = tmp_path_factory.mktemp("small")
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in data["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg["grid"] = [4, 4, 4] if cfg["n"] == 10 else [4, 4, 8]
        c["file"] = f"{c['name']}.json"
        (root / c["file"]).write_text(json.dumps(cfg))
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    return root, data


def test_the_control_in_the_programs_place_is_not_correct(small_root, capsys):
    """The reference one precision below the configuration's (bfloat16
    three-pass products) put where the program runs, with the cells' own
    limits: every cell's checks must refuse it.  Its readings on the chip,
    at the cells' own sizes, are in PERF.md."""
    import control

    root, data = small_root

    def make(cfg, traffic, pool, tols, checker):
        return control.ControlSystem(checker)

    for cell in _cells(data):
        rc = harness.main(["--workload", cell, "--seed", "77",
                           "--seconds", "0.5", "--trace", "0"],
                          root=root, require_tpu=False, make_system=make)
        res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rc == 0
        assert not res["correct"], (cell, res["checks"])
