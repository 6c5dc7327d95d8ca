"""Rehearsal of ``chip_smoke.py`` at a tiny size on CPU (interpret mode).

Each phase function runs with n=4 on a 2x2x4 element grid, so paths,
arguments and checks are exercised without a chip; ``main()`` itself must
refuse to run without a TPU.  The four-device phase is rehearsed on four
virtual CPU devices through ``tests/distributed_checks.py``.
"""
import importlib.util
import json
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
GRID = (2, 2, 4)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_operator_phase(smoke):
    out = smoke.operator_phase(grid=GRID, n=4)
    assert out["shape"] == [16, 4, 4, 4]
    assert out["err_over_roundoff_bound"] <= 1.0


@pytest.mark.parametrize("ax_impl", ["pallas", "pallas_fused_cg_v2"])
def test_cg_phase(smoke, ax_impl):
    out = smoke.cg_phase(ax_impl, grid=GRID, n=4, niter=12)
    assert out["ax_impl"] == ax_impl
    assert len(out["hist_rel_diff_first10"]) == 10
    assert out["x_max_abs_diff"] <= out["x_bound"]


def test_service_phase(smoke, tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    out = smoke.service_phase(grid=GRID, n=4, requests=5, max_b=2,
                              niter=10)
    assert out["requests"] == 5
    assert out["batch_sizes"] == [2, 2, 1]
    assert out["history_diff_over_bound"] <= 1.0
    assert out["x_diff_over_bound"] <= 1.0


def test_ulp_noise_envelope_is_a_running_max(smoke):
    """The measured envelope never shrinks along the history, and a solve
    that ignores its rhs perturbation has zero noise."""
    import types

    import jax.numpy as jnp
    import numpy as np

    f = jnp.ones((4, 3), jnp.float32)

    def solve(b):
        h = jnp.asarray([1.0, 0.5, 0.25]) * (1 + (b[0, 0] - 1) * 1e3)
        return types.SimpleNamespace(history=h, x=jnp.zeros(3))

    ref, noise, x_noise = smoke._ulp_noise(solve, f, patterns=3)
    assert np.all(np.diff(noise) >= 0)
    assert x_noise == 0.0
    assert np.asarray(ref.history)[0] == 1.0


def test_main_refuses_cpu(smoke, capsys):
    rc = smoke.main([])
    captured = capsys.readouterr()
    assert rc != 0
    assert "cpu" in captured.err
    assert '"ok": true' not in captured.out
    for line in captured.out.splitlines():
        assert json.loads(line).get("ok") is not True
