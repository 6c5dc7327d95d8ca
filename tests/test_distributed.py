"""Multi-device behaviour (shard_map, collectives) via subprocesses.

The 8-device host-platform flag must be set before jax initializes, so
these checks run in ``distributed_checks.py`` as child processes — keeping
the main pytest process at 1 device per the dry-run contract.  One
subprocess per check name: a failure names its check instead of taking the
whole suite down, and the slow checks parallelize under ``pytest -n``.
"""
import os
import pathlib
import subprocess
import sys

import pytest

_SCRIPT = pathlib.Path(__file__).parent / "distributed_checks.py"
_SRC = pathlib.Path(__file__).parents[1] / "src"

# Mirrors distributed_checks.CHECKS (cannot import it here: the module sets
# the device-count flag at import).  test_check_names_consistent pins the
# two lists together via the subprocess --list protocol.
CHECK_NAMES = [
    "device_count",
    "compressed_psum",
    "collective_matmul",
    "collective_matmul_colsharded",
    "collective_matmul_sweep",
    "cp_decode_attention",
    "sharded_gather_scatter",
    "sharded_gs_hierarchical",
    "sharded_nekbone_cg",
    "fused_cg_sharded",
    "fused_cg_sharded_precision",
    "sstep_sharded_s1",
    "sstep_sharded_s2",
    "sstep_sharded_s4",
    "sstep_collective_counts",
    "chip_smoke_sharded",
    "pcg_jacobi_sharded",
    "pcg_cheb_sharded",
    "pcg_sharded_precision",
    "pcg_sharded_tol_prefix",
    "seq_sharded_attention",
    "seq_sharded_decode",
    "moe_shardmap_equals_local",
    "pipeline_parallel",
    "elastic_checkpoint_reshard",
]


def _run_checks(args, timeout=580):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(_SRC)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, str(_SCRIPT), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
    )


def test_check_names_consistent():
    proc = _run_checks(["--list"])
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == CHECK_NAMES


@pytest.mark.parametrize("name", CHECK_NAMES)
def test_distributed_check(name):
    proc = _run_checks([name])
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr[-2000:])
    assert proc.returncode == 0, f"check {name} failed:\n{proc.stdout}"
    assert "ALL-DISTRIBUTED-OK" in proc.stdout
