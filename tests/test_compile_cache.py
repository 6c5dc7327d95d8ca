"""Where the entry points put JAX's persistent compilation cache.

Each case runs a child process (the cache directory is process-wide JAX
state): it calls ``configure_caches`` on a scratch checkout root, compiles
one small function, and the test looks where the cache entry landed.
"""
import os
import pathlib
import subprocess
import sys

import pytest

_SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

_CHILD = """
import sys
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from repro.compile_cache import configure_caches
print(configure_caches(sys.argv[1]))
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


def _run(root: pathlib.Path, env_dir: pathlib.Path | None) -> str:
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "REPRO_CACHE_DIR")}
    env.update(PYTHONPATH=str(_SRC), JAX_PLATFORMS="cpu")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c", _CHILD, str(root)],
                          capture_output=True, text=True, timeout=120,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


@pytest.mark.parametrize("from_env", [True, False],
                         ids=["env_dir", "checkout_default"])
def test_compile_cache_location(tmp_path, from_env):
    root = tmp_path / "checkout"
    root.mkdir()
    env_dir = tmp_path / "env_cache" if from_env else None
    used = _run(root, env_dir)
    want = env_dir if from_env else root / ".jax_cache"
    assert pathlib.Path(used) == want
    assert any(want.iterdir()), "the compile wrote no cache entry"
    if from_env:
        assert not (root / ".jax_cache").exists()
