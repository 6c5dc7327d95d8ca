"""Placement of JAX's persistent compilation cache and the autotune cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the ``examples/``
scripts, ``python -m repro.launch.solver_service``) call
:func:`configure_caches` once, before their first compile; importing
``repro`` never does.

* If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no other
  directory is set in code.
* Otherwise the compile cache goes to ``<checkout>/.jax_cache`` — a fixed
  path, since the path is part of what a later run must find again.
* The autotune cache (``REPRO_CACHE_DIR``, see
  :func:`repro.kernels.autotune.cache_path`) defaults to
  ``<checkout>/.repro_cache`` for these entry points, so a run writes
  nothing outside its checkout.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["configure_caches", "checkout_root"]


def checkout_root() -> pathlib.Path:
    """The repository checkout this package is imported from."""
    return pathlib.Path(__file__).resolve().parents[2]


def configure_caches(root: str | os.PathLike | None = None) -> str:
    """Point the compile and autotune caches at fixed places; returns the
    compile-cache directory in use."""
    import jax

    root = pathlib.Path(root) if root is not None else checkout_root()
    os.environ.setdefault("REPRO_CACHE_DIR", str(root / ".repro_cache"))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
