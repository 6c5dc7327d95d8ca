"""The problem's least time on a chip: the roofline every share is against.

The work is the problem's, not an implementation's, so the share reads the
same whatever runs it, and no later change can push it past 100%:

* operations: ``DOFs x iterations x (12 n + 34)``, the per-iteration count
  of Nekbone's CG with the matrix-free operator (Karp et al. 2020,
  arXiv:2005.13425, Eq. 1), ``DOFs = E n^3`` per right-hand side;
* bytes: the compulsory ``2 x DOFs x 4`` per solve of one right-hand side,
  read ``f`` and write ``x`` in float32.

``T_roof = max(ops / peak_flops, bytes / peak_bandwidth)``.  The peaks come
from ``peaks.json``, keyed by the device kind JAX reports; a device that is
not in the table is an error.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def flops_per_dof_iter(n: int) -> int:
    """Eq. 1 of the paper: operations per DOF per CG iteration."""
    return 12 * n + 34


def peaks(device_kind: str, path=PEAKS) -> dict:
    table = json.loads(pathlib.Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def t_roof_s(*, dofs: int, n: int, iterations: float, solves: float,
             peak: dict) -> tuple[float, str]:
    """Least seconds for ``solves`` right-hand sides taking ``iterations``
    CG iterations in all, and which bound sets it."""
    t_flops = dofs * iterations * flops_per_dof_iter(n) / peak["flops_per_s"]
    t_bytes = 2 * dofs * 4 * solves / peak["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("compute" if t_flops >= t_bytes
                                   else "memory")
