"""Quickstart: the paper's operator in five minutes.

Builds a degree-9 spectral-element Poisson problem (the paper's setting),
applies the fused tensor-product operator through all three implementations
(Listing-1 reference, XLA-fused, Pallas TPU kernel in interpret mode),
verifies they agree, and solves the system with CG.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

import jax.numpy as jnp

from repro.core.nekbone import NekboneCase


def main():
    from repro.compile_cache import configure_caches

    configure_caches()
    # Paper setup: polynomial degree 9 -> n = 10 GLL points, 64 elements.
    case = NekboneCase(n=10, grid=(4, 4, 4), dtype=jnp.float32)
    print(f"case: {case.mesh.nelt} elements, {case.mesh.ndof} local DOFs, "
          f"intensity I(n)={case.cost.intensity:.3f} flop/byte")

    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(case.mesh.nelt, 10, 10, 10)),
                    jnp.float32)

    outs = {}
    for impl in ("listing1", "fused", "pallas"):
        case.ax_impl = impl
        outs[impl] = case.ax_local(u)
    for name, w in outs.items():
        err = float(jnp.abs(w - outs["fused"]).max())
        print(f"ax[{name:9s}]  max|diff vs fused| = {err:.2e}")

    case.ax_impl = "fused"
    res, u_exact = case.solve_manufactured(tol=1e-5, max_iter=300)
    print(f"CG: {int(res.iters)} iterations, residual {float(res.rnorm):.2e}, "
          f"solution max-error {float(case.solution_error(res.x, u_exact)):.2e}")


if __name__ == "__main__":
    main()
