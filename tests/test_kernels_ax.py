"""Pallas nekbone_ax kernel vs pure-jnp oracle: shape/dtype/block sweeps."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.sem import derivative_matrix
from repro.kernels import ops, ref


def _data(rng, E, n, dtype):
    u = jnp.asarray(rng.normal(size=(E, n, n, n)), dtype)
    g = jnp.asarray(rng.normal(size=(E, 6, n, n, n)), dtype)
    D = jnp.asarray(derivative_matrix(n), dtype)
    return u, D, g


@pytest.mark.parametrize("n", [2, 3, 4, 6, 8, 10, 12, 16])
def test_ax_kernel_n_sweep(rng, n):
    E = 8
    u, D, g = _data(rng, E, n, jnp.float32)
    w_k = ops.nekbone_ax(u, D, g, block_e=4, interpret=True)
    w_r = ref.nekbone_ax_ref(u, D, g)
    tol = 1e-5 * max(1.0, float(jnp.abs(w_r).max()))
    np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_r), atol=tol)


@pytest.mark.parametrize("E,block_e", [(1, 1), (3, 2), (8, 8), (10, 4),
                                       (17, 8)])
def test_ax_kernel_block_sweep(rng, E, block_e):
    """Arbitrary element counts incl. non-divisible (padding path)."""
    n = 6
    u, D, g = _data(rng, E, n, jnp.float32)
    w_k = ops.nekbone_ax(u, D, g, block_e=block_e, interpret=True)
    w_r = ref.nekbone_ax_ref(u, D, g)
    assert w_k.shape == (E, n, n, n)
    tol = 1e-5 * max(1.0, float(jnp.abs(w_r).max()))
    np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_r), atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ax_kernel_dtypes(rng, dtype):
    n, E = 10, 4
    u, D, g = _data(rng, E, n, dtype)
    w_k = ops.nekbone_ax(u, D, g, block_e=2, interpret=True)
    w_r = ref.nekbone_ax_ref(u.astype(jnp.float32), D.astype(jnp.float32),
                             g.astype(jnp.float32))
    assert w_k.dtype == dtype
    rtol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
    scale = float(jnp.abs(w_r).max())
    np.testing.assert_allclose(np.asarray(w_k, np.float32),
                               np.asarray(w_r), atol=rtol * scale)


def test_ax_kernel_f64_interpret(rng, x64):
    """fp64 path (paper precision) validated through interpret mode."""
    n, E = 10, 4
    u, D, g = _data(rng, E, n, jnp.float64)
    w_k = ops.nekbone_ax(u, D, g, block_e=2, interpret=True)
    w_r = ref.nekbone_ax_ref(u, D, g)
    np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_r),
                               rtol=1e-9, atol=1e-9)


def test_ax_autotuned_block(rng):
    """Default block_e autotune keeps the VMEM estimate under budget."""
    from repro.kernels.ops import _pick_block_e

    for n in (4, 8, 10, 12, 16):
        from repro.kernels import autotune

        be = _pick_block_e(1024, n)
        assert be >= 1
        assert (autotune.vmem_bytes("flat", n, be)
                <= autotune.VMEM_LIMIT_BYTES)
    n, E = 10, 16
    u, D, g = _data(rng, E, n, jnp.float32)
    w_k = ops.nekbone_ax(u, D, g, interpret=True)   # autotuned path
    w_r = ref.nekbone_ax_ref(u, D, g)
    tol = 1e-5 * max(1.0, float(jnp.abs(w_r).max()))
    np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_r), atol=tol)


@pytest.mark.parametrize("storage,acc", [("float64", None),
                                         ("float32", "float64")])
def test_compiled_kernel_refuses_f64(x64, rng, storage, acc):
    """Mosaic has no float64: a compiled (non-interpret) call with f64
    storage or f64 accumulation raises before it reaches the compiler."""
    from repro.kernels import nekbone_ax as K

    E, n = 8, 4
    u, D, g = _data(rng, E, n, jnp.dtype(storage))
    with pytest.raises(TypeError, match="float64 is not supported"):
        K.nekbone_ax_pallas(K.to_lanes(u.reshape(E, n ** 3), n), D, D.T,
                            K.metric_lanes(g.reshape(E, 6, n ** 3), n), n=n,
                            block_e=E, interpret=False, acc_dtype=acc)
