"""Compute/communication overlap: collective (all-gather) matmul.

The standard TP inefficiency is ``all_gather(x) @ w``: the interconnect is
idle while the MXU works and vice versa.  The collective matmul pipelines
them — each step matmuls the chunk it already has while ``ppermute``-ing
the next chunk around the ring, hiding (steps-1)/steps of the transfer
latency behind compute.  (XLA's ``--xla_tpu_enable_async_collective_...``
latency-hiding scheduler can do this for some patterns; this is the explicit
shard_map form, usable as a drop-in where profiling shows serialization.)

``collective_matmul_allgather(x_shard, w, axis)``:
  x is sharded over ``axis`` on its leading (row) dim; w is replicated or
  row-sharded to match x columns.  Computes ``all_gather(x) @ w`` without
  materializing the gather.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


__all__ = ["collective_matmul_allgather"]


def collective_matmul_allgather(x: jnp.ndarray, w: jnp.ndarray,
                                axis_name: str) -> jnp.ndarray:
    """Per-shard body (call inside shard_map).

    x: (m_local, k) — this shard's rows of the global (m, k) operand.
    w: (k, n) replicated.
    Returns: (m_local * axis_size, n) == all_gather(x, tiled) @ w.

    Ring schedule: at step s we hold the block that originated at shard
    (i - s) mod P; matmul it into its output slot while forwarding it.
    """
    P = jax.lax.axis_size(axis_name)
    i = jax.lax.axis_index(axis_name)
    m_loc, _ = x.shape
    n = w.shape[1]
    out = jnp.zeros((m_loc * P, n), x.dtype)
    # mark the carry as device-varying (VMA)
    out = jax.lax.pcast(out, (axis_name,), to="varying")
    perm = [(p, (p + 1) % P) for p in range(P)]

    def body(s, carry):
        blk, out = carry
        src = (i - s) % P                      # owner of the block we hold
        y = jnp.dot(blk, w, preferred_element_type=jnp.float32).astype(x.dtype)
        out = jax.lax.dynamic_update_slice_in_dim(out, y, src * m_loc, axis=0)
        # forward the block around the ring (skipped result on last step)
        blk = jax.lax.ppermute(blk, axis_name, perm)
        return blk, out

    _, out = jax.lax.fori_loop(0, P, body, (x, out))
    return out
