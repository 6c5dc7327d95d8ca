"""GPipe-style pipeline parallelism over a mesh axis (shard_map form).

Completes the parallelism matrix (DP/TP/EP/SP elsewhere): stage ``s`` owns a
contiguous slice of layers; microbatches stream through with boundary
activations moving stage-to-stage by ``ppermute``.  The classic schedule —
``n_micro + n_stages - 1`` ticks, bubble fraction ``(S-1)/(M+S-1)`` — is
expressed as a ``lax.fori_loop`` so the whole pipeline jits as one program.

Usage (inside shard_map over the pipeline axis, e.g. 'pod'):

    out = pipeline_apply(stage_params_local, microbatches, stage_fn,
                         axis_name='pod', n_stages=2)

``stage_fn(params_local, x) -> x`` runs this stage's layers.  Input
microbatches: (M, mb, ...) fed to stage 0; output collected from the last
stage (every stage returns the full (M, mb, ...) buffer; non-final stages
return garbage rows that the caller discards by reading the last stage's
shard — see tests/distributed_checks.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


__all__ = ["pipeline_apply"]


def pipeline_apply(stage_params, microbatches, stage_fn, *, axis_name: str):
    """Run the pipeline; call inside shard_map over ``axis_name``.

    stage_params: this stage's layer-slice params (pytree).
    microbatches: (M, mb, ...) — the global input, replicated per stage
                  (only stage 0 reads it).
    Returns (M, mb, ...): valid on the LAST stage (use a masked psum or
    read that shard to collect).
    """
    S = jax.lax.axis_size(axis_name)
    sid = jax.lax.axis_index(axis_name)
    M = microbatches.shape[0]
    mb_shape = microbatches.shape[1:]
    ticks = M + S - 1
    fwd_perm = [(i, i + 1) for i in range(S - 1)]

    out = jnp.zeros_like(microbatches)
    cur = jnp.zeros(mb_shape, microbatches.dtype)

    def tick(t, carry):
        cur, out = carry
        # stage 0 ingests microbatch t (when in range)
        mb_idx = jnp.clip(t, 0, M - 1)
        feed = jax.lax.dynamic_index_in_dim(microbatches, mb_idx, 0,
                                            keepdims=False)
        x_in = jnp.where(sid == 0, feed, cur)
        y = stage_fn(stage_params, x_in)
        # my microbatch index this tick; valid while 0 <= m < M
        m = t - sid
        valid = jnp.logical_and(m >= 0, m < M)
        out = jax.lax.dynamic_update_index_in_dim(
            out, jnp.where(valid, y, jax.lax.dynamic_index_in_dim(
                out, jnp.clip(m, 0, M - 1), 0, keepdims=False)),
            jnp.clip(m, 0, M - 1), 0)
        # boundary activation moves to the next stage
        cur = jax.lax.ppermute(y, axis_name, fwd_perm)
        return cur, out

    _, out = jax.lax.fori_loop(0, ticks, tick, (cur, out))
    return out
