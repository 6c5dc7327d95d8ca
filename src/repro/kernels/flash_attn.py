"""Block online-softmax (flash) attention forward kernel for TPU.

The prefill hot-spot for the dense-transformer architectures.  The paper's
locality insight maps directly: the small per-row state (running max ``m``,
normalizer ``l``, output accumulator) stays resident in VMEM while KV blocks
stream past — the KV-sequence axis plays the role of the paper's ``k`` layer
axis.

Supports GQA (q heads grouped over kv heads), causal masking, sliding
window, logit soft-capping (gemma2), and a ``q_offset`` for chunked prefill.

Forward-only: the training path uses the XLA chunked implementation in
``models/attention.py`` (differentiable, memory-bound-optimal); this kernel
serves inference prefill where no VJP is needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from jax.experimental.pallas.tpu import CompilerParams as _CompilerParams

__all__ = ["flash_attention"]

_NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window: int | None,
                 softcap: float | None, q_offset: int, block_q: int,
                 block_k: int, kv_len: int, nk: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    qpos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = kpos < kv_len                         # KV padding
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[:, 0:1]                       # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)               # (bq, 1)
    l_new = l_ref[:, 0:1] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[:, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)          # fully-masked (padded) rows
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "window", "softcap",
                              "q_offset", "block_q", "block_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, scale: float | None = None,
                    window: int | None = None, softcap: float | None = None,
                    q_offset: int = 0, block_q: int = 512, block_k: int = 512,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, Sq, d); k, v: (B, Hkv, Skv, d); returns (B, Hq, Sq, d)."""
    B, Hq, Sq, d = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    scale = float(d ** -0.5) if scale is None else float(scale)

    bq = min(block_q, max(Sq, 8))
    bk = min(block_k, max(Skv, 8))
    pad_q = (-Sq) % bq
    pad_k = (-Skv) % bk
    qf = q.reshape(B * Hq, Sq, d)
    kf = k.reshape(B * Hkv, Skv, d)
    vf = v.reshape(B * Hkv, Skv, d)
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
    nq = (Sq + pad_q) // bq
    nk = (Skv + pad_k) // bk

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, q_offset=q_offset, block_q=bq, block_k=bk,
        kv_len=Skv, nk=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda bh, iq, ik, g=group: (bh // g, ik, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda bh, iq, ik, g=group: (bh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq + pad_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max m
            pltpu.VMEM((bq, 128), jnp.float32),   # normalizer l
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=f"flash_attn_bq{bq}_bk{bk}",
    )(qf, kf, vf)
    out = out[:, :Sq, :]
    return out.reshape(B, Hq, Sq, d)
