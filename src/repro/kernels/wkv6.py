"""RWKV6 (Finch) WKV recurrence as a Pallas TPU kernel.

The most direct transfer of the paper's optimization (DESIGN.md §4): the
per-head state matrix ``S in R^{d x d}`` is the small operand held in fast
memory (VMEM scratch, the shared-memory analog) while the long axis — time,
playing the role of the paper's element ``k``-layers — streams past in
blocks.  Two bodies:

* ``variant='sequential'`` — faithful per-token recurrence (matches the
  reference CUDA WKV kernels; unconditionally stable for any decay).
* ``variant='chunked'`` — the optimized within-chunk *parallel* form: the
  recurrence over a time chunk of length ``c`` is algebraically rewritten as
  three MXU matmuls plus a masked (c, c) correlation, exactly the paper's
  "restructure many tiny contractions into a few large ones" move:

      r~_t = r_t * P_{t-1}      (P = inclusive cumprod of decay, P_{-1}=1)
      k~_s = k_s / P_s
      O    = r~ @ S0 + (strict_tril(r~ k~^T) + diag(r.(u*k))) @ V
      S'   = diag(P_c) (S0 + k~^T V)

  Stability: 1/P_s grows as decays accumulate, so the chunk size bounds the
  dynamic range (with w >= w_min the factor is w_min^{-c}).  The default
  c = 16 keeps f32 exact to ~1e-5 for the decay ranges RWKV6 produces
  (w = exp(-exp(x)) clipped to w >= 0.05 by construction in models/rwkv6.py).

Shapes: r, k, v, w: (B, H, T, d); u (bonus): (H, d).  Heads map to the
parallel grid axis; time blocks map to an 'arbitrary' axis with the state
carried in scratch between steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from jax.experimental.pallas.tpu import CompilerParams as _CompilerParams

__all__ = ["wkv6"]


def _seq_body(r, k, v, w, u, S0):
    """Per-token recurrence over a (c, d) chunk. All f32. Returns (O, S)."""
    c, d = r.shape

    def step(t, carry):
        S, O = carry
        rt = jax.lax.dynamic_slice(r, (t, 0), (1, d))      # (1, d)
        kt = jax.lax.dynamic_slice(k, (t, 0), (1, d))
        vt = jax.lax.dynamic_slice(v, (t, 0), (1, d))
        wt = jax.lax.dynamic_slice(w, (t, 0), (1, d))
        out = jax.lax.dot(rt, S, preferred_element_type=jnp.float32)
        bonus = jnp.sum(rt * u * kt, axis=-1, keepdims=True)  # (1, 1)
        out = out + bonus * vt
        S = S * wt.T + kt.T @ vt
        O = jax.lax.dynamic_update_slice(O, out, (t, 0))
        return S, O

    O = jnp.zeros((c, d), jnp.float32)
    S, O = jax.lax.fori_loop(0, c, step, (S0, O))
    return O, S


def _chunk_body(r, k, v, w, u, S0):
    """Parallel within-chunk form (three matmuls). All f32. Returns (O, S)."""
    c, d = r.shape
    logw = jnp.log(w)
    cum = jnp.cumsum(logw, axis=0)                  # log P_t (inclusive)
    p_incl = jnp.exp(cum)
    p_excl = jnp.exp(cum - logw)                    # P_{t-1}
    r_t = r * p_excl
    k_t = k * jnp.exp(-cum)
    A = jax.lax.dot_general(r_t, k_t, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (c, c)
    ti = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    A = jnp.where(si < ti, A, 0.0)                  # strictly causal
    bonus = jnp.sum(r * u * k, axis=-1)             # (c,)
    A = A + jnp.diag(bonus)
    O = jax.lax.dot(r_t, S0, preferred_element_type=jnp.float32)
    O = O + jax.lax.dot(A, v, preferred_element_type=jnp.float32)
    S = p_incl[-1][:, None] * (
        S0 + jax.lax.dot(k_t.T, v, preferred_element_type=jnp.float32))
    return O, S


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sout_ref,
                 s_scr, *, nt: int, variant: str):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    f32 = jnp.float32
    r = r_ref[0].astype(f32)
    k = k_ref[0].astype(f32)
    v = v_ref[0].astype(f32)
    w = w_ref[0].astype(f32)
    u = u_ref[...].astype(f32)                       # (1, d)

    body = _seq_body if variant == "sequential" else _chunk_body
    O, S = body(r, k, v, w, u, s_scr[...])
    o_ref[0] = O.astype(o_ref.dtype)
    s_scr[...] = S

    @pl.when(it == nt - 1)
    def _finish():
        sout_ref[0] = S.astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("return_state", "block_t",
                                             "variant", "interpret"))
def wkv6(r, k, v, w, u, *, initial_state=None, return_state: bool = False,
         block_t: int = 16, variant: str = "chunked", interpret: bool = False):
    """RWKV6 recurrence. r,k,v,w: (B,H,T,d); u: (H,d) -> (B,H,T,d) [, state].

    T is zero-padded to a multiple of ``block_t`` (padded steps use decay 1
    and contribute nothing: k rows are zero).
    """
    B, H, T, d = r.shape
    bt = block_t
    pad = (-T) % bt
    Tp = T + pad

    def flat(x, pad_value=0.0):
        x = x.reshape(B * H, T, d)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)),
                        constant_values=pad_value)
        return x

    rf, kf, vf = flat(r), flat(k), flat(v)
    wf = flat(w, pad_value=1.0)                     # decay 1 on padding
    s0 = (jnp.zeros((B * H, d, d), jnp.float32) if initial_state is None
          else initial_state.reshape(B * H, d, d).astype(jnp.float32))
    uf = u.astype(jnp.float32)                      # (H, d)
    nt = Tp // bt

    kernel = functools.partial(_wkv6_kernel, nt=nt, variant=variant)
    o, s_out = pl.pallas_call(
        kernel,
        grid=(B * H, nt),
        in_specs=[
            pl.BlockSpec((1, bt, d), lambda bh, it: (bh, it, 0)),
            pl.BlockSpec((1, bt, d), lambda bh, it: (bh, it, 0)),
            pl.BlockSpec((1, bt, d), lambda bh, it: (bh, it, 0)),
            pl.BlockSpec((1, bt, d), lambda bh, it: (bh, it, 0)),
            pl.BlockSpec((1, d), lambda bh, it, h=H: (bh % h, 0)),
            pl.BlockSpec((1, d, d), lambda bh, it: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, d), lambda bh, it: (bh, it, 0)),
            pl.BlockSpec((1, d, d), lambda bh, it: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tp, d), r.dtype),
            jax.ShapeDtypeStruct((B * H, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=f"wkv6_{variant}_bt{bt}",
    )(rf, kf, vf, wf, uf, s0)

    o = o[:, :T, :].reshape(B, H, T, d)
    if return_state:
        return o, s_out.reshape(B, H, d, d)
    return o
