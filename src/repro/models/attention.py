"""GQA attention: prefill (naive / chunked-XLA / Pallas-flash) and decode.

Implementation ladder (DESIGN.md §4):
  * ``naive``   — full (Sq, Skv) score matrix; the oracle, small shapes only.
  * ``chunked`` — nested-scan online softmax in pure XLA: flash-attention
    scheduling without the kernel.  Differentiable (training default) and
    compile-friendly at 32k+ (no S^2 materialization) — used by the dry-run.
  * ``flash``   — the Pallas kernel (kernels/flash_attn.py), inference
    prefill on real TPUs; validated against ``naive`` in interpret mode.

Decode attends a (B, Hkv, S, hd) KV cache updated at ``cache_index``.
Cache sharding (distributed/sharding.py): kv-heads over the TP axis when
divisible, otherwise the cache *sequence* axis is TP-sharded and XLA's SPMD
partitioner turns the softmax reductions into all-reduces — the same
partial-softmax scheme as ring/context-parallel attention.

Supports: GQA grouping, sliding window, gemma2 logit softcap, QKV biases
(qwen1.5/2.5), qk-norm (qwen3), and learned or rotary positions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import RULES, constrain
from repro.models import layers as L

__all__ = ["init_attention", "attention", "decode_attention", "init_kv_cache"]

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------
def init_attention(key, cfg) -> dict:
    ks = jax.random.split(key, 6)
    d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "wq": L.init_linear(ks[0], d, H * hd, bias=cfg.qkv_bias, dtype=dt),
        "wk": L.init_linear(ks[1], d, Hkv * hd, bias=cfg.qkv_bias, dtype=dt),
        "wv": L.init_linear(ks[2], d, Hkv * hd, bias=cfg.qkv_bias, dtype=dt),
        "wo": L.init_linear(ks[3], H * hd, d, dtype=dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = L.init_norm(hd, dtype=dt)
        p["k_norm"] = L.init_norm(hd, dtype=dt)
    return p


def _project_qkv(x, p, cfg, positions):
    B, S, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cdt = jnp.dtype(cfg.compute_dtype)
    q = L.linear(x, p["wq"], cdt).reshape(B, S, H, hd)
    k = L.linear(x, p["wk"], cdt).reshape(B, S, Hkv, hd)
    v = L.linear(x, p["wv"], cdt).reshape(B, S, Hkv, hd)
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"], eps=cfg.norm_eps)
        k = L.rms_norm(k, p["k_norm"], eps=cfg.norm_eps)
    if cfg.pos_emb == "rope":
        q = L.rope(q, positions, theta=cfg.rope_theta)
        k = L.rope(k, positions, theta=cfg.rope_theta)
    q = constrain(q, RULES.act_bthd(H))
    k = constrain(k, RULES.act_bthd(Hkv))
    v = constrain(v, RULES.act_bthd(Hkv))
    return q, k, v


# ---------------------------------------------------------------------------
# Prefill implementations (q, k, v in (B, heads, S, hd))
# ---------------------------------------------------------------------------
def _naive(q, k, v, *, causal, window, cap, scale, q_offset):
    from repro.kernels.ref import attention_ref

    return attention_ref(q, k, v, causal=causal, scale=scale, window=window,
                        softcap=cap, q_offset=q_offset)


def _pick_block(s: int, want: int) -> int:
    b = min(want, s)
    while s % b:
        b //= 2
    return max(b, 1)


def _dp_size(mesh) -> int:
    s = 1
    for a in RULES.dp:
        if a in mesh.axis_names:
            s *= mesh.shape[a]
    return s


def _chunked(q, k, v, *, causal, window, cap, scale, q_offset,
             block_q=512, block_k=1024, q_shift=0, halo=0):
    """Nested-scan online-softmax attention (flash scheduling in XLA).

    ``window`` must be a *static* int (or None): sliding-window layers use
    the banded schedule — each q block visits only the ``ceil(w/bk)+1``
    kv blocks its band can touch, instead of all ``Skv/bk`` (a ~S/w compute
    saving at long context; EXPERIMENTS.md §Perf hymba prefill_32k).

    ``q_shift`` is a (possibly traced) bk-aligned absolute position offset
    of the whole q array (sequence-sharded path: each device owns a
    contiguous q slice).  ``halo`` (static, bk-aligned) says the kv array
    is laid out ``[halo | local]``: kv index i has absolute position
    ``q_shift - halo + i`` (halo-exchange path; the first shard's halo
    rows sit at negative positions and are masked).
    """
    B, Hq, Sq, hd = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Skv, block_k)
    nq, nk = Sq // bq, Skv // bk
    qg = q.reshape(B, Hkv, G, Sq, hd)
    # banded schedule needs a *static* window smaller than the kv length
    # (traced windows fall back to the full scan, which is still correct)
    banded = isinstance(window, int) and causal and window < Skv
    if banded:
        # kv blocks per band: the band spans (window-1 back) + bq q-positions
        nb = min(nk, (int(window) + bq - 2) // bk + 2)
    k_base = (q_shift - halo) if halo else 0      # abs position of kv[0]

    def q_block(iq):
        qb = jax.lax.dynamic_slice_in_dim(qg, iq * bq, bq, axis=3)
        qb = qb.astype(jnp.float32)
        qpos = q_offset + q_shift + iq * bq + jnp.arange(bq)

        def kv_step(carry, ik):
            # banded: ik is a backwards offset from the q block's top block
            if banded:
                if halo:
                    # halo layout: local block arithmetic is fully static
                    top = (halo + q_offset + (iq + 1) * bq - 1) // bk
                else:
                    # q_shift is bk-aligned, so the block split is exact
                    top = q_shift // bk + (q_offset + (iq + 1) * bq - 1) // bk
                kb_idx = top - ik
                valid = (kb_idx >= 0) & (kb_idx < nk)
                kb_idx = jnp.clip(kb_idx, 0, nk - 1)
            else:
                kb_idx = ik
                valid = jnp.asarray(True)
            m, l, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(k, kb_idx * bk, bk, axis=2)
            vb = jax.lax.dynamic_slice_in_dim(v, kb_idx * bk, bk, axis=2)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kb.astype(jnp.float32))
            s = s * scale
            if cap is not None:
                s = L.softcap(s, cap)
            kpos = k_base + kb_idx * bk + jnp.arange(bk)
            mask = jnp.broadcast_to(valid, (bq, bk))
            if halo:
                mask &= kpos[None, :] >= 0        # first-shard halo padding
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= qpos[:, None] - kpos[None, :] < window
            s = jnp.where(mask, s, _NEG_INF)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            pfac = jnp.exp(m - m_new)                   # (..., bq, 1)
            pb = jnp.exp(s - m_new) * mask
            l = l * pfac + pb.sum(-1, keepdims=True)
            acc = acc * pfac + jnp.einsum("bhgqk,bhkd->bhgqd", pb,
                                          vb.astype(jnp.float32))
            return (m_new, l, acc), None

        m0 = jnp.full((B, Hkv, G, bq, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, bq, 1), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, bq, hd), jnp.float32)
        steps = jnp.arange(nb if banded else nk)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), steps)
        l = jnp.where(l == 0.0, 1.0, l)
        return (acc / l).astype(q.dtype)

    blocks = jax.lax.map(q_block, jnp.arange(nq))     # (nq, B, Hkv, G, bq, hd)
    out = jnp.moveaxis(blocks, 0, 3).reshape(B, Hkv, G, Sq, hd)
    return out.reshape(B, Hq, Sq, hd)


def _flash(q, k, v, *, causal, window, cap, scale, q_offset):
    from repro.kernels import ops

    return ops.flash_attention(q, k, v, causal=causal, scale=scale,
                               window=window, softcap=cap, q_offset=q_offset)


_IMPLS = {"naive": _naive, "chunked": _chunked, "flash": _flash}


# ---------------------------------------------------------------------------
# Public blocks
# ---------------------------------------------------------------------------
def _seq_sharded_chunked(q, k, v, *, causal, window, cap, scale):
    """Sequence-parallel chunked attention over the TP axis.

    When the head count does not divide the TP degree (hymba: 25 heads,
    whisper: 20), GSPMD replicates attention compute across 'model' — a
    tp_size-fold waste.  Here each TP device owns a contiguous q slice
    (KV replicated, cheap vs the S^2 compute) so the quadratic work is
    divided by tp_size regardless of head count.
    """
    from repro.distributed.sharding import current_mesh

    mesh = current_mesh()
    tp = RULES.tp
    tp_size = mesh.shape[tp]
    dp = tuple(a for a in RULES.dp if a in mesh.axis_names)
    S = q.shape[2]
    S_loc = S // tp_size
    bq = min(512, S_loc)
    bk = min(1024, S_loc)

    # Windowed layers: KV stays sequence-sharded too; each shard only needs
    # a ``window``-sized halo from its left neighbour (one ppermute) instead
    # of the full KV all-gather — the dominant collective of this path
    # (EXPERIMENTS.md §Perf, hymba prefill_32k iteration 3).
    halo = 0
    if isinstance(window, int) and causal and window < S_loc:
        halo = -(-window // bk) * bk              # round up to block size

    def body(q_l, k_f, v_f):
        shift = jax.lax.axis_index(tp) * S_loc
        if halo:
            perm = [(i, i + 1) for i in range(tp_size - 1)]
            hk = jax.lax.ppermute(k_f[:, :, S_loc - halo:], tp, perm)
            hv = jax.lax.ppermute(v_f[:, :, S_loc - halo:], tp, perm)
            k_ext = jnp.concatenate([hk, k_f], axis=2)
            v_ext = jnp.concatenate([hv, v_f], axis=2)
            return _chunked(q_l, k_ext, v_ext, causal=causal, window=window,
                            cap=cap, scale=scale, q_offset=0, q_shift=shift,
                            halo=halo, block_q=bq, block_k=bk)
        # block_k must divide S_loc so the traced q_shift stays block-aligned
        return _chunked(q_l, k_f, v_f, causal=causal, window=window,
                        cap=cap, scale=scale, q_offset=0, q_shift=shift,
                        block_q=bq, block_k=bk)

    kv_spec = (P(dp, None, tp, None) if halo else P(dp, None, None, None))
    return shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, tp, None), kv_spec, kv_spec),
        out_specs=P(dp, None, tp, None), check_vma=False)(q, k, v)


def _use_seq_shard(cfg, q, k) -> bool:
    from repro.distributed.sharding import current_mesh

    mesh = current_mesh()
    if mesh is None or RULES.tp not in mesh.axis_names:
        return False
    tp_size = mesh.shape[RULES.tp]
    if tp_size == 1 or cfg.n_heads % tp_size == 0:
        return False                       # head sharding already divides work
    S = q.shape[2]
    B = q.shape[0]
    dp = 1
    for a in RULES.dp:
        if a in mesh.axis_names:
            dp *= mesh.shape[a]
    return S % tp_size == 0 and (S // tp_size) >= 8 and B % max(dp, 1) == 0


def attention(x, p, cfg, *, positions, window=None, causal=True,
              impl: str = "chunked", kv_override=None):
    """Full-sequence (training / prefill) attention.

    Returns (out, (k, v)) — k/v in (B, Hkv, S, hd) for cache construction.
    ``kv_override`` supplies external K/V (cross-attention).
    """
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = _project_qkv(x, p, cfg, positions)
    q = q.swapaxes(1, 2)                     # (B, H, S, hd)
    if kv_override is not None:
        k, v = kv_override                   # already (B, Hkv, S, hd)
    else:
        k = k.swapaxes(1, 2)
        v = v.swapaxes(1, 2)
    scale = hd ** -0.5
    seq_sharded = impl == "chunked" and _use_seq_shard(cfg, q, k)
    if seq_sharded:
        out = _seq_sharded_chunked(q, k, v, causal=causal, window=window,
                                   cap=cfg.attn_softcap, scale=scale)
    else:
        out = _IMPLS[impl](q, k, v, causal=causal, window=window,
                           cap=cfg.attn_softcap, scale=scale, q_offset=0)
    B, _, S, _ = out.shape
    out = out.swapaxes(1, 2).reshape(B, S, H * hd)
    if seq_sharded:
        # keep the output projection running on sequence shards; only its
        # (B, S, d) result is gathered by the caller's constraint
        out = constrain(out, P(RULES.dp, RULES.tp, None))
    return L.linear(out, p["wo"], jnp.dtype(cfg.compute_dtype)), (k, v)


def init_kv_cache(cfg, batch: int, max_len: int, *, context_parallel=False):
    """Stacked-over-layers KV cache arrays for one layer (scan stacks them)."""
    Hkv, hd = cfg.n_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.compute_dtype)
    shape = (batch, Hkv, max_len, hd)
    spec = (RULES.kv_cache_cp(Hkv) if context_parallel
            else RULES.kv_cache(Hkv))
    k = constrain(jnp.zeros(shape, dt), spec)
    v = constrain(jnp.zeros(shape, dt), spec)
    return {"k": k, "v": v}


def _decode_attn_seq_sharded(q, cache_k, cache_v, k_new, v_new, cache_index,
                             *, axis: str, window, softcap, scale):
    """Decode against a sequence-sharded KV cache, zero cache movement.

    The write lands only on the shard owning ``cache_index`` (local masked
    update — no collective); attention is partial-softmax combined across
    shards (distributed/context_parallel.py).  This is what makes the
    kv_heads < TP-degree serving configs (qwen2.5, nemotron, arctic, hymba)
    and the 512k context-parallel cells scale (EXPERIMENTS.md §Perf).
    """
    from repro.distributed.context_parallel import cp_decode_attention
    from repro.distributed.sharding import current_mesh

    mesh = current_mesh()
    dp = tuple(a for a in RULES.dp if a in mesh.axis_names and a != axis)
    B = q.shape[0]
    dp_sz = 1
    for a in dp:
        dp_sz *= mesh.shape[a]
    if B % max(dp_sz, 1) != 0:
        dp = ()                            # batch 1 (context-parallel cells)
    S = cache_k.shape[2]
    S_loc = S // mesh.shape[axis]
    # context-parallel cells (axis='data') can still shard heads over TP —
    # dropping that sharding at the shard_map boundary would all-gather the
    # whole cache over 'model' every layer (EXPERIMENTS.md §Perf, gemma2
    # long_500k: 24.7 GB/step -> ~0).
    head_axis = None
    if (RULES.tp in mesh.axis_names and RULES.tp != axis
            and mesh.shape[RULES.tp] > 1):
        tp_sz = mesh.shape[RULES.tp]
        if cache_k.shape[1] % tp_sz == 0 and q.shape[1] % tp_sz == 0:
            head_axis = RULES.tp

    def body(q, kc, vc, kn, vn):
        j = jax.lax.axis_index(axis)
        li = cache_index - j * S_loc
        owner = jnp.logical_and(li >= 0, li < S_loc)
        lic = jnp.clip(li, 0, S_loc - 1)
        kc = jnp.where(owner,
                       jax.lax.dynamic_update_slice_in_dim(kc, kn, lic, 2), kc)
        vc = jnp.where(owner,
                       jax.lax.dynamic_update_slice_in_dim(vc, vn, lic, 2), vc)
        out = cp_decode_attention(q, kc, vc, axis_name=axis,
                                  kv_valid_len=cache_index + 1,
                                  window=window, softcap=softcap, scale=scale)
        return out, kc, vc

    kv_spec = P(dp, head_axis, axis, None)
    rep = P(dp, head_axis, None, None)
    out, kc, vc = shard_map(
        body, mesh=mesh,
        in_specs=(rep, kv_spec, kv_spec, rep, rep),
        out_specs=(rep, kv_spec, kv_spec), check_vma=False,
    )(q, cache_k, cache_v, k_new, v_new)
    # re-assert the cache sharding so the layer-scan carry keeps it sharded
    # (otherwise GSPMD may replicate the carry and all-gather per layer)
    return out, constrain(kc, kv_spec), constrain(vc, kv_spec)


def decode_attention(x, p, cfg, cache: dict, cache_index, *, window=None,
                     context_parallel=False):
    """Single-token decode: update cache at ``cache_index`` and attend.

    x: (B, 1, d); cache k/v: (B, Hkv, S, hd).  Returns (out, new_cache).

    Cache layouts (matching configs/specs.cache_specs):
      * kv-heads divisible by TP -> heads sharded, GSPMD path below;
      * otherwise the cache *sequence* is sharded (over 'model', or over
        'data' for the context-parallel long_500k cells) and the explicit
        shard_map path runs: local masked write + partial-softmax combine.
    """
    from repro.distributed.sharding import current_mesh

    B = x.shape[0]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // Hkv
    positions = jnp.full((B, 1), cache_index, jnp.int32)
    q, k_new, v_new = _project_qkv(x, p, cfg, positions)

    mesh = current_mesh()
    seq_axis = None
    if mesh is not None:
        if context_parallel and RULES.seq in mesh.axis_names:
            seq_axis = RULES.seq
        elif (RULES.tp in mesh.axis_names and mesh.shape[RULES.tp] > 1
              and Hkv % mesh.shape[RULES.tp] != 0
              and cache["k"].shape[2] % mesh.shape[RULES.tp] == 0
              and B % _dp_size(mesh) == 0):
            seq_axis = RULES.tp

    if seq_axis is not None:
        out, k, v = _decode_attn_seq_sharded(
            q.swapaxes(1, 2), cache["k"], cache["v"],
            k_new.swapaxes(1, 2), v_new.swapaxes(1, 2), cache_index,
            axis=seq_axis, window=window, softcap=cfg.attn_softcap,
            scale=hd ** -0.5)
        out = out.swapaxes(1, 2).reshape(B, 1, H * hd)
        out = L.linear(out.astype(x.dtype), p["wo"],
                       jnp.dtype(cfg.compute_dtype))
        return out, {"k": k, "v": v}

    spec = (RULES.kv_cache_cp(Hkv) if context_parallel
            else RULES.kv_cache(Hkv))
    k = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k_new.swapaxes(1, 2), cache_index, axis=2)
    v = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v_new.swapaxes(1, 2), cache_index, axis=2)
    k = constrain(k, spec)
    v = constrain(v, spec)

    qg = q.reshape(B, 1, Hkv, G, hd).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bhkd->bhgqk", qg, k.astype(jnp.float32))
    s = s * (hd ** -0.5)
    s = L.softcap(s, cfg.attn_softcap)
    kpos = jnp.arange(k.shape[2])
    mask = kpos <= cache_index
    if window is not None:
        mask &= cache_index - kpos < window
    s = jnp.where(mask[None, None, None, None, :], s, _NEG_INF)
    pmax = s.max(-1, keepdims=True)
    pe = jnp.exp(s - pmax)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", pe, v.astype(jnp.float32))
    out = out / pe.sum(-1, keepdims=True)
    out = out.reshape(B, Hkv * G, 1, hd).swapaxes(1, 2).reshape(B, 1, H * hd)
    out = L.linear(out.astype(x.dtype), p["wo"], jnp.dtype(cfg.compute_dtype))
    return out, {"k": k, "v": v}
