"""Mixture-of-Experts FFN with expert parallelism over the TP mesh axis.

Design (DESIGN.md §3): tokens are data-parallel (replicated across the
``model`` axis), experts are sharded over ``model``.  Dispatch therefore
needs *no token communication at all* — each device routes its local tokens
to its local expert slice and the partial outputs are combined with one
``psum`` over ``model`` (the same collective a dense TP MLP pays).  This is
implemented with ``shard_map`` so the sort-based dispatch stays shard-local
(a global top-k/sort under GSPMD would all-gather the token stream).

Dispatch is the static-shape, capacity-based sort scheme:
  top-k -> mask to local experts -> stable sort by expert id -> position
  within expert group -> scatter into an (E_local, C, d) buffer -> batched
  expert GEMMs -> gather back with gate weights.
Tokens beyond an expert's capacity ``C = ceil(T_local * top_k / E * cf)``
are dropped (standard GShard/Switch behaviour; ``capacity_factor`` tunes it).

The expert GEMMs fold (expert, capacity) into the M dimension of one
``(E_loc, C, d) x (E_loc, d, f)`` batched matmul — the paper's "many small
problems -> one skinny GEMM" layout move (DESIGN.md §4).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import RULES, current_mesh
from repro.models import layers as L

__all__ = ["init_moe", "moe_ffn"]


def _capacity(tokens: int, top_k: int, n_experts: int, cf: float) -> int:
    """Per-expert capacity.  The ``min(tokens, 16)`` floor makes tiny-token
    calls (single-token decode, smoke tests) drop-free — a token can occupy
    at most one slot per expert, so capacity >= tokens suffices there."""
    cap = max(1, -(-tokens * top_k // n_experts) if cf == 1.0
              else int(tokens * top_k / n_experts * cf) + 1)
    return max(cap, min(tokens, 16))


def init_moe(key, cfg) -> dict:
    ks = jax.random.split(key, 5)
    d, f, E = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    dt = jnp.dtype(cfg.param_dtype)
    s_in, s_out = d ** -0.5, f ** -0.5
    p = {
        "router": jax.random.normal(ks[0], (d, E), dt) * s_in,
        "w_in": jax.random.normal(ks[1], (E, d, f), dt) * s_in,
        "w_out": jax.random.normal(ks[2], (E, f, d), dt) * s_out,
    }
    if cfg.gated:
        p["w_gate"] = jax.random.normal(ks[3], (E, d, f), dt) * s_in
    return p


def _dispatch_compute(x, router_w, w_in, w_gate, w_out, *, top_k: int,
                      n_experts_global: int, expert_lo, capacity: int,
                      act: str, compute_dtype) -> jnp.ndarray:
    """Route ``x (T, d)`` through the local expert slice. Pure, shard-local."""
    T, d = x.shape
    E_loc, _, f = w_in.shape
    cdt = compute_dtype

    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)               # (T, E_global)
    gate, eid = jax.lax.top_k(probs, top_k)               # (T, k)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)

    # Flatten (T, k) assignments; mask to this shard's expert range.
    eid = eid.reshape(-1)
    gate = gate.reshape(-1)
    tok = jnp.repeat(jnp.arange(T), top_k)
    local_e = eid - expert_lo
    mine = (local_e >= 0) & (local_e < E_loc)
    key = jnp.where(mine, local_e, E_loc)                 # foreign -> sentinel
    order = jnp.argsort(key, stable=True)
    se, stok, sgate = key[order], tok[order], gate[order]

    counts = jnp.bincount(key, length=E_loc + 1)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(se.shape[0]) - starts[se]
    keep = (se < E_loc) & (pos < capacity)
    slot = jnp.where(keep, se * capacity + pos, E_loc * capacity)

    buf = jnp.zeros((E_loc * capacity, d), cdt)
    buf = buf.at[slot].set(x[stok].astype(cdt), mode="drop")
    buf = buf.reshape(E_loc, capacity, d)

    h = jnp.einsum("ecd,edf->ecf", buf, w_in.astype(cdt))
    if w_gate is not None:
        g = L.activation(jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(cdt)),
                         act)
        h = h * g
    else:
        h = L.activation(h, act)
    y = jnp.einsum("ecf,efd->ecd", h, w_out.astype(cdt))
    y = y.reshape(E_loc * capacity, d)

    yt = jnp.take(y, slot, axis=0, fill_value=0.0)        # (T*k, d)
    yt = yt * (sgate * keep).astype(cdt)[:, None]
    out = jnp.zeros((T, d), cdt).at[stok].add(yt)
    return out


def moe_ffn(x: jnp.ndarray, p: dict, cfg) -> jnp.ndarray:
    """MoE FFN on (B, S, d) activations, expert-parallel over the TP axis."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    cdt = jnp.dtype(cfg.compute_dtype)
    mesh = current_mesh()
    tp = RULES.tp if (mesh is not None and RULES.tp in mesh.axis_names
                      and E % mesh.shape[RULES.tp] == 0
                      and mesh.shape[RULES.tp] > 1) else None

    if tp is None:
        cap = _capacity(B * S, k, E, cfg.capacity_factor)
        out = _dispatch_compute(
            x.reshape(B * S, d), p["router"], p["w_in"], p.get("w_gate"),
            p["w_out"], top_k=k, n_experts_global=E, expert_lo=0,
            capacity=cap, act=cfg.act, compute_dtype=cdt)
        return out.reshape(B, S, d).astype(x.dtype)

    tp_size = mesh.shape[tp]
    E_loc = E // tp_size
    dp = tuple(a for a in RULES.dp if a in mesh.axis_names)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    T_loc = (B // dp_size if B % dp_size == 0 else B) * S
    cap = _capacity(T_loc, k, E, cfg.capacity_factor)

    has_gate = "w_gate" in p
    gate_w = p.get("w_gate")

    def shard_fn(x_l, router_w, w_in, w_gate, w_out):
        tp_idx = jax.lax.axis_index(tp)
        Bl, Sl, _ = x_l.shape
        out = _dispatch_compute(
            x_l.reshape(Bl * Sl, d), router_w, w_in,
            w_gate if has_gate else None, w_out, top_k=k,
            n_experts_global=E, expert_lo=tp_idx * E_loc, capacity=cap,
            act=cfg.act, compute_dtype=cdt)
        out = jax.lax.psum(out, tp)
        return out.reshape(Bl, Sl, d)

    in_specs = (P(dp, None, None), P(), P(tp, None, None),
                P(tp, None, None) if has_gate else P(),
                P(tp, None, None))
    args = (x, p["router"], p["w_in"],
            gate_w if has_gate else jnp.zeros((), cdt), p["w_out"])
    out = shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                        out_specs=P(dp, None, None), check_vma=False)(*args)
    return out.astype(x.dtype)
