"""Sharding rules: how model tensors map onto the production mesh.

Axis convention (launch/mesh.py):
  * ``pod``   — data parallelism across pods (gradient all-reduce over DCN;
                params replicated, optionally FSDP'd for the largest archs)
  * ``data``  — FSDP parameter sharding + batch data parallelism (ICI)
  * ``model`` — Megatron-style tensor parallelism (heads / ffn hidden /
                experts / vocab)

All constraints go through :func:`constrain`, which is a no-op when no mesh
is active — the same model code runs in single-device smoke tests and in the
512-chip dry-run.  Dimensions are only sharded when divisible by the axis
size (helper :meth:`AxisRules.div`), so e.g. 8 KV heads on a 16-way model
axis degrade gracefully to replication instead of erroring.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["AxisRules", "constrain", "current_mesh", "RULES", "set_rules",
           "solver_mesh", "shard_leading", "replicate"]


# ---------------------------------------------------------------------------
# solver mesh helpers (distributed/sstep.py, distributed/pcg.py): the sharded
# Nekbone drivers run on a 1-D mesh whose single axis carries the z element
# slabs — a much simpler world than the pod/data/model production mesh above.
# ---------------------------------------------------------------------------

def solver_mesh(ndev: int | None = None, axis_name: str = "z",
                devices=None):
    """A 1-D mesh over ``ndev`` devices for the sharded solver drivers.

    Defaults to every visible device; ``devices`` picks a sub-mesh (the
    shard-count sweeps in the tests).
    """
    if devices is None:
        devices = jax.devices()
    if ndev is None:
        ndev = len(devices)
    return jax.make_mesh((ndev,), (axis_name,), devices=devices[:ndev],
                         axis_types=(jax.sharding.AxisType.Auto,))


def shard_leading(x: jnp.ndarray, mesh, axis_name: str) -> jnp.ndarray:
    """``device_put`` with the leading axis split over ``axis_name``."""
    return jax.device_put(
        x, jax.sharding.NamedSharding(mesh, P(axis_name)))


def replicate(x: jnp.ndarray, mesh) -> jnp.ndarray:
    """``device_put`` fully replicated on ``mesh``."""
    return jax.device_put(x, jax.sharding.NamedSharding(mesh, P()))


def current_mesh():
    """The ambient mesh set by ``jax.set_mesh``, or ``None`` outside one."""
    m = jax.sharding.get_abstract_mesh()
    if m is None or m.empty:
        return None
    return m


def constrain(x: jnp.ndarray, spec: P) -> jnp.ndarray:
    """``with_sharding_constraint`` that is a no-op without an active mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    # Drop axis names the current mesh doesn't have (e.g. 'pod' on 1-pod).
    def filt(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in mesh.axis_names)
            return kept if kept else None
        return entry if entry in mesh.axis_names else None

    spec = P(*(filt(e) for e in spec))
    return jax.lax.with_sharding_constraint(x, spec)


@dataclasses.dataclass
class AxisRules:
    """Logical-to-mesh mapping with divisibility-aware helpers.

    Mutable singleton (:data:`RULES`): launchers tune it per run via
    :func:`set_rules` (e.g. ``fsdp_pod=True`` for the >100B archs) and every
    module sees the change because they all hold the same object.
    """

    dp: tuple[str, ...] = ("pod", "data")   # batch / token parallelism
    fsdp: str | None = "data"               # parameter sharding
    fsdp_pod: bool = False                  # also FSDP over 'pod' (huge archs)
    tp: str | None = "model"                # tensor parallelism
    seq: str | None = "data"                # context parallelism (long decode)

    # -- axis-size helpers --------------------------------------------------
    def _size(self, axes) -> int:
        mesh = current_mesh()
        if mesh is None:
            return 1
        if isinstance(axes, str):
            axes = (axes,)
        s = 1
        for a in axes:
            if a in mesh.axis_names:
                s *= mesh.shape[a]
        return s

    def div(self, dim: int, axes):
        """Return ``axes`` if ``dim`` divides evenly over them, else None."""
        if axes is None:
            return None
        sz = self._size(axes)
        return axes if (sz > 1 and dim % sz == 0) else (axes if sz == 1 else None)

    @property
    def fsdp_axes(self):
        if self.fsdp is None:
            return None
        return ("pod", self.fsdp) if self.fsdp_pod else self.fsdp

    # -- common specs --------------------------------------------------------
    def act_btd(self, d: int | None = None) -> P:
        """Activations (batch, seq, d_model): batch over dp."""
        return P(self.dp, None, None)

    def act_bthd(self, heads: int) -> P:
        """(batch, seq, heads, head_dim): heads over tp when divisible."""
        return P(self.dp, None, self.div(heads, self.tp), None)

    def w_in(self, d_in: int, d_out: int) -> P:
        """Input-side weight (d_in, d_out): FSDP rows, TP cols."""
        return P(self.div(d_in, self.fsdp_axes), self.div(d_out, self.tp))

    def w_out(self, d_in: int, d_out: int) -> P:
        """Output-side weight (d_in, d_out): TP rows, FSDP cols."""
        return P(self.div(d_in, self.tp), self.div(d_out, self.fsdp_axes))

    def w_expert(self, n_exp: int, d_in: int, d_out: int) -> P:
        """Expert weights (E, d_in, d_out): experts over TP, FSDP on d_in."""
        return P(self.div(n_exp, self.tp), self.div(d_in, self.fsdp_axes), None)

    def embed(self, vocab: int, d: int) -> P:
        """Embedding / unembedding (vocab, d): vocab over TP, d over FSDP."""
        return P(self.div(vocab, self.tp), self.div(d, self.fsdp_axes))

    def kv_cache(self, kv_heads: int) -> P:
        """KV cache (batch, kv_heads, seq, head_dim)."""
        return P(self.dp, self.div(kv_heads, self.tp), None, None)

    def kv_cache_cp(self, kv_heads: int) -> P:
        """Context-parallel KV cache for long single-sequence decode:
        the *sequence* axis is sharded (batch is 1)."""
        return P(None, self.div(kv_heads, self.tp), self.seq, None)


RULES = AxisRules()


def set_rules(**kw) -> AxisRules:
    """Mutate the global rules in place (same object everywhere)."""
    for k, v in kw.items():
        if not hasattr(RULES, k):
            raise AttributeError(f"AxisRules has no field {k!r}")
        setattr(RULES, k, v)
    return RULES
