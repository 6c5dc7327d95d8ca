"""End-to-end Nekbone case: SEM Poisson on a box, solved with CG.

This is the composable entry point for the paper's system:

    case = NekboneCase(n=10, grid=(8, 8, 16))     # degree 9, 1024 elements
    res  = case.solve_manufactured(niter=100)      # paper's benchmark run
    err  = case.solution_error(res.x)

The operator pipeline is exactly Nekbone's ``ax``:
    w = mask( gather_scatter( ax_local(u) ) )
with ``ax_local`` selectable between the paper-faithful Listing-1 version,
the XLA-fused version, and the Pallas TPU kernel (DESIGN.md §2).

Distribution: :meth:`sharded_ops` returns the same functions expressed for a
``shard_map`` over a device mesh, sharding elements along the z element axis
and assembling interfaces with a ppermute halo exchange (core/gs.py).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax.numpy as jnp
import numpy as np

import repro.core.ax as ax_mod
import repro.core.cg as cg_mod
import repro.core.cg_fused as cg_fused_mod
import repro.core.gs as gs_mod
from repro.core.cost import CostModel
from repro.core.geom import BoxMesh

__all__ = ["NekboneCase"]


@dataclasses.dataclass
class NekboneCase:
    """A runnable Nekbone problem instance.

    Args:
      n:       GLL points per direction (degree + 1). Paper uses 10.
      grid:    element grid (EX, EY, EZ).
      lengths: physical box size.
      dtype:   compute dtype (fp64 validated on CPU; fp32/bf16 TPU target).
      ax_impl: 'listing1' | 'fused' | 'pallas' | 'pallas_fused_cg' |
               'pallas_fused_cg_v2' | 'pallas_sstep_v3' | 'auto'.
               'auto' resolves at construction to the measured-fastest
               fused pipeline for this case shape via the autotune cache
               (kernels/autotune.pick_pipeline): on TPU both fused CG
               pipelines are timed once per (backend, case key) and the
               winner is persisted; elsewhere the documented E-threshold
               heuristic applies (E < AUTO_V2_MIN_E selects v1 — small
               element counts cannot amortize v2's second kernel
               dispatch; preconditioned cases always select v2, the only
               pipeline with fused PCG drivers).  The requested value is
               kept in ``ax_impl_requested``.  A vector case
               (``components`` = 3) resolves to 'pallas_fused_cg_v2', the
               pipeline its coupled route runs.
               The fused_cg variants select the step-fused CG pipelines
               (core/cg_fused.py): v1 runs one multi-output Pallas call per
               iteration plus XLA assembly/vector passes (DESIGN.md §3.3);
               v2 runs the whole iteration in two slab-resident Pallas
               kernels with in-kernel gather-scatter (DESIGN.md §3.4);
               sstep_v3 runs s iterations per cycle through the
               matrix-powers pipeline (core/cg_sstep.py, DESIGN.md §8).
      s:       iterations per s-step cycle (the 'pallas_sstep_v3' knob;
               ignored by every other ax_impl).
      precision: 'f64' | 'f32' | 'bf16' | 'bf16_ir' | 'f32_ir' | None —
               the fused pipeline's precision policy (DESIGN.md §7).
               Non-refined policies also set the case ``dtype`` to the
               storage dtype; refined (``*_ir``) policies keep ``dtype``
               as the *outer* (residual) precision and route fixed-iter
               solves through ``cg_ir_fixed_iters``.  ``None`` keeps the
               pre-policy behaviour: everything in ``dtype``.
      precond: None | 'jacobi' | 'cheb' (optionally 'cheb<k>') | 'pmg'
               (optionally 'pmg[cheb<k>]') — the case's default
               preconditioner (DESIGN.md §9 and §13, core/precond.py).
               Solves through the v2 fused pipeline dispatch to the fused
               PCG drivers (Jacobi: 14 streams/iter, Chebyshev: 18, pmg:
               the §13.4 V-cycle budget — more streams/iter, far fewer
               iterations); other ``ax_impl`` choices apply the reference
               (XLA) preconditioner through ``core/cg.py``.
               ``solve(precond=...)`` overrides per call and takes the
               same registry *names* — the string surface is the API.
               The pre-subsystem booleans (``True`` for 'jacobi',
               ``False`` for unpreconditioned) completed their
               deprecation cycle and now raise ``TypeError``.
      cheb_k:  Chebyshev polynomial order for ``precond='cheb'``.
      b:       default RHS batch for this case (DESIGN.md §12).  ``b > 1``
               routes unpreconditioned v2-family solves through the
               multi-RHS block kernels (core/cg_block.py), amortizing the
               operator streams across the batch.
      components: 1 (a scalar field) or 3 (a vector field, CEED BP6):
               with 3, ``solve`` takes one ``(3, E, n, n, n)`` field as
               one linear system under the shared scalar operator; its
               dots, residual measure and stopping rule run over the
               whole field (the ``vector`` route of core/solvers.py).
    """

    n: int = 10
    grid: tuple[int, int, int] = (4, 4, 4)
    lengths: tuple[float, float, float] = (1.0, 1.0, 1.0)
    dtype: jnp.dtype = jnp.float32
    ax_impl: str = "fused"
    precision: str | None = None
    s: int = 4
    precond: str | None = None
    cheb_k: int = 4
    b: int = 1
    components: int = 1

    def __post_init__(self):
        if self.components not in (1, 3):
            raise ValueError(f"components must be 1 (scalar) or 3 "
                             f"(vector); got {self.components!r}")
        policy = None
        if self.precision is not None:
            from repro.core.precision import resolve_policy

            policy = resolve_policy(self.precision)
            if not policy.refine:
                # storage dtype IS the case dtype: mesh fields, rhs, and
                # the solver all live in it (Eq.-2 streams are billed here).
                self.dtype = policy.storage_dtype
        self.ax_impl_requested = self.ax_impl
        if self.ax_impl == "auto" and self.components > 1:
            # the coupled vector route runs on the v2 kernels alone
            self.ax_impl = "pallas_fused_cg_v2"
        elif self.ax_impl == "auto":
            from repro.kernels import autotune as _autotune

            self.ax_impl = _autotune.pick_pipeline(
                self.grid, self.n, self.dtype,
                acc_dtype=None if policy is None else policy.accum,
                precond=self.precond)
        self.mesh = BoxMesh(self.n, self.grid, self.lengths)
        ops = self.mesh.ops
        dt = self.dtype
        self.D = jnp.asarray(ops.D, dt)
        self.g = jnp.asarray(self.mesh.geometric_factors(), dt)
        self.mask = jnp.asarray(self.mesh.dirichlet_mask(), dt)
        self.mult = jnp.asarray(self.mesh.multiplicity(), dt)
        self.c = self.mask / self.mult          # Nekbone's weight vector
        self.bmass = jnp.asarray(self.mesh.mass(), dt)

    # ------------------------------------------------------------------
    @property
    def cost(self) -> CostModel:
        from repro.core.cost import precision_itemsize

        itemsize = (precision_itemsize(self.precision)
                    if self.precision is not None
                    else jnp.dtype(self.dtype).itemsize)
        return CostModel(self.mesh.nelt, self.n, itemsize)

    # ------------------------------------------------------------------
    def ax_local(self, u: jnp.ndarray) -> jnp.ndarray:
        return ax_mod.ax_local(u, self.D, self.g, impl=self.ax_impl)

    def ax_full(self, u: jnp.ndarray) -> jnp.ndarray:
        """Assembled, masked Poisson operator (single shard)."""
        w = self.ax_local(u)
        w = gs_mod.ds_sum_local(w, self.grid)
        return w * self.mask

    # ------------------------------------------------------------------
    def manufactured(self):
        """Manufactured solution  u = prod sin(pi x_d / L_d)  and its rhs.

        Returns ``(u_exact, f)`` with f the *weak-form* right-hand side
        ``B f_strong`` assembled and masked, ready for CG.
        """
        xyz = self.mesh.coords()
        lx, ly, lz = self.lengths
        sx = np.sin(np.pi * xyz[..., 0] / lx)
        sy = np.sin(np.pi * xyz[..., 1] / ly)
        sz = np.sin(np.pi * xyz[..., 2] / lz)
        u_ex = sx * sy * sz
        lap = np.pi ** 2 * (1 / lx ** 2 + 1 / ly ** 2 + 1 / lz ** 2)
        f_strong = lap * u_ex
        f = jnp.asarray(f_strong, self.dtype) * self.bmass
        f = gs_mod.ds_sum_local(f, self.grid) * self.mask
        return jnp.asarray(u_ex, self.dtype), f

    # ------------------------------------------------------------------
    def dot(self) -> Callable:
        return cg_mod.weighted_dot(self.c)

    def _precond_name(self, precond) -> str | None:
        """Resolve a ``solve(precond=...)`` argument against the case.

        ``None`` inherits the case's ``precond`` field; a string names a
        registry preconditioner.  The pre-subsystem booleans (``True`` =
        'jacobi', ``False`` = unpreconditioned) went through one release
        of ``DeprecationWarning`` compat and are now removed.
        """
        if precond is None:
            return self.precond
        if isinstance(precond, bool):
            raise TypeError(
                "solve(precond=True|False) was removed after its "
                "deprecation cycle; pass the registry name instead "
                "(precond='jacobi', 'cheb4', 'pmg', ...), or omit the "
                "argument / pass precond=None for unpreconditioned.")
        return str(precond)

    def precond_spec(self, name: str | None = None):
        """The case's preconditioner spec (core/precond.py), cached.

        The Jacobi diagonal / Chebyshev Lanczos interval depend only on
        the case's operator — like the s-step theta, they are one-time
        setup costs per case, not per solve.
        """
        from repro.core import precond as precond_mod

        name = name or self.precond
        if name is None:
            return None
        if name in ("cheb", "chebyshev"):
            name = f"cheb{self.cheb_k}"
        cache = getattr(self, "_precond_specs", None)
        if cache is None:
            cache = self._precond_specs = {}
        spec = cache.get(name)
        if spec is None:
            spec = precond_mod.make_preconditioner(
                name, D=self.D, g=self.g, grid=self.grid, mask=self.mask,
                c=self.c, lengths=self.lengths)
            cache[name] = spec
        return spec

    def box_fields(self) -> jnp.ndarray:
        """The case's metric diagonal, checked and packed for the v2 family.

        The v2-family kernels rebuild ``mask`` and ``c`` from per-axis
        factors and read only the metric's diagonal, so both fields and
        the zero off-diagonal metric must be the structured box's.  Both
        checks (``cg_fused._check_box_fields``, ``kernels/ops.diag_metric``)
        run on the first call; later calls return the same packed
        ``(E, 3, n, n, n)`` diagonal in the case dtype, counted as
        ``driver.box_fields_reused``.  The cache is keyed on the identity of
        ``(g, mask, c)``: assigning another array to any of them checks
        again on the next call.
        """
        from repro.kernels import ops as kernel_ops
        from repro.obs import trace

        fields = (self.g, self.mask, self.c)
        cached = getattr(self, "_box_fields", None)
        if cached is not None and all(
                a is b for a, b in zip(cached[0], fields)):
            trace.count("driver.box_fields_reused")
            return cached[1]
        with trace.span("driver.prepare"):
            cg_fused_mod._check_box_fields(self.grid, self.n, self.mask,
                                           self.c)
            E, n = self.g.shape[0], self.n
            g3 = kernel_ops.diag_metric(self.g, E, n).reshape(E, 3, n, n, n)
        self._box_fields = (fields, g3)
        return g3

    def _reference_preconditioner(self, name: str | None):
        """The XLA-composed ``M(r)`` for the non-fused solver paths."""
        from repro.core import precond as precond_mod

        if name is None:
            return None
        spec = self.precond_spec(name)
        if isinstance(spec, precond_mod.JacobiPrecond):
            return lambda r: r * spec.invdiag
        if isinstance(spec, precond_mod.PMGPrecond):
            from repro.core import pmg as pmg_mod

            return pmg_mod.pmg_vcycle_reference(
                spec, D=self.D, g=self.g, grid=self.grid, mask=self.mask,
                c=self.c)
        return precond_mod.chebyshev_preconditioner(
            self.ax_full, spec.k, spec.lmin, spec.lmax)

    def solve(self, f: jnp.ndarray, *, b: int | None = None,
              niter: int | None = None, tol: float = 1e-8,
              max_iter: int = 1000,
              precond: str | None = None) -> cg_mod.SolveResult:
        """Solve ``A x = f`` through the driver registry (DESIGN.md §12).

        Routing (pipeline × precond × tol × batch) lives in
        :mod:`repro.core.solvers`; this method is the per-case entry.  A
        5-D ``f`` of shape (b, E, n, n, n) is a multi-RHS batch; ``b``
        can also be passed explicitly to validate the batch size.
        """
        from repro.core import solvers as solvers_mod

        return solvers_mod.solve_case(self, f, b=b, niter=niter, tol=tol,
                                      max_iter=max_iter, precond=precond)

    def solve_manufactured(self, *, niter: int | None = None, tol: float = 1e-8,
                           max_iter: int = 1000,
                           precond: str | None = None):
        u_ex, f = self.manufactured()
        res = self.solve(f, niter=niter, tol=tol, max_iter=max_iter,
                         precond=precond)
        return res, u_ex

    def solution_error(self, x: jnp.ndarray, u_exact: jnp.ndarray) -> jnp.ndarray:
        """Weighted max-norm error against the exact solution."""
        return jnp.max(jnp.abs((x - u_exact) * self.mask))

    # ------------------------------------------------------------------
    def operator_diagonal(self) -> jnp.ndarray:
        """diag(A) for the Jacobi preconditioner, computed structurally.

        Delegates to :func:`repro.core.precond.operator_diagonal` (the
        preconditioning subsystem owns the algebra, DESIGN.md §9.2):
        element-local diagonal from three small ``D∘D`` einsums, then
        assembled; masked rows are 1 to keep the inverse finite.
        """
        from repro.core.precond import operator_diagonal

        return operator_diagonal(self.D, self.g, self.grid,
                                 self.mask).astype(self.dtype)

    # ------------------------------------------------------------------
    # Distributed (shard_map) operator set
    # ------------------------------------------------------------------
    def shard_grid(self, n_shards: int) -> tuple[int, int, int]:
        ex, ey, ez = self.grid
        if ez % n_shards:
            raise ValueError(f"EZ={ez} not divisible by {n_shards} shards")
        return ex, ey, ez // n_shards

    def sharded_ax_full(self, axis_names) -> Callable:
        """Per-shard assembled operator, for use inside ``shard_map``.

        Shard-local inputs: u, g, mask blocks split along the element axis
        (z-major ordering makes a leading-axis split a z-split).
        """
        axis_names = tuple(axis_names)

        def op(u_local, g_local, mask_local, grid_local):
            w = ax_mod.ax_local(u_local, self.D, g_local, impl=self.ax_impl)
            w = gs_mod.ds_sum_sharded(w, grid_local, axis_names)
            return w * mask_local

        return op
