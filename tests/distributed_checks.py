"""Multi-device checks, run in a subprocess with 8 fake host devices.

Invoked by tests/test_distributed.py (the device-count flag must be set
before jax initializes, so it cannot run in the main pytest process).
Prints one ``OK <name>`` line per passing check; exits non-zero on failure.

Usage:
    python tests/distributed_checks.py            # run every check
    python tests/distributed_checks.py NAME ...   # run named checks only
    python tests/distributed_checks.py --list     # print check names

Check names live in the ``CHECKS`` registry; ``test_distributed.py``
parametrizes one subprocess per name so a failure pinpoints its check.
"""
import contextlib
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp

from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from jax import set_mesh, shard_map  # noqa: E402
from jax.lax import axis_size  # noqa: E402


def make_mesh(shape, names):
    return jax.make_mesh(shape, names, axis_types=(
        jax.sharding.AxisType.Auto,) * len(names))


def check(name, cond):
    if not cond:
        raise SystemExit(f"FAIL {name}")
    print(f"OK {name}", flush=True)


@contextlib.contextmanager
def _x64():
    """Enable f64 for the fp64-round-off parity checks, restore after."""
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", old)


def mesh2d():
    return make_mesh((2, 4), ("data", "model"))


def mesh1d(name="data"):
    return make_mesh((8,), (name,))


# ---------------------------------------------------------------------------
def check_compressed_psum():
    from repro.distributed.compression import compressed_psum, quantized_psum

    mesh = mesh1d("pod")
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 64)), jnp.float32)

    def f(x):
        return compressed_psum(x, "pod")

    y = jax.jit(shard_map(f, mesh=mesh, in_specs=P("pod"),
                              out_specs=P("pod")))(x)
    # bf16 wire: ~3 decimal digits
    rel = float(jnp.abs(y - x.sum(0)).max() / (jnp.abs(x.sum(0)).max()))
    check("compressed_psum_bf16", rel < 2e-2)

    def fq(x):
        return quantized_psum(x, "pod")

    yq = jax.jit(shard_map(fq, mesh=mesh, in_specs=P("pod"),
                               out_specs=P("pod")))(x)
    relq = float(jnp.abs(yq - x.sum(0)).max() / (jnp.abs(x.sum(0)).max()))
    check("quantized_psum_int8", relq < 5e-2)


def check_collective_matmul():
    from repro.distributed.overlap import collective_matmul_allgather

    mesh = mesh1d("model")
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)  # global rows
    w = jnp.asarray(rng.normal(size=(32, 24)), jnp.float32)

    def f(x_shard, w):
        return collective_matmul_allgather(x_shard, w, "model")

    # after the full ring pass every shard holds the identical full result;
    # the VMA checker can't infer that, hence check_vma=False.
    y_full = jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P("model"), P()), out_specs=P(),
        check_vma=False))(x, w)
    want = x @ w
    err = float(jnp.abs(y_full - want).max())
    check("collective_matmul", err < 1e-4)


def check_cp_decode_attention():
    from repro.distributed.context_parallel import cp_decode_attention
    from repro.kernels.ref import attention_ref

    mesh = mesh1d("data")
    rng = np.random.default_rng(2)
    B, H, Hkv, S, d = 1, 4, 2, 64, 16
    q = jnp.asarray(rng.normal(size=(B, H, 1, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, d)), jnp.float32)
    valid = 50

    def f(q, k, v):
        return cp_decode_attention(q, k, v, axis_name="data",
                                   kv_valid_len=valid)

    got = jax.jit(shard_map(
        f, mesh=mesh,
        in_specs=(P(), P(None, None, "data", None),
                  P(None, None, "data", None)),
        out_specs=P()))(q, k, v)
    want = attention_ref(q, k[:, :, :valid], v[:, :, :valid], causal=False)
    err = float(jnp.abs(got - want).max())
    check("cp_decode_attention", err < 1e-4)


def check_sharded_gather_scatter():
    from repro.core.gs import ds_sum_local, ds_sum_sharded

    mesh = mesh1d("data")
    n, gridl = 4, (2, 3, 2)            # per-shard: EX=2 EY=3 EZ=2
    E_loc = 2 * 3 * 2
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(8 * E_loc, n, n, n)), jnp.float32)

    def f(u_loc):
        return ds_sum_sharded(u_loc, gridl, ("data",))

    got = jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"),
                                out_specs=P("data")))(u)
    want = ds_sum_local(u, (2, 3, 16))  # global grid: z stacked over shards
    err = float(jnp.abs(got - want).max())
    check("ds_sum_sharded_1d", err < 1e-5)


def check_sharded_gs_hierarchical():
    from repro.core.gs import ds_sum_local, ds_sum_sharded

    mesh = make_mesh((2, 4), ("pod", "data"))
    n, gridl = 3, (2, 2, 2)
    E_loc = 8
    rng = np.random.default_rng(4)
    u = jnp.asarray(rng.normal(size=(8 * E_loc, n, n, n)), jnp.float32)

    def f(u_loc):
        return ds_sum_sharded(u_loc, gridl, ("pod", "data"))

    got = jax.jit(shard_map(f, mesh=mesh, in_specs=P(("pod", "data")),
                                out_specs=P(("pod", "data"))))(u)
    want = ds_sum_local(u, (2, 2, 16))
    err = float(jnp.abs(got - want).max())
    check("ds_sum_sharded_hierarchical", err < 1e-5)


def check_sharded_nekbone_cg():
    """Distributed CG solve == single-shard solve (bitwise-ish)."""
    import repro.core.cg as cg_mod
    from repro.core.nekbone import NekboneCase

    mesh = mesh1d("data")
    case = NekboneCase(n=4, grid=(2, 2, 8), dtype=jnp.float32)
    u_ex, f = case.manufactured()
    res_local = case.solve(f, niter=40)

    op = case.sharded_ax_full(("data",))
    grid_l = case.shard_grid(8)

    def solve_sharded(f, g, mask, c):
        def A(u):
            return op(u, g, mask, grid_l)

        dot = cg_mod.weighted_dot(c, psum_axes="data")
        return cg_mod.cg_fixed_iters(A, f, niter=40, dot=dot).x

    espec = P("data")
    x = jax.jit(shard_map(
        solve_sharded, mesh=mesh,
        in_specs=(espec, P("data"), espec, espec),
        out_specs=espec))(f, case.g, case.mask, case.c)
    err = float(jnp.abs(x - res_local.x).max())
    scale = float(jnp.abs(res_local.x).max())
    check("sharded_nekbone_cg", err < 1e-4 * max(scale, 1.0))


def check_fused_cg_sharded():
    """Sharded fused-CG pipeline == single-device fused CG.

    Per shard: the fused operator+pap Pallas kernel, ``ds_sum_sharded`` for
    the cross-shard z-planes (``halo_exchange_z`` ppermutes), and psum'd
    inner-product partials.  check_vma off: the replication checker has no
    rule for pallas_call.
    """
    from repro.core.cg_fused import (cg_fused_fixed_iters,
                                     cg_fused_sharded_fixed_iters)
    from repro.core.nekbone import NekboneCase

    mesh = mesh1d("data")
    case = NekboneCase(n=4, grid=(2, 2, 8), dtype=jnp.float32)
    _, f = case.manufactured()
    niter = 30
    ref = cg_fused_fixed_iters(f, D=case.D, g=case.g, mask=case.mask,
                               c=case.c, grid=case.grid, niter=niter,
                               interpret=True)
    grid_l = case.shard_grid(8)

    def solve(f_l, g_l, m_l, c_l):
        res = cg_fused_sharded_fixed_iters(
            f_l, D=case.D, g=g_l, mask=m_l, c=c_l, grid_local=grid_l,
            axis_names=("data",), niter=niter, interpret=True)
        return res.x, res.rnorm_history

    x, hist = jax.jit(shard_map(
        solve, mesh=mesh, in_specs=(P("data"),) * 4,
        out_specs=(P("data"), P()), check_vma=False))(
            f, case.g, case.mask, case.c)
    scale = float(jnp.abs(ref.x).max())
    err = float(jnp.abs(x - ref.x).max())
    check("fused_cg_sharded_x", err < 1e-4 * max(scale, 1.0))
    h_ref = np.asarray(ref.rnorm_history)
    h = np.asarray(hist)
    check("fused_cg_sharded_hist",
          np.isfinite(h).all()
          and float(np.abs(h[:10] - h_ref[:10]).max()) < 1e-4 * h_ref[0])


def check_fused_cg_sharded_precision():
    """Sharded fused CG under non-f64 precision policies (DESIGN.md §7).

    The sharded path was previously only exercised wide: here each of the
    f32 / bf16 storage policies must (a) run SPMD-uniform on the 8-device
    mesh — the psum'd partials travel in the *accum* dtype, so alpha/beta
    stay shard-identical even when storage rounds — and (b) reproduce the
    single-device fused pipeline at the same policy: identical arithmetic
    except the psum association of the inner products.
    """
    from repro.core.cg_fused import (cg_fused_fixed_iters,
                                     cg_fused_sharded_fixed_iters)
    from repro.core.nekbone import NekboneCase

    mesh = mesh1d("data")
    niter = 20
    for policy, tol in (("f32", 1e-4), ("bf16", 2e-2)):
        case = NekboneCase(n=4, grid=(2, 2, 8), dtype=jnp.float32)
        _, f = case.manufactured()
        ref = cg_fused_fixed_iters(f, D=case.D, g=case.g, mask=case.mask,
                                   c=case.c, grid=case.grid, niter=niter,
                                   interpret=True, precision=policy)
        grid_l = case.shard_grid(8)

        def solve(f_l, g_l, m_l, c_l, policy=policy):
            res = cg_fused_sharded_fixed_iters(
                f_l, D=case.D, g=g_l, mask=m_l, c=c_l, grid_local=grid_l,
                axis_names=("data",), niter=niter, interpret=True,
                precision=policy)
            return res.x, res.rnorm_history

        x, hist = jax.jit(shard_map(
            solve, mesh=mesh, in_specs=(P("data"),) * 4,
            out_specs=(P("data"), P()), check_vma=False))(
                f, case.g, case.mask, case.c)
        check(f"fused_cg_sharded_{policy}_dtype",
              x.dtype == ref.x.dtype)
        xs = np.asarray(x, np.float64)
        rs = np.asarray(ref.x, np.float64)
        scale = float(np.abs(rs).max()) + 1e-30
        check(f"fused_cg_sharded_{policy}_x",
              float(np.abs(xs - rs).max()) < tol * scale)
        h = np.asarray(hist, np.float64)
        h_ref = np.asarray(ref.rnorm_history, np.float64)
        # early history must track tightly; late entries drift chaotically
        # once round-off feeds back through alpha/beta (same budget as the
        # wide-path check above) — finiteness + net decrease pin those.
        check(f"fused_cg_sharded_{policy}_hist",
              np.isfinite(h).all()
              and float(np.abs(h[:10] - h_ref[:10]).max()) < tol * h_ref[0]
              and h[-1] < h[0])


def check_seq_sharded_attention():
    """Sequence-parallel chunked attention == plain chunked (odd head count)."""
    from repro.models.attention import _chunked, _seq_sharded_chunked

    mesh = mesh2d()          # data=2, model=4
    rng = np.random.default_rng(5)
    B, H, Hkv, S, d = 2, 5, 5, 256, 16      # 5 heads: not divisible by tp=4
    q = jnp.asarray(rng.normal(size=(B, H, S, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, d)), jnp.float32)
    for window in (None, 32):
        want = _chunked(q, k, v, causal=True, window=window, cap=None,
                        scale=d ** -0.5, q_offset=0, block_q=64, block_k=64)
        with set_mesh(mesh):
            got = jax.jit(lambda q, k, v, w=window: _seq_sharded_chunked(
                q, k, v, causal=True, window=w, cap=None,
                scale=d ** -0.5))(q, k, v)
        err = float(jnp.abs(got - want).max())
        check(f"seq_sharded_attention_w{window}", err < 1e-4)


def check_seq_sharded_decode():
    """shard_map decode (seq-sharded KV + local write) == plain decode."""
    import dataclasses

    from repro.models import attention as A

    @dataclasses.dataclass(frozen=True)
    class Cfg:
        d_model: int = 32
        n_heads: int = 6          # not divisible by tp=4 -> seq-shard path
        n_kv_heads: int = 2
        head_dim: int = 8
        qkv_bias: bool = False
        qk_norm: bool = False
        attn_softcap: float | None = None
        pos_emb: str = "rope"
        rope_theta: float = 1e4
        norm_eps: float = 1e-6
        param_dtype: str = "float32"
        compute_dtype: str = "float32"

    cfg = Cfg()
    p = A.init_attention(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(6)
    B, S = 2, 32
    x = jnp.asarray(rng.normal(size=(B, 1, 32)), jnp.float32)
    cache = {
        "k": jnp.asarray(rng.normal(size=(B, 2, S, 8)), jnp.float32),
        "v": jnp.asarray(rng.normal(size=(B, 2, S, 8)), jnp.float32),
    }
    idx = jnp.asarray(17, jnp.int32)
    out_plain, nc_plain = A.decode_attention(x, p, cfg, cache, idx, window=9)
    mesh = mesh2d()
    with set_mesh(mesh):
        out_s, nc_s = jax.jit(
            lambda x, c: A.decode_attention(x, p, cfg, c, idx, window=9))(
                x, cache)
    check("seq_sharded_decode_out",
          float(jnp.abs(out_s - out_plain).max()) < 1e-4)
    check("seq_sharded_decode_cache",
          float(jnp.abs(nc_s["k"] - nc_plain["k"]).max()) < 1e-6)


def check_moe_shardmap_equals_local():
    import dataclasses

    from repro.models.moe import init_moe, moe_ffn

    @dataclasses.dataclass(frozen=True)
    class Cfg:
        d_model: int = 32
        d_ff_expert: int = 64
        n_experts: int = 8
        top_k: int = 2
        gated: bool = True
        act: str = "silu"
        capacity_factor: float = 8.0
        param_dtype: str = "float32"
        compute_dtype: str = "float32"

    cfg = Cfg()
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
    y_local = moe_ffn(x, p, cfg)
    mesh = mesh2d()
    with set_mesh(mesh):
        y_sharded = jax.jit(lambda x: moe_ffn(x, p, cfg))(x)
    err = float(jnp.abs(y_sharded - y_local).max())
    check("moe_shardmap_equals_local", err < 1e-5)


def check_pipeline_parallel():
    """2-stage GPipe pipeline == sequential application of both stages."""
    from repro.distributed.pipeline import pipeline_apply

    mesh = make_mesh((2, 4), ("pod", "data"))
    rng = np.random.default_rng(7)
    L, M, mb, d = 4, 6, 3, 16             # 4 layers -> 2 stages x 2 layers
    Ws = jnp.asarray(rng.normal(size=(L, d, d)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.normal(size=(M, mb, d)), jnp.float32)

    def stage_fn(W_stage, x):
        for i in range(W_stage.shape[0]):
            x = jnp.tanh(x @ W_stage[i])
        return x

    want = jnp.stack([stage_fn(Ws, x[m]) for m in range(M)])  # sequential
    Ws_staged = Ws.reshape(2, 2, d, d)     # (stage, layers/stage, d, d)

    def wrapped(ws, x):
        from jax.sharding import PartitionSpec as P

        def body(ws_local, x_full):
            out = pipeline_apply(ws_local[0], x_full, stage_fn,
                                 axis_name="pod")
            sid = jax.lax.axis_index("pod")
            S = axis_size("pod")
            return jnp.where(sid == S - 1, out, 0.0)[None]

        out = shard_map(
            body, mesh=mesh,
            in_specs=(P("pod"), P()), out_specs=P("pod"),
            check_vma=False)(ws, x)
        return out.sum(0)                  # only the last stage is nonzero

    got = jax.jit(wrapped)(Ws_staged, x)
    err = float(jnp.abs(got - want).max())
    check("pipeline_parallel_gpipe", err < 1e-5)


def check_elastic_checkpoint_reshard():
    """Save on one sharding, restore onto another mesh layout."""
    import tempfile

    from repro.checkpoint import CheckpointManager

    mesh = mesh2d()
    x = jnp.arange(64.0).reshape(8, 8)
    xs = jax.device_put(x, NamedSharding(mesh, P("data", "model")))
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(1, {"x": xs}, blocking=True)
        mesh_b = mesh1d("data")
        shard_b = {"x": NamedSharding(mesh_b, P(None, "data"))}
        _, back = mgr.restore({"x": x}, shardings=shard_b)
        np.testing.assert_array_equal(np.asarray(back["x"]), np.asarray(x))
        check("elastic_checkpoint_reshard",
              back["x"].sharding.spec == P(None, "data"))


def check_collective_matmul_colsharded():
    """Collective matmul, column-sharded weight layout: each shard holds a
    column slice of w and produces its column slice of all_gather(x) @ w —
    the ring body is layout-agnostic, only the specs change."""
    from repro.distributed.overlap import collective_matmul_allgather

    mesh = mesh1d("model")
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 24)), jnp.float32)

    def f(x_shard, w_cols):
        return collective_matmul_allgather(x_shard, w_cols, "model")

    y = jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P("model"), P(None, "model")),
        out_specs=P(None, "model"), check_vma=False))(x, w)
    err = float(jnp.abs(y - x @ w).max())
    check("collective_matmul_colsharded", err < 1e-4)


def check_collective_matmul_sweep():
    """Collective matmul over 1/2/4/8-device sub-meshes (solver_mesh)."""
    from repro.distributed.overlap import collective_matmul_allgather
    from repro.distributed.sharding import solver_mesh

    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)
    want = x @ w

    def f(x_shard, w_rep):
        return collective_matmul_allgather(x_shard, w_rep, "model")

    for p in (1, 2, 4, 8):
        mesh = solver_mesh(p, axis_name="model")
        y = jax.jit(shard_map(
            f, mesh=mesh, in_specs=(P("model"), P()), out_specs=P(),
            check_vma=False))(x, w)
        err = float(jnp.abs(y - want).max())
        check(f"collective_matmul_p{p}", err < 1e-4)


# -- sharded Nekbone solvers (DESIGN.md §10) --------------------------------

def _sstep_sharded_parity(s, grid, sz, niter, label):
    """Sharded s-step CG == single-device trajectory to fp64 round-off.

    ``niter`` stays pre-asymptotic (the in-cycle history floor caveat of
    tests/test_cg_sstep.py: once the residual collapses many orders within
    one cycle, late history entries sit at the f64-Gram round-off floor in
    *both* drivers but need not agree bitwise)."""
    with _x64():
        from repro.core.cg_sstep import cg_sstep_fixed_iters
        from repro.core.nekbone import NekboneCase
        from repro.distributed.sstep import cg_sstep_sharded_fixed_iters

        case = NekboneCase(n=4, grid=grid, dtype=jnp.float64)
        _, f = case.manufactured()
        kw = dict(D=case.D, g=case.g, grid=grid, niter=niter, s=s,
                  mask=case.mask, c=case.c, sz=sz, theta=2.25,
                  interpret=True)
        ref = cg_sstep_fixed_iters(f, **kw)
        got = cg_sstep_sharded_fixed_iters(f, ndev=8, **kw)
        h_ref = np.asarray(ref.rnorm_history, np.float64)
        h = np.asarray(got.rnorm_history, np.float64)
        check(f"{label}_hist",
              h.shape == h_ref.shape
              and float(np.abs(h - h_ref).max()) < 1e-9 * h_ref[0])
        xs = np.asarray(got.x, np.float64)
        rs = np.asarray(ref.x, np.float64)
        scale = float(np.abs(rs).max()) + 1e-30
        check(f"{label}_x", float(np.abs(xs - rs).max()) < 1e-8 * scale)


def check_sstep_sharded_s1():
    _sstep_sharded_parity(1, (2, 2, 16), 2, 10, "sstep_sharded_s1")


def check_sstep_sharded_s2():
    _sstep_sharded_parity(2, (2, 2, 16), 2, 10, "sstep_sharded_s2")


def check_sstep_sharded_s4():
    # EZ=32 over 8 shards: ez_local=4 >= s=4 (single-neighbour halo)
    _sstep_sharded_parity(4, (1, 2, 32), 2, 8, "sstep_sharded_s4")


def check_chip_smoke_sharded():
    """The ``--chips 4`` phase of chip_smoke.py on four of the host devices,
    at a tiny size (n=4, 2x2x16 elements, f32, interpret mode)."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    out = smoke.sharded_phase(4, grid=(2, 2, 16), n=4, s=4, niter=8)
    check("chip_smoke_sharded_placement", len(set(out["devices"])) == 4)
    check("chip_smoke_sharded_hist",
          len(out["hist_rel_diff_first10"]) == 9
          and out["hist_diff_over_bound_max"] <= 1.0)
    check("chip_smoke_sharded_x", out["x_max_abs_diff"] <= out["x_bound"])


def check_sstep_collective_counts():
    """The acceptance contract: exactly one stacked halo exchange
    (2 ppermutes) and one Gram psum per cycle; collective-free update.
    Covers both cycle paths: thin shards (single powers call) and the
    interior/boundary overlap split."""
    from repro.distributed.sstep import cycle_collective_counts

    cases = (
        (1, 1, (2, 2, 16)),   # thin: 2*nb >= nblk, single powers call
        (2, 2, (2, 2, 16)),
        (4, 2, (1, 2, 32)),
        (1, 1, (1, 1, 32)),   # ez_local=4, nblk=4: interior/boundary split
    )
    for s, sz, grid in cases:
        counts = cycle_collective_counts(grid=grid, n=4, s=s, sz=sz, ndev=8)
        check(f"sstep_counts_s{s}_sz{sz}_ez{grid[2]}",
              counts["cycle"] == {"ppermute": 2, "psum": 1}
              and counts["update"] == {})


def check_pcg_jacobi_sharded():
    """Sharded Jacobi PCG == single-device fused-v2 trajectory (f64)."""
    with _x64():
        from repro.core.nekbone import NekboneCase
        from repro.core.precond import pcg_fused_v2_fixed_iters
        from repro.distributed.pcg import pcg_sharded_fixed_iters

        grid = (2, 2, 16)
        case = NekboneCase(n=4, grid=grid, dtype=jnp.float64)
        _, f = case.manufactured()
        kw = dict(D=case.D, g=case.g, grid=grid, niter=12,
                  precond="jacobi", mask=case.mask, c=case.c, sz=2,
                  interpret=True)
        ref = pcg_fused_v2_fixed_iters(f, **kw)
        got = pcg_sharded_fixed_iters(f, ndev=8, **kw)
        h_ref = np.asarray(ref.rnorm_history, np.float64)
        h = np.asarray(got.rnorm_history, np.float64)
        ok = np.isfinite(h_ref)
        check("pcg_jacobi_sharded_hist",
              float(np.abs(h[ok] - h_ref[ok]).max()) < 1e-10 * h_ref[0])
        xs = np.asarray(got.x, np.float64)
        rs = np.asarray(ref.x, np.float64)
        scale = float(np.abs(rs).max()) + 1e-30
        check("pcg_jacobi_sharded_x",
              float(np.abs(xs - rs).max()) < 1e-9 * scale)


def check_pcg_cheb_sharded():
    """Sharded Chebyshev PCG == single-device fused-v2 trajectory (f64).

    ``cheb2``: k=2 ghost slabs <= ez_local=2 on the 8-way split of EZ=16.
    """
    with _x64():
        from repro.core.nekbone import NekboneCase
        from repro.core.precond import pcg_fused_v2_fixed_iters
        from repro.distributed.pcg import pcg_sharded_fixed_iters

        grid = (2, 2, 16)
        case = NekboneCase(n=4, grid=grid, dtype=jnp.float64)
        _, f = case.manufactured()
        kw = dict(D=case.D, g=case.g, grid=grid, niter=12,
                  precond="cheb2", mask=case.mask, c=case.c, sz=2,
                  cheb_sz=2, interpret=True)
        ref = pcg_fused_v2_fixed_iters(f, **kw)
        got = pcg_sharded_fixed_iters(f, ndev=8, **kw)
        h_ref = np.asarray(ref.rnorm_history, np.float64)
        h = np.asarray(got.rnorm_history, np.float64)
        ok = np.isfinite(h_ref)
        check("pcg_cheb_sharded_hist",
              float(np.abs(h[ok] - h_ref[ok]).max()) < 1e-10 * h_ref[0])
        xs = np.asarray(got.x, np.float64)
        rs = np.asarray(ref.x, np.float64)
        scale = float(np.abs(rs).max()) + 1e-30
        check("pcg_cheb_sharded_x",
              float(np.abs(xs - rs).max()) < 1e-9 * scale)


def check_pcg_sharded_precision():
    """Sharded PCG under the f32/bf16 storage policies (DESIGN.md §7):
    SPMD-uniform on 8 devices and within policy round-off of the
    single-device pipeline at the same policy."""
    from repro.core.nekbone import NekboneCase
    from repro.core.precond import pcg_fused_v2_fixed_iters
    from repro.distributed.pcg import pcg_sharded_fixed_iters

    grid = (2, 2, 16)
    for precond, policy, tol in (("jacobi", "f32", 1e-4),
                                 ("jacobi", "bf16", 2e-2),
                                 ("cheb2", "f32", 1e-4)):
        case = NekboneCase(n=4, grid=grid, dtype=jnp.float32)
        _, f = case.manufactured()
        kw = dict(D=case.D, g=case.g, grid=grid, niter=12, precond=precond,
                  mask=case.mask, c=case.c, sz=2, cheb_sz=2,
                  interpret=True, precision=policy)
        ref = pcg_fused_v2_fixed_iters(f, **kw)
        got = pcg_sharded_fixed_iters(f, ndev=8, **kw)
        check(f"pcg_sharded_{precond}_{policy}_dtype",
              got.x.dtype == ref.x.dtype)
        xs = np.asarray(got.x, np.float64)
        rs = np.asarray(ref.x, np.float64)
        scale = float(np.abs(rs).max()) + 1e-30
        check(f"pcg_sharded_{precond}_{policy}_x",
              float(np.abs(xs - rs).max()) < tol * scale)
        h = np.asarray(got.rnorm_history, np.float64)
        h_ref = np.asarray(ref.rnorm_history, np.float64)
        # early history tracks tightly; late entries drift chaotically once
        # storage round-off feeds back through alpha/beta (same budget as
        # check_fused_cg_sharded_precision) — finiteness + net decrease pin
        # the tail.
        check(f"pcg_sharded_{precond}_{policy}_hist",
              np.isfinite(h).all()
              and float(np.abs(h[:8] - h_ref[:8]).max()) < tol * h_ref[0]
              and h[-1] < h[0])


def check_pcg_sharded_tol_prefix():
    """Tol-driven sharded PCG is a bitwise prefix of the fixed-iteration
    trajectory (the tol2 = -1 sentinel contract of core/precond.py)."""
    with _x64():
        from repro.core.nekbone import NekboneCase
        from repro.distributed.pcg import (pcg_sharded_fixed_iters,
                                           pcg_sharded_tol)

        grid = (2, 2, 16)
        case = NekboneCase(n=4, grid=grid, dtype=jnp.float64)
        _, f = case.manufactured()
        kw = dict(D=case.D, g=case.g, grid=grid, precond="jacobi",
                  mask=case.mask, c=case.c, sz=2, interpret=True)
        full = pcg_sharded_fixed_iters(f, niter=20, ndev=8, **kw)
        tol = float(np.asarray(full.rnorm_history, np.float64)[12]) * 1.01
        got = pcg_sharded_tol(f, tol=tol, max_iter=20, ndev=8, **kw)
        kk = int(got.iters)
        check("pcg_sharded_tol_stops", 0 < kk < 20)
        h = np.asarray(got.rnorm_history, np.float64)
        h_full = np.asarray(full.rnorm_history, np.float64)
        check("pcg_sharded_tol_prefix",
              np.array_equal(h[:kk + 1], h_full[:kk + 1]))
        check("pcg_sharded_tol_nan_tail",
              np.isnan(h[kk + 1:]).all())


# ---------------------------------------------------------------------------
# registry + CLI
# ---------------------------------------------------------------------------

CHECKS = {
    "device_count": lambda: check("device_count", jax.device_count() == 8),
    "compressed_psum": check_compressed_psum,
    "collective_matmul": check_collective_matmul,
    "collective_matmul_colsharded": check_collective_matmul_colsharded,
    "collective_matmul_sweep": check_collective_matmul_sweep,
    "cp_decode_attention": check_cp_decode_attention,
    "sharded_gather_scatter": check_sharded_gather_scatter,
    "sharded_gs_hierarchical": check_sharded_gs_hierarchical,
    "sharded_nekbone_cg": check_sharded_nekbone_cg,
    "fused_cg_sharded": check_fused_cg_sharded,
    "fused_cg_sharded_precision": check_fused_cg_sharded_precision,
    "sstep_sharded_s1": check_sstep_sharded_s1,
    "sstep_sharded_s2": check_sstep_sharded_s2,
    "sstep_sharded_s4": check_sstep_sharded_s4,
    "sstep_collective_counts": check_sstep_collective_counts,
    "chip_smoke_sharded": check_chip_smoke_sharded,
    "pcg_jacobi_sharded": check_pcg_jacobi_sharded,
    "pcg_cheb_sharded": check_pcg_cheb_sharded,
    "pcg_sharded_precision": check_pcg_sharded_precision,
    "pcg_sharded_tol_prefix": check_pcg_sharded_tol_prefix,
    "seq_sharded_attention": check_seq_sharded_attention,
    "seq_sharded_decode": check_seq_sharded_decode,
    "moe_shardmap_equals_local": check_moe_shardmap_equals_local,
    "pipeline_parallel": check_pipeline_parallel,
    "elastic_checkpoint_reshard": check_elastic_checkpoint_reshard,
}


def main(argv=None):
    import sys

    argv = list(sys.argv[1:] if argv is None else argv)
    if "--list" in argv:
        for name in CHECKS:
            print(name)
        return
    names = argv or list(CHECKS)
    unknown = [a for a in names if a not in CHECKS]
    if unknown:
        raise SystemExit(
            f"unknown checks {unknown}; see --list for valid names")
    for name in names:
        CHECKS[name]()
    print("ALL-DISTRIBUTED-OK")


if __name__ == "__main__":
    main()
