"""Compile the main-path Pallas kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached (``jax.experimental.topologies``).  Each case
lowers one kernel wrapper at n=10 on the paper grids (the block kernels
also at n=8 on CEED BP6's 16x16x32) with every block size
the autotuner admits on a TPU backend (``autotune.candidate_*(tpu=True)``),
compiles it with Mosaic, and checks that the kernel is in the executable.
A compile proves nothing about results or times.  The loop cases compile
whole CG drivers and the autotuner's slab sweep loop, and check that the
loop carries its fields in place: no field-sized ``copy`` in the program.

The topology is described inside a module fixture (never at import) so
that every test worker collects the same tests; the persistent compilation
cache is off around these compiles.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune
from repro.kernels import nekbone_ax as K

N = 10
N2 = N * N
GRID_1024 = (8, 8, 16)
GRID_4096 = (16, 16, 16)
GRID_4096_SLIM = (8, 8, 64)     # one chip's share of the 4-chip s-step path
GRID_BP6 = (16, 16, 32)         # CEED BP5/BP6's mesh, at n=8
S = 4                           # s-step depth / Chebyshev order


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_tpu(one_chip, no_persistent_cache):
    """``compile_tpu(fn, *shapes)`` -> compiled HLO text, for one chip."""
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
                for s in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    return run


def _slab_operands(grid):
    """Kernel-layout operands: (n, n^2, E) fields, (3, n, n^2, E) metric."""
    ex, ey, ez = grid
    E = ex * ey * ez
    return E, ((N, N2, E), (N, N2, E), (N, N), (3, N, N2, E), (ex, N),
               (ey, N), (ez, N), (1, 1))


def _update_operands(grid, sz, b=None):
    ex, ey, ez = grid
    E = ex * ey * ez
    lead = () if b is None else (b,)
    f = lead + (N, N2, E)
    planes = lead + (ez // sz, N2, ex * ey)
    return (f, f, f, f, planes, planes, (1, 1 if b is None else b),
            (ex, N), (ey, N), (ez, N))


def _window_operands(grid, sz, halo):
    """Halo windows side by side on the element axis."""
    ex, ey, ez = grid
    nblk, L = ez // sz, sz + 2 * halo
    lanes = nblk * L * ex * ey
    return (N, N2, lanes), (3, N, N2, lanes), (nblk, L, N)


CASES = []
for E in (1024, 4096):
    for be in autotune.candidate_blocks(E, N, tpu=True):
        CASES.append(("v1_operator", E, be))
for grid in (GRID_1024, GRID_4096):
    for sz in autotune.candidate_slab_sizes("slab", grid, N, tpu=True):
        CASES.append(("v2_slab", grid, sz))
        CASES.append(("v2_cg_update", grid, sz))
for sz in autotune.candidate_slab_sizes("slab", GRID_1024, N, nrhs=8,
                                       tpu=True):
    CASES.append(("v2_slab_block_b8", GRID_1024, sz))
    CASES.append(("v2_cg_update_block_b8", GRID_1024, sz))
# the multi-RHS block kernels at the benchmark's grids: the coupled BP6
# solve (n=8, b=3, block slab + batched Jacobi update) and a service drain
# of b=2-4 at the paper's n=10 on a 16x16 cross-section
for b in (2, 3, 4):
    for sz in autotune.candidate_slab_sizes("slab", GRID_4096, N, nrhs=b,
                                           tpu=True):
        CASES.append(("slab_block", (GRID_4096, N, b), sz))
for sz in autotune.candidate_slab_sizes("slab", GRID_BP6, 8, nrhs=3, tpu=True):
    CASES.append(("slab_block", (GRID_BP6, 8, 3), sz))
    CASES.append(("pcg_update_block", (GRID_BP6, 8, 3), sz))
for grid in (GRID_1024, GRID_4096_SLIM):
    for sz in autotune.candidate_slab_sizes("sstep", grid, N, depth=S,
                                           tpu=True):
        CASES.append(("sstep_powers", grid, sz))
        CASES.append(("sstep_update", grid, sz))
for sz in autotune.candidate_slab_sizes("slab", GRID_1024, N, tpu=True):
    CASES.append(("fused_v1_dots", GRID_1024, sz))
    CASES.append(("fused_v1_pap", GRID_1024, sz))
    CASES.append(("pcg_update", GRID_1024, sz))
    CASES.append(("interp_10to5", GRID_1024, sz))
for sz in autotune.candidate_slab_sizes("cheb", GRID_1024, N, depth=S,
                                       tpu=True):
    CASES.append(("cheb_apply", GRID_1024, sz))


def _build(kind, where, size):
    """(fn, operand shapes) for one kernel wrapper call."""
    if kind == "v1_operator":
        E, be = where, size
        return (lambda u, D, g: K.nekbone_ax_pallas(u, D, D.T, g, n=N,
                                                    block_e=be),
                [(N, N2, E), (N, N), (6, N, N2, E)])
    if kind in ("slab_block", "pcg_update_block"):
        return _build_block(kind, *where, size)
    grid, sz = where, size
    ex, ey, ez = grid
    E = ex * ey * ez
    if kind == "v2_slab":
        return (lambda p, r, D, g, mx, my, mz, b: K.nekbone_ax_slab_pallas(
            p, r, D, D.T, g, mx, my, mz, b, n=N, grid=grid, sz=sz),
            list(_slab_operands(grid)[1]))
    if kind == "v2_cg_update":
        return (lambda *a: K.nekbone_cg_update_pallas(*a, n=N, grid=grid,
                                                      sz=sz),
                list(_update_operands(grid, sz)))
    if kind == "v2_slab_block_b8":
        shapes = list(_slab_operands(grid)[1])
        shapes[0], shapes[1], shapes[7] = (8, N, N2, E), (8, N, N2, E), (1, 8)
        return (lambda p, r, D, g, mx, my, mz, b:
                K.nekbone_ax_slab_block_pallas(p, r, D, D.T, g, mx, my, mz,
                                               b, n=N, grid=grid, sz=sz),
                shapes)
    if kind == "v2_cg_update_block_b8":
        return (lambda *a: K.nekbone_cg_update_block_pallas(
            *a, n=N, grid=grid, sz=sz), list(_update_operands(grid, sz, 8)))
    if kind == "sstep_powers":
        win, gwin, mzwin = _window_operands(grid, sz, S)
        return (lambda pe, re, D, ge, mx, my, mze, cx, cy, cz, th:
                K.nekbone_ax_powers_pallas(pe, re, D, D.T, ge, mx, my, mze,
                                           cx, cy, cz, th, n=N, grid=grid,
                                           sz=sz, s=S),
                [win, win, (N, N), gwin, (ex, N), (ey, N), mzwin, (ex, N),
                 (ey, N), (ez, N), (1, 1)])
    if kind == "sstep_update":
        return (lambda *a: K.nekbone_sstep_update_pallas(
            *a, n=N, grid=grid, sz=sz, s=S),
            [(N, N2, E)] * 3 + [(2 * S - 1, N, N2, E), (3, 2 * S + 1),
                                (ex, N), (ey, N), (ez, N)])
    be = sz * ex * ey
    if kind == "fused_v1_dots":
        return (lambda p, D, g, m, r, c: K.nekbone_ax_dots_pallas(
            p, D, D.T, g, m, r, c, n=N, block_e=be),
            [(N, N2, E), (N, N), (6, N, N2, E)] + [(N, N2, E)] * 3)
    if kind == "fused_v1_pap":
        return (lambda p, D, g, m: K.nekbone_ax_pap_pallas(
            p, D, D.T, g, m, n=N, block_e=be),
            [(N, N2, E), (N, N), (6, N, N2, E), (N, N2, E)])
    if kind == "pcg_update":
        shapes = list(_update_operands(grid, sz))
        shapes.insert(7, (N, N2, E))
        return (lambda *a: K.nekbone_pcg_update_pallas(*a, n=N, grid=grid,
                                                       sz=sz), shapes)
    if kind == "interp_10to5":
        return (lambda u, m: K.nekbone_interp_pallas(
            u, m, nin=N, nout=5, grid=grid, sz=sz), [(N, N2, E), (N, 5)])
    if kind == "cheb_apply":
        win, gwin, mzwin = _window_operands(grid, sz, S)
        return (lambda re, D, ge, mx, my, mze, cx, cy, cz, co:
                K.nekbone_cheb_apply_pallas(re, D, D.T, ge, mx, my, mze, cx,
                                            cy, cz, co, n=N, grid=grid,
                                            sz=sz, k=S),
                [win, (N, N), gwin, (ex, N), (ey, N), mzwin, (ex, N),
                 (ey, N), (ez, N), (S + 1, 2)])
    raise ValueError(kind)


def _build_block(kind, grid, n, b, sz):
    """(fn, operand shapes) of a multi-RHS block kernel at degree n-1."""
    ex, ey, ez = grid
    E, n2 = ex * ey * ez, n * n
    f = (b, n, n2, E)
    axes = [(ex, n), (ey, n), (ez, n)]
    if kind == "slab_block":
        return (lambda p, r, D, g, mx, my, mz, beta:
                K.nekbone_ax_slab_block_pallas(p, r, D, D.T, g, mx, my, mz,
                                               beta, n=n, grid=grid, sz=sz),
                [f, f, (n, n), (3, n, n2, E)] + axes + [(1, b)])
    planes = (b, ez // sz, n2, ex * ey)
    return (lambda *a: K.nekbone_pcg_update_block_pallas(
        *a, n=n, grid=grid, sz=sz),
        [f] * 4 + [planes, planes, (1, b), (n, n2, E)] + axes)


@pytest.mark.parametrize("kind,where,size", CASES,
                         ids=[f"{k}-{w}-{s}" for k, w, s in CASES])
def test_kernel_compiles_for_v5e(compile_tpu, kind, where, size):
    fn, shapes = _build(kind, where, size)
    assert "tpu_custom_call" in compile_tpu(fn, *shapes)


def test_autotuner_admits_no_16x16_sstep_window():
    """At a 16x16 cross-section the s=4 window's modelled footprint exceeds
    the VMEM limit, so the TPU pick raises instead of overshooting."""
    assert autotune.candidate_slab_sizes("sstep", GRID_4096, N, depth=S,
                                         tpu=True) == []
    with pytest.raises(ValueError, match="VMEM"):
        autotune.slab_config("sstep", GRID_4096, N, jnp.float32, depth=S,
                             backend="tpu")


@pytest.mark.xfail(strict=True, reason=(
    "Mosaic: 'Ran out of memory in memory space vmem' — the s=4 matrix-"
    "powers window of a 16x16 slab (2304 elements x 4 KB per vector) needs "
    "about 160 MiB of the chip's 128 MiB"))
def test_sstep_powers_16x16_window_exceeds_vmem(compile_tpu):
    fn, shapes = _build("sstep_powers", GRID_4096, 1)
    assert "tpu_custom_call" in compile_tpu(fn, *shapes)


# ---------------------------------------------------------------------------
# The CG loops carry their fields in place (kernels/nekbone_ax.SLAB_ALIASES,
# UPDATE_ALIASES): without the aliases XLA copies x, r (or z) and p out of
# the kernels' buffers into the loop carry on every iteration.
# ---------------------------------------------------------------------------

def _axes(grid, n):
    return [(g, n) for g in grid] * 2


def _loop_case(kind):
    """(fn, operand shapes, carried field shape) of one CG loop."""
    from repro.core import cg_block, cg_fused, precond

    flags = dict(interpret=False, acc_name="float32", x_name="float32")
    if kind == "cg_fused_v2":
        n, grid = N, GRID_4096
        E = grid[0] * grid[1] * grid[2]
        return (lambda b, D, g, *ax: cg_fused._cg_fused_v2(
            b, D, D.T, g, *ax, n=n, grid=grid, niter=100, sz=2, **flags),
            [(E, n, n, n), (n, n), (E, 3, n ** 3)] + _axes(grid, n),
            (n, n * n, E))
    n, grid = 8, GRID_BP6
    E = grid[0] * grid[1] * grid[2]
    if kind == "pcg_jacobi":
        return (lambda b, invd, D, g, *ax: precond._pcg_jacobi(
            b, invd, D, D.T, g, *ax, 1e-12, n=n, grid=grid, max_iter=1000,
            sz=4, **flags),
            [(E, n, n, n), (E, n ** 3), (n, n), (E, 3, n ** 3)]
            + _axes(grid, n), (n, n * n, E))
    if kind == "cg_block_coupled":
        return (lambda B, invd, D, g, *ax: cg_block._cg_block_coupled(
            B, invd, D, D.T, g, *ax, 1e-12, n=n, grid=grid, max_iter=1000,
            sz=2, **flags),
            [(3, E, n, n, n), (E, n ** 3), (n, n), (E, 3, n ** 3)]
            + _axes(grid, n), (3, n, n * n, E))
    assert kind == "slab_sweep_loop"

    def sweep(p, r, D, g, mx, my, mz, beta):
        f, _ = autotune._device_loop(
            lambda *a: K.nekbone_ax_slab_pallas(*a, n=n, grid=grid, sz=4),
            (p, r, D, D.T, g, mx, my, mz, beta), K.SLAB_ALIASES)
        return f()

    field = (n, n * n, E)
    return (sweep, [field, field, (n, n), (3,) + field]
            + _axes(grid, n)[:3] + [(1, 1)], field)


def _loop_field_copies(hlo: str, shape) -> list[str]:
    """The ``copy`` ops (and copy fusions) of an f32 ``shape`` inside the
    program's while-loop bodies: the ones paid on every iteration."""
    from repro.launch.hlo_analysis import parse_computations

    comps, _ = parse_computations(hlo)
    bodies = {b for lines in comps.values() for line in lines
              for b in re.findall(r"\bbody=%?([\w.\-]+)", line)}
    assert bodies, "no while loop in the program"
    field = "f32[" + ",".join(map(str, shape)) + "]"
    out = []
    for line in (ln for b in bodies for ln in comps[b]):
        m = re.match(r"\s*(ROOT )?%(\S+) = (.*?) ([\w-]+)\(", line)
        if m and field in m.group(3) and (
                m.group(4) in ("copy", "copy-start")
                or m.group(2).startswith("copy")):
            out.append(line.strip()[:120])
    return out


@pytest.mark.parametrize("kind", ["cg_fused_v2", "pcg_jacobi",
                                  "cg_block_coupled", "slab_sweep_loop"])
def test_cg_loop_carries_its_fields_in_place(compile_tpu, kind):
    fn, shapes, field = _loop_case(kind)
    hlo = compile_tpu(fn, *shapes)
    assert "tpu_custom_call" in hlo
    assert _loop_field_copies(hlo, field) == []
