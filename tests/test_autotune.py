"""block_e autotuner: heuristic bounds, measurement path, cache behavior,
slab-mode candidates, and the JSON disk cache.

Picks timed with an injected ``measure`` on ``backend="tpu"`` see the same
candidate lists as a real TPU pick (128-lane element blocks fitting the
VMEM model), so those tests use shapes that have such blocks: E=512 flat
blocks and the 8x8x8 element grid (64-element slabs) at n=4.
"""
import json

import pytest

import jax.numpy as jnp

from repro.kernels import autotune


@pytest.fixture(autouse=True)
def _fresh_cache(tmp_path, monkeypatch):
    # point the disk layer at a per-test dir so tests never touch ~/.cache
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    autotune.clear_cache()
    yield
    autotune.clear_cache()


TPU_E = 512                    # flat blocks 512, 256, 128 on TPU
TPU_GRID = (8, 8, 8)           # slab blocks sz = 8, 4, 2 on TPU (n=4)


def test_vmem_heuristic_fits_budget():
    for n in (4, 8, 10, 12, 16):
        be = autotune.vmem_block_e(1024, n)
        assert be >= 1 and 1024 % be == 0
        assert (autotune.vmem_bytes("flat", n, be)
                <= autotune.VMEM_LIMIT_BYTES)


def test_candidates_divide_E():
    for E in (6, 8, 24, 1024):
        cands = autotune.candidate_blocks(E, 10)
        assert cands, (E,)
        assert all(E % be == 0 for be in cands)
        assert cands == sorted(cands, reverse=True)


def test_pick_is_cached_per_key():
    calls = []

    def measure(be):
        calls.append(be)
        return float(be)            # smaller block "faster": picks 1

    be1 = autotune.pick_block_e(TPU_E, 4, jnp.float32, backend="tpu",
                                measure=measure)
    assert be1 == 128
    n_calls = len(calls)
    # one reading per candidate in each of the sweep's rounds
    assert n_calls == autotune.SWEEP_ROUNDS * len(
        autotune.candidate_blocks(TPU_E, 4, tpu=True))

    # same key: served from cache, measure never re-runs
    be2 = autotune.pick_block_e(TPU_E, 4, jnp.float32, backend="tpu",
                                measure=measure)
    assert be2 == be1
    assert len(calls) == n_calls

    # different dtype / backend / shape are distinct cache keys
    autotune.pick_block_e(TPU_E, 4, jnp.float64, backend="tpu", measure=measure)
    assert len(calls) > n_calls
    assert len(autotune.cache_info()) == 2


def test_cpu_backend_uses_heuristic_without_measuring():
    def boom(be):
        raise AssertionError("must not measure on cpu")

    be = autotune.pick_block_e(64, 10, jnp.float32, backend="cpu")
    assert be == autotune.candidate_blocks(64, 10)[0]
    # keys carry the resolved (storage, accum) dtype pair (DESIGN.md §7)
    assert (10, 64, "float32", "float32", "cpu") in autotune.cache_info()


def test_measured_winner_beats_heuristic_order():
    # fastest candidate in the middle of the ladder must win
    target = {512: 3.0, 256: 1.0, 128: 2.0}

    def measure(be):
        return target[be]

    be = autotune.pick_block_e(TPU_E, 4, jnp.float32, backend="tpu",
                               measure=measure)
    assert be == 256


# ---------------------------------------------------------------------------
# precision-policy keys: (storage, accum) dtype pairs must never collide
# ---------------------------------------------------------------------------

def test_block_keys_distinct_per_dtype_pair():
    """(bf16,f32), (bf16,f64), (f32,f32), (f32,f64): four distinct keys.

    A collision would hand a slab/block size tuned for one VMEM working set
    (accum dtype decides the resident bytes) to a different kernel.
    """
    calls = []

    def measure_factory(tag):
        def measure(be):
            calls.append((tag, be))
            return float(be)
        return measure

    pairs = [("bfloat16", None), ("bfloat16", "float64"),
             ("float32", None), ("float32", "float64")]
    for i, (storage, acc) in enumerate(pairs):
        autotune.pick_block_e(TPU_E, 4, jnp.dtype(storage), acc_dtype=acc,
                              backend="tpu", measure=measure_factory(i))
    # every pair measured independently (no cache hits across pairs) ...
    assert {t for t, _ in calls} == set(range(len(pairs)))
    # ... under four distinct keys
    assert len(autotune.cache_info()) == len(pairs)

    # explicit accum equal to the storage-derived default is the SAME key:
    # the resolved pair, not the spelling, is what identifies the kernel.
    def boom(be):
        raise AssertionError("resolved-identical pair must hit the cache")

    autotune.pick_block_e(TPU_E, 4, jnp.bfloat16, acc_dtype="float32",
                          backend="tpu", measure=boom)


# ---------------------------------------------------------------------------
# slab mode (v2 pipeline)
# ---------------------------------------------------------------------------

def test_slab_candidates_divide_ez_and_fit_budget():
    for grid in ((2, 2, 8), (4, 8, 16), (1, 3, 5), (16, 16, 14)):
        for n in (4, 10):
            for tpu in (False, True):
                cands = autotune.candidate_slab_sizes("slab", grid, n,
                                                      tpu=tpu)
                assert all(grid[2] % sz == 0 for sz in cands)
                assert cands == sorted(cands, reverse=True)
                ex, ey, _ = grid
                # every candidate fits the one footprint model; compiled
                # blocks are also 128-lane aligned
                for sz in cands:
                    assert (autotune.vmem_bytes("slab", n, sz * ex * ey)
                            <= autotune.VMEM_LIMIT_BYTES), (grid, n, sz)
                    assert not tpu or (sz * ex * ey) % 128 == 0
            assert autotune.candidate_slab_sizes("slab", grid, n), \
                (grid, n)


def test_pick_slab_sz_cached_per_grid():
    calls = []

    def measure(sz, grid_order):
        calls.append((sz, grid_order))
        return float(sz)               # smallest "fastest": picks 1

    sz1, _ = autotune.slab_config("slab", TPU_GRID, 4, jnp.float32,
                                  backend="tpu", measure=measure)
    assert sz1 == 2                    # the smallest 128-lane block
    n_calls = len(calls)
    assert n_calls == autotune.SWEEP_ROUNDS * len(autotune.candidate_configs(
        autotune.candidate_slab_sizes("slab", TPU_GRID, 4, tpu=True)))
    # same key: cached; different grid: distinct key
    autotune.slab_config("slab", TPU_GRID, 4, jnp.float32, backend="tpu",
                         measure=measure)
    assert len(calls) == n_calls
    autotune.slab_config("slab", (8, 8, 4), 4, jnp.float32, backend="tpu",
                         measure=measure)
    assert len(calls) > n_calls
    assert (("cfg", "slab", 4, 8, 8, 8, "float32", "float32", "tpu")
            in autotune.cache_info())


@pytest.mark.parametrize("grid_order", [None, "parallel", "arbitrary"])
def test_slab_heuristic_on_cpu_prefers_largest(grid_order):
    """Off-TPU the pick is the largest fitting sz (``parallel`` unless the
    caller fixes the order), without measuring and without a disk entry."""
    def boom(sz, grid_order):
        raise AssertionError("must not measure on cpu")

    cfg = autotune.slab_config("slab", TPU_GRID, 4, jnp.float32,
                               backend="cpu", grid_order=grid_order)
    assert cfg == (autotune.candidate_slab_sizes("slab", TPU_GRID, 4)[0],
                   grid_order or "parallel")
    assert not autotune.cache_path().exists()


# ---------------------------------------------------------------------------
# disk persistence
# ---------------------------------------------------------------------------

def test_measured_pick_persists_and_reloads():
    def measure(be):
        return {512: 3.0, 256: 1.0, 128: 2.0}[be]

    be = autotune.pick_block_e(TPU_E, 4, jnp.float32, backend="tpu",
                               measure=measure)
    assert be == 256
    assert autotune.cache_path().exists()

    # simulate a fresh process: drop memory but keep the file
    autotune._CACHE.clear()
    autotune._DISK_LOADED = False

    def boom(be):
        raise AssertionError("disk-cached pick must not re-measure")

    be2 = autotune.pick_block_e(TPU_E, 4, jnp.float32, backend="tpu",
                                measure=boom)
    assert be2 == 256


def test_heuristic_pick_does_not_write_disk():
    autotune.pick_block_e(64, 10, jnp.float32, backend="cpu")
    assert not autotune.cache_path().exists()


def test_heuristic_picks_stay_out_of_measured_disk_cache():
    # a heuristic pick memoized before a measured one must not be persisted
    # alongside it — heuristic values recompute when the budget constants
    # change, so pinning them on disk would mask that.
    autotune.pick_block_e(64, 10, jnp.float32, backend="cpu")
    autotune.pick_block_e(TPU_E, 4, jnp.float32, backend="tpu",
                          measure=lambda be: float(be))
    data = json.loads(autotune.cache_path().read_text())
    keys = {tuple(e["key"]) for e in data["entries"]}
    assert keys == {(4, TPU_E, "float32", "float32", "tpu")}


def test_sstep_candidates_shrink_with_s():
    """The joint (sz, s) working set: more powers -> deeper halo + more
    live basis vectors -> a lower VMEM ceiling on sz."""
    for grid in ((2, 2, 8), (4, 4, 16)):
        for n in (4, 10):
            prev_max = None
            for s in (1, 2, 4, 8):
                cands = autotune.candidate_slab_sizes("sstep", grid, n,
                                                      depth=s)
                assert cands, (grid, n, s)
                assert all(grid[2] % sz == 0 for sz in cands)
                assert cands[-1] == 1
                if prev_max is not None:
                    assert cands[0] <= prev_max, (grid, n, s)
                prev_max = cands[0]


def test_cheb_candidates_shrink_with_k():
    """The Chebyshev-apply working set: deeper polynomial -> deeper halo
    -> a lower VMEM ceiling on sz (DESIGN.md §9.3)."""
    for grid in ((2, 2, 8), (4, 4, 16)):
        for n in (4, 10):
            prev_max = None
            for k in (1, 2, 4, 8):
                cands = autotune.candidate_slab_sizes("cheb", grid, n,
                                                      depth=k)
                assert cands, (grid, n, k)
                assert all(grid[2] % sz == 0 for sz in cands)
                assert cands[-1] == 1
                if prev_max is not None:
                    assert cands[0] <= prev_max, (grid, n, k)
                prev_max = cands[0]


# Each case: the keyword arguments of two picks that differ in one key
# dimension, and the two keys they must write.  A pick for one value must
# never serve another: the dtype pair sets the resident bytes, s and k the
# halo depth, the Jacobi update holds one more live block, and the RHS
# batch scales the per-RHS residents.
_F32 = ("float32", "float32", "tpu")
_KEY_CASES = {
    "dtype_pair": (
        dict(kind="slab", dtype=jnp.bfloat16), dict(acc_dtype="float64"),
        ("cfg", "slab", 4, 8, 8, 8, "bfloat16", "float32", "tpu"),
        ("cfg", "slab", 4, 8, 8, 8, "bfloat16", "float64", "tpu")),
    "s": (dict(kind="sstep", depth=2), dict(depth=4),
          ("cfg", "sstep", 4, 8, 8, 8, 2) + _F32,
          ("cfg", "sstep", 4, 8, 8, 8, 4) + _F32),
    "k": (dict(kind="cheb", depth=2), dict(depth=4),
          ("cfg", "cheb", 4, 8, 8, 8, 2) + _F32,
          ("cfg", "cheb", 4, 8, 8, 8, 4) + _F32),
    "precond": (dict(kind="slab"), dict(precond="jacobi"),
                ("cfg", "slab", 4, 8, 8, 8) + _F32,
                ("cfg", "slab", 4, 8, 8, 8) + _F32 + ("pc:jacobi",)),
    "nrhs": (dict(kind="slab", precond="jacobi"), dict(nrhs=3),
             ("cfg", "slab", 4, 8, 8, 8) + _F32 + ("pc:jacobi",),
             ("cfg", "slab", 4, 8, 8, 8) + _F32 + ("pc:jacobi", "rhs:3")),
}


@pytest.mark.parametrize("case", list(_KEY_CASES))
def test_slab_keys_carry_each_dimension(case):
    first, change, key_a, key_b = _KEY_CASES[case]
    calls = []

    def measure(sz, grid_order):
        calls.append((sz, grid_order))
        return float(sz)

    kw = dict(grid=TPU_GRID, n=4, dtype=jnp.float32, backend="tpu",
              measure=measure) | first
    assert autotune.slab_config(**kw) == (2, "parallel")
    n_calls = len(calls)
    autotune.slab_config(**kw)
    assert len(calls) == n_calls       # same key: cached
    autotune.slab_config(**kw | change)
    assert len(calls) > n_calls        # distinct key: a fresh sweep
    assert set(autotune.cache_info()) == {key_a, key_b}


def test_corrupt_cache_file_is_tolerated():
    path = autotune.cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text("{ not json !!")

    calls = []

    def measure(be):
        calls.append(be)
        return float(be)

    be = autotune.pick_block_e(TPU_E, 4, jnp.float32, backend="tpu",
                               measure=measure)
    assert be == 128 and calls         # re-measured, no crash
    # and the rewritten file is valid JSON with the new entry
    data = json.loads(path.read_text())
    assert any(tuple(e["key"]) == (4, TPU_E, "float32", "float32", "tpu")
               for e in data["entries"])


def test_clear_cache_removes_disk():
    def measure(be):
        return float(be)

    autotune.pick_block_e(TPU_E, 4, jnp.float32, backend="tpu", measure=measure)
    assert autotune.cache_path().exists()
    autotune.clear_cache()
    assert not autotune.cache_path().exists()
    assert not autotune.cache_info()


# ---------------------------------------------------------------------------
# joint (sz x grid_order) configs + pipeline dispatch (DESIGN.md §11)
# ---------------------------------------------------------------------------

def test_candidate_configs_cover_the_sweep_space():
    from repro.kernels.nekbone_ax import GRID_ORDERS

    cands = autotune.candidate_configs([4, 2, 1])
    assert len(cands) == 3 * len(GRID_ORDERS)
    assert len(set(cands)) == len(cands)
    # sz-major with the parallel point first per sz, so a measured tie
    # keeps the established configuration
    assert cands[0] == (4, "parallel")
    assert cands[len(GRID_ORDERS)] == (2, "parallel")


def test_pick_slab_config_measured_winner_and_persistence():
    def measure(sz, grid_order):
        # a non-default point must win: (2, arbitrary)
        return 0.0 if (sz, grid_order) == (2, "arbitrary") else 1.0 + sz

    cfg = autotune.slab_config("slab", TPU_GRID, 4, jnp.float32,
                               backend="tpu", measure=measure)
    assert cfg == (2, "arbitrary")
    assert autotune.cache_path().exists()

    # fresh process: reload from disk, tuple round-trips intact
    autotune._CACHE.clear()
    autotune._DISK_LOADED = False

    def boom(sz, grid_order):
        raise AssertionError("disk-cached pick must not re-measure")

    cfg2 = autotune.slab_config("slab", TPU_GRID, 4, jnp.float32,
                                backend="tpu", measure=boom)
    assert cfg2 == cfg
    assert isinstance(cfg2, tuple)


def test_keys_never_alias_across_kinds_pipeline_and_flat_blocks():
    """Each slab kind, the pipeline pick and the flat-block pick of one
    case keep their own key and their own measured value."""
    autotune.pick_block_e(TPU_E, 4, jnp.float32, backend="tpu",
                          measure=lambda be: float(be))
    autotune.pick_pipeline(TPU_GRID, 4, jnp.float32, backend="tpu",
                           measure=lambda p: float(p.endswith("v2")))
    for kind, depth in (("slab", 0), ("sstep", 2), ("cheb", 2)):
        autotune.slab_config(kind, TPU_GRID, 4, jnp.float32, depth=depth,
                             backend="tpu", measure=lambda sz, go: float(sz))
    assert autotune.cache_info() == {
        (4, TPU_E) + _F32: 128,
        ("pipeline", 4, 8, 8, 8) + _F32: "pallas_fused_cg",
        ("cfg", "slab", 4, 8, 8, 8) + _F32: (2, "parallel"),
        ("cfg", "sstep", 4, 8, 8, 8, 2) + _F32: (2, "parallel"),
        ("cfg", "cheb", 4, 8, 8, 8, 2) + _F32: (2, "parallel")}


def test_fixed_order_caller_reads_the_joint_sweep():
    """A caller that fixes the grid order (a kernel that takes none) gets
    that order's fastest size from the one joint sweep, by the sweep's
    margin rule: one ``autotune.sweep`` span for both callers."""
    from repro.obs import trace

    times = {(8, "parallel"): 1.0, (8, "arbitrary"): 0.9,
             (4, "parallel"): 0.97, (4, "arbitrary"): 0.5,
             (2, "parallel"): 0.99, (2, "arbitrary"): 0.6}

    def measure(sz, grid_order):
        return times[sz, grid_order]

    with trace.recording() as rec:
        par = autotune.slab_config("slab", TPU_GRID, 4, jnp.float32,
                                   grid_order="parallel", backend="tpu",
                                   measure=measure)
        joint = autotune.slab_config("slab", TPU_GRID, 4, jnp.float32,
                                     backend="tpu", measure=measure)
        arb = autotune.slab_config("slab", TPU_GRID, 4, jnp.float32,
                                   grid_order="arbitrary", backend="tpu",
                                   measure=measure)
    assert joint == (4, "arbitrary")
    assert par == (4, "parallel")      # 3% faster than 8/parallel
    # the arbitrary points' first, 8, is beaten by 4 by more than 2%
    assert arb == (4, "arbitrary")
    assert sum(r["name"] == "autotune.sweep" for r in rec.records
               if r["type"] == "span") == 1
    # within the margin the order's first point stays
    autotune.clear_cache()
    times[4, "parallel"] = 0.99
    assert autotune.slab_config("slab", TPU_GRID, 4, jnp.float32,
                                grid_order="parallel", backend="tpu",
                                measure=measure) == (8, "parallel")


# The three benchmark cells' shapes and routes, and the key and candidate
# configs each driver asked the autotuner for before its pick went
# behind one function, written out.
_CELL_PICKS = {
    "nekbone_p9_e4096.cg100": (
        "v2", 10, (16, 16, 16),
        ("cfg", "slab", 10, 16, 16, 16) + _F32,
        [(2, "parallel"), (2, "arbitrary"), (1, "parallel"),
         (1, "arbitrary")]),
    "ceed_bp5_p7_e8192.jacobi_rtol": (
        "v2_tol", 8, (16, 16, 32),
        ("cfg", "slab", 8, 16, 16, 32) + _F32 + ("pc:jacobi",),
        [(4, "parallel"), (4, "arbitrary"), (2, "parallel"),
         (2, "arbitrary"), (1, "parallel"), (1, "arbitrary")]),
    "ceed_bp6_p7_e8192.jacobi_rtol": (
        "vector", 8, (16, 16, 32),
        ("cfg", "slab", 8, 16, 16, 32) + _F32 + ("pc:jacobi", "rhs:3"),
        [(2, "parallel"), (2, "arbitrary"), (1, "parallel"),
         (1, "arbitrary")]),
}


@pytest.mark.parametrize("cell", list(_CELL_PICKS))
def test_cell_drivers_request_the_established_keys(cell, monkeypatch):
    """With a TPU backend and an injected timer, each cell's driver asks
    for the key and sweeps the candidates its cached pick was made under,
    in order, so a disk cache from before keeps answering it.  The
    driver is stopped right after its pick: nothing runs at cell size."""
    import numpy as np

    from repro.core import cg_block, cg_fused, precond

    route, n, grid, key, configs = _CELL_PICKS[cell]

    class Picked(Exception):
        pass

    seen = []
    pick = autotune.slab_config

    def tpu_pick(*a, **kw):
        kw.update(backend="tpu", measure=lambda sz, go: seen.append(
            (sz, go)) or float(sz))
        pick(*a, **kw)
        raise Picked

    monkeypatch.setattr(autotune, "slab_config", tpu_pick)
    E = grid[0] * grid[1] * grid[2]
    lead = (3,) if route == "vector" else ()
    rhs = np.zeros(lead + (E, n, n, n), np.float32)
    args = dict(D=np.zeros((n, n), np.float32), g=None, grid=grid)
    drive = {
        "v2": lambda: cg_fused.cg_fused_v2_fixed_iters(rhs, niter=100,
                                                       **args),
        "v2_tol": lambda: precond.cg_fused_tol(
            rhs, tol=1e-6, max_iter=10, precond="jacobi", **args),
        "vector": lambda: cg_block.cg_block_coupled_tol(
            rhs, tol=1e-6, max_iter=10, precond="jacobi", **args),
    }[route]
    with pytest.raises(Picked):
        drive()
    assert list(autotune.cache_info()) == [key]
    assert seen == configs * autotune.SWEEP_ROUNDS


def test_pick_pipeline_heuristic_threshold():
    # below AUTO_V2_MIN_E the fixed v2 overhead is not amortized -> v1
    assert autotune.pick_pipeline((2, 2, 2), 4, backend="cpu") \
        == "pallas_fused_cg"
    assert autotune.pick_pipeline((4, 4, 4), 4, backend="cpu") \
        == "pallas_fused_cg_v2"
    # heuristic picks never reach the disk cache
    assert not autotune.cache_path().exists()


def test_pick_pipeline_preconditioned_always_v2():
    """The fused PCG drivers only exist in v2 — no measurement, no cache
    entry, any E."""
    before = len(autotune.cache_info())

    def boom(pipeline):
        raise AssertionError("precond dispatch must not measure")

    got = autotune.pick_pipeline((2, 2, 2), 4, backend="tpu",
                                 precond="jacobi", measure=boom)
    assert got == "pallas_fused_cg_v2"
    assert len(autotune.cache_info()) == before


def test_pick_pipeline_measured_winner_persists():
    def measure(pipeline):
        return 1.0 if pipeline == "pallas_fused_cg_v2" else 2.0

    got = autotune.pick_pipeline((4, 4, 8), 4, jnp.float32, backend="tpu",
                                 measure=measure)
    assert got == "pallas_fused_cg_v2"

    autotune._CACHE.clear()
    autotune._DISK_LOADED = False

    def boom(pipeline):
        raise AssertionError("disk-cached pipeline must not re-measure")

    assert autotune.pick_pipeline((4, 4, 8), 4, jnp.float32, backend="tpu",
                                  measure=boom) == "pallas_fused_cg_v2"
    # str values survive the JSON round-trip as str (not listified)
    assert isinstance(autotune.pick_pipeline((4, 4, 8), 4, jnp.float32,
                                             backend="tpu"), str)


def test_pipeline_measure_checks_the_v2_fields_once():
    """The pick times each pipeline through its case route: v2's one-time
    box-field check falls in the warm-up, not in every timed solve."""
    from repro.obs import trace

    m = autotune._default_measure_pipeline((2, 2, 2), 4, jnp.float32)
    for pipeline, checks in (("pallas_fused_cg", 0),
                             ("pallas_fused_cg_v2", 1)):
        with trace.recording() as rec:
            assert m(pipeline) > 0
        assert sum(1 for r in rec.records
                   if r["name"] == "driver.validate") == checks, pipeline


def test_case_ax_impl_auto_resolves_and_records_request():
    from repro.core.nekbone import NekboneCase

    case = NekboneCase(n=3, grid=(2, 2, 2), dtype=jnp.float32,
                       ax_impl="auto")
    assert case.ax_impl_requested == "auto"
    assert case.ax_impl in ("pallas_fused_cg", "pallas_fused_cg_v2")
    # E=8 < AUTO_V2_MIN_E on the CPU heuristic -> v1
    if autotune.jax.default_backend() == "cpu":
        assert case.ax_impl == "pallas_fused_cg"
    big = NekboneCase(n=3, grid=(4, 4, 4), dtype=jnp.float32,
                      ax_impl="auto")
    assert big.ax_impl == "pallas_fused_cg_v2"
    # preconditioned auto: the fused PCG drivers force v2 at any E
    pc = NekboneCase(n=3, grid=(2, 2, 2), dtype=jnp.float32,
                     ax_impl="auto", precond="jacobi")
    assert pc.ax_impl == "pallas_fused_cg_v2"


def test_tpu_pick_sweeps_only_compiled_candidates():
    """An injected timer sees exactly the TPU candidate list: the 128-lane
    filter depends on the backend alone, never on who measures."""
    seen = []

    def measure(sz, grid_order):
        seen.append((sz, grid_order))
        return float(sz)

    autotune.slab_config("slab", TPU_GRID, 4, jnp.float32, backend="tpu",
                         measure=measure)
    # the rounds interleave the candidates
    assert seen == (autotune.candidate_configs(autotune.candidate_slab_sizes(
        "slab", TPU_GRID, 4, tpu=True)) * autotune.SWEEP_ROUNDS)
    assert 1 not in {sz for sz, _ in seen}   # 64 lanes: not a compiled block


def test_tpu_pick_without_admissible_block_raises_even_when_measured():
    with pytest.raises(ValueError, match="VMEM"):
        autotune.slab_config("slab", (2, 2, 8), 4, jnp.float32,
                             backend="tpu", measure=lambda sz, go: float(sz))
    with pytest.raises(ValueError, match="VMEM"):
        autotune.pick_block_e(8, 4, jnp.float32, backend="tpu",
                              measure=lambda be: float(be))


def test_stale_disk_schema_is_ignored():
    """A cache file of an older schema (3-tuple configs) is not read back:
    the pick re-measures and rewrites the file at the current version."""
    path = autotune.cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    key = ["cfg", "slab", 4, 8, 8, 8, "float32", "float32", "tpu"]
    path.write_text(json.dumps({"version": 1, "entries": [
        {"key": key, "value": [2, "fold", "parallel"]}]}))
    cfg = autotune.slab_config("slab", TPU_GRID, 4, jnp.float32,
                               backend="tpu", measure=lambda sz, go: float(sz))
    assert cfg == (2, "parallel")
    assert json.loads(path.read_text())["version"] == autotune._DISK_VERSION


# ---------------------------------------------------------------------------
# the sweep's estimator: interleaved rounds of chained calls, median, and a
# margin before the established (first) config is displaced
# ---------------------------------------------------------------------------

class _NoisyDevice:
    """A fake device and its wall clock.  A kernel call of a config costs
    the config's true seconds with 2% jitter; a sync waits up to 0.3 ms
    more (the dispatch-and-sync latency a lone sub-millisecond call is
    dominated by); one sync in twenty is stalled by 5 ms (preemption)."""

    def __init__(self, costs: dict, seed: int):
        import numpy as np

        self.costs = costs
        self.rng = np.random.default_rng(seed)
        self.now = 0.0

    def timer(self):
        return self.now

    def sync(self, x):
        self.now += self.rng.uniform(0.0, 3e-4)
        if self.rng.random() < 0.05:
            self.now += 5e-3
        return x

    def make(self, *cfg):
        cost = self.costs[cfg]

        def call():
            self.now += cost * (1.0 + self.rng.normal(0.0, 0.02))

        return call, 1


def _noisy_sweep(costs: dict, seed: int):
    dev = _NoisyDevice(costs, seed)
    timed = autotune._chained(dev.make, timer=dev.timer, sync=dev.sync)
    return autotune._sweep(("cfg", "slab", "test"), list(costs), timed)


def test_chained_sweep_picks_the_truly_faster_config_under_noise():
    # the second config is 5% faster: under the noise above a lone synced
    # call cannot tell them apart, a 20-ms chain can
    costs = {(2, "parallel"): 0.35e-3, (1, "arbitrary"): 0.3325e-3}
    for seed in range(20):
        best, seconds = _noisy_sweep(costs, seed)
        assert best == (1, "arbitrary"), (seed, seconds)
        assert set(seconds) == {"2/parallel", "1/arbitrary"}
        assert seconds["1/arbitrary"] == pytest.approx(0.3325e-3, rel=0.03)


def test_chained_sweep_keeps_the_first_config_under_a_tie():
    costs = {(2, "parallel"): 0.35e-3, (1, "arbitrary"): 0.35e-3}
    for seed in range(20):
        best, seconds = _noisy_sweep(costs, seed)
        assert best == (2, "parallel"), (seed, seconds)


def test_sweep_margin_keeps_the_first_candidate_within_two_percent():
    near = {512: 1.0, 256: 0.99, 128: 1.5}
    assert autotune.pick_block_e(TPU_E, 4, jnp.float32, backend="tpu",
                                 measure=near.get) == 512
    autotune.clear_cache()
    far = {512: 1.0, 256: 0.97, 128: 1.5}
    assert autotune.pick_block_e(TPU_E, 4, jnp.float32, backend="tpu",
                                 measure=far.get) == 256


def test_sweep_spans_name_the_key_and_each_timing():
    from repro.obs import trace

    with trace.recording() as rec:
        autotune.pick_block_e(TPU_E, 4, jnp.float32, backend="tpu",
                              measure=lambda be: float(be))
    spans = [r for r in rec.records if r["type"] == "span"]
    sweeps = [r for r in spans if r["name"] == "autotune.sweep"]
    measures = [r for r in spans if r["name"] == "autotune.measure"]
    n_cands = len(autotune.candidate_blocks(TPU_E, 4, tpu=True))
    assert len(sweeps) == 1
    assert sweeps[0]["attrs"] == {"key": f"4/{TPU_E}/float32/float32/tpu",
                                  "candidates": n_cands}
    assert len(measures) == autotune.SWEEP_ROUNDS * n_cands
    assert {(m["attrs"]["config"], m["attrs"]["seconds"])
            for m in measures} == {("512", 512.0), ("256", 256.0),
                                   ("128", 128.0)}


def test_disk_cache_round_trips_candidate_seconds():
    times = {512: 3.0, 256: 1.0, 128: 2.0}
    assert autotune.pick_block_e(TPU_E, 4, jnp.float32, backend="tpu",
                                 measure=times.get) == 256
    key = (4, TPU_E, "float32", "float32", "tpu")
    want = {"512": 3.0, "256": 1.0, "128": 2.0}
    (entry,) = json.loads(autotune.cache_path().read_text())["entries"]
    assert entry["seconds"] == want

    # a fresh process reads the pick and its seconds back ...
    autotune.clear_cache(disk=False)

    def boom(*_):
        raise AssertionError("disk-cached pick must not re-measure")

    assert autotune.pick_block_e(TPU_E, 4, jnp.float32, backend="tpu",
                                 measure=boom) == 256
    assert autotune._SECONDS == {key: want}
    # ... and keeps them when a later measured pick rewrites the file
    autotune.slab_config("slab", TPU_GRID, 4, jnp.float32, backend="tpu",
                         measure=lambda sz, go: float(sz))
    data = json.loads(autotune.cache_path().read_text())
    assert data["version"] == autotune._DISK_VERSION == 3
    seconds = {tuple(e["key"]): e["seconds"] for e in data["entries"]}
    assert seconds[key] == want
    assert seconds[("cfg", "slab", 4, 8, 8, 8, "float32", "float32",
                    "tpu")] == {"8/parallel": 8.0, "8/arbitrary": 8.0,
                                "4/parallel": 4.0, "4/arbitrary": 4.0,
                                "2/parallel": 2.0, "2/arbitrary": 2.0}


def test_schema_2_cache_file_loads_as_a_miss():
    """A schema-2 file (picks without their seconds) is not read back: the
    pick re-measures and the file is rewritten at schema 3."""
    path = autotune.cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    key = ["cfg", "slab", 4, 8, 8, 8, "float32", "float32", "tpu"]
    path.write_text(json.dumps({"version": 2, "entries": [
        {"key": key, "value": [8, "arbitrary"]}]}))
    calls = []

    def measure(sz, grid_order):
        calls.append((sz, grid_order))
        return float(sz)

    cfg = autotune.slab_config("slab", TPU_GRID, 4, jnp.float32,
                               backend="tpu", measure=measure)
    assert calls and cfg == (2, "parallel")
    (entry,) = json.loads(path.read_text())["entries"]
    assert entry["key"] == key and entry["value"] == [2, "parallel"]
    assert entry["seconds"]["2/parallel"] == 2.0
