"""Block-size selection for the nekbone Ax kernels, with a persistent cache.

The element block size is the kernel family's one tuning knob: it trades
VMEM residency (larger blocks amortize the grid and give the MXU taller
``e*n^2 x n`` operands) against the double-buffering headroom the pipeline
needs.  Two block modes exist:

* **Flat blocks** (:func:`pick_block_e`): any power-of-two element count —
  the v1 kernels' mode, where the block never needs to know the element
  grid.
* **Slab blocks** (:func:`slab_config`): whole z-slabs of the element box,
  ``block_e = sz * EX * EY`` with ``sz | EZ`` — the v2 pipeline's mode
  (DESIGN.md §3.4), where the x/y direct-stiffness summation must be
  intra-block, so the block must cover complete slabs of the z-major
  element order.  Every slab-family kernel (the slab kernel, its
  multi-RHS twin, the s-step powers and Chebyshev windows, and the
  update and interpolation kernels that share their split) gets its
  ``(sz, grid_order)`` from this one function, swept jointly over both;
  a kernel kind is one row of :data:`_SLAB_KINDS`.

Selection strategy (all modes):

* **Candidates**: the block sizes whose modelled VMEM footprint
  (:func:`vmem_bytes`, the one footprint model) fits the limit every
  compiled kernel gets (:data:`VMEM_LIMIT_BYTES`); on a TPU backend the
  element blocks must also be 128-lane aligned.  No candidate is admitted
  that overshoots, so a shape with none raises.
* **Heuristic** (off-TPU): the largest candidate.  Kernels there run in
  interpret mode, where wall time is meaningless.
* **Measurement** (on a TPU backend, or with an injected ``measure``):
  times the real kernel over the candidates and keeps the fastest — the
  empirical analog of the paper's per-architecture tuning sweep (its
  Table 1 re-tunes the CUDA kernel per GPU generation).  The estimator
  (:func:`_sweep`): :data:`SWEEP_ROUNDS` rounds interleaved across the
  candidates, each round one chain of back-to-back kernel calls lasting
  at least :data:`SWEEP_CHAIN_S` and ended by one sync, the median round
  per candidate; a candidate displaces the first in the fixed order only
  if it is faster by more than :data:`SWEEP_MARGIN`.

Results are memoized in a process-wide cache and — for *measured*
selections — persisted as JSON under ``$REPRO_CACHE_DIR`` (default
``~/.cache/repro``), together with every candidate's measured seconds,
so repeated benchmark runs skip the re-measuring sweep entirely and the
margin that decided a pick can be read back from the file.  The disk
cache is corrupt-file tolerant: an unreadable or malformed file is
ignored and overwritten on the next measured pick.
``clear_cache`` wipes both layers (pass ``disk=False`` to keep the file).
"""
from __future__ import annotations

import json
import os
import pathlib
import threading
from typing import Callable

import jax
import jax.numpy as jnp

from repro.kernels import timing as _timing

__all__ = ["vmem_block_e", "pick_block_e", "candidate_blocks",
           "candidate_slab_sizes", "candidate_configs", "slab_config",
           "pick_pipeline", "AUTO_V2_MIN_E",
           "SWEEP_ROUNDS", "SWEEP_CHAIN_S", "SWEEP_MARGIN",
           "clear_cache", "cache_info", "cache_path", "cache_stats"]

_CACHE: dict[tuple, object] = {}
# Disk-cache schema: 2 = joint configs are (sz, grid_order) pairs;
# 3 = each entry also holds every candidate's measured seconds.
_DISK_VERSION = 3
# keys whose value came from a timing sweep -> {config name: seconds}
_SECONDS: dict[tuple, dict[str, float]] = {}
_LOCK = threading.Lock()
_DISK_LOADED = False

# Scoped-VMEM limit every compiled kernel gets (kernels/nekbone_ax._call);
# TPU v5e has 128 MiB of VMEM per core.  The candidate lists below admit
# only blocks whose modelled footprint (vmem_bytes) fits this limit.
VMEM_LIMIT_BYTES = 100 * 2 ** 20


# ---------------------------------------------------------------------------
# the VMEM footprint model every candidate list is filtered by
# ---------------------------------------------------------------------------

def _field_bytes(n: int, lanes: int, itemsize: int = 4) -> int:
    """VMEM bytes of one ``(n, n^2, lanes)`` field block: n^2 sublanes
    padded to 8, lanes padded to 128, held in the (>= 4-byte) accumulation
    dtype."""
    return (n * (-(-(n * n) // 8) * 8) * (-(-lanes // 128) * 128)
            * max(itemsize, 4))


def vmem_bytes(kind: str, n: int, lanes: int, *, nrhs: int = 1,
               halo_lanes: int = 0, depth: int = 0,
               itemsize: int = 4) -> int:
    """Modelled VMEM footprint of one compiled kernel block.

    ``lanes`` is the block's element count (its lane width); ``kind``:

    * ``"flat"`` — v1 operator / fused-CG kernels: 10 double-buffered field
      streams (p, 6 metric, mask, r|c, w), 3 scratch fields, 2 of slack;
    * ``"slab"`` — the v2 slab and update kernels (and their multi-RHS
      twins): 12 field buffers per RHS (the update kernel's 4 in + 2 out
      streams, double-buffered) plus 16 shared (metric, scratch, stitch);
    * ``"sstep"`` / ``"cheb"`` — halo'd window kernels: windows of
      ``lanes + 2*halo_lanes`` elements, ``depth`` = s or k.  Powers: 5
      double-buffered window inputs + 5 scratch windows, and the update's
      ``2*(2s+5) + 2s+1`` owned fields; Chebyshev: 4 window inputs
      double-buffered + 7 scratch windows.
    """
    B = _field_bytes(n, lanes, itemsize)
    if kind == "flat":
        return 25 * B
    if kind == "slab":
        return (12 * nrhs + 16) * B
    W = _field_bytes(n, lanes + 2 * halo_lanes, itemsize)
    if kind == "sstep":
        return max(15 * W + 2 * (2 * depth - 1) * B,
                   (2 * (2 * depth + 5) + 2 * depth + 1) * B)
    if kind == "cheb":
        return 15 * W + 2 * B
    raise ValueError(f"unknown kernel kind {kind!r}")


def _fits(kind: str, n: int, lanes: int, itemsize: int, tpu: bool,
          **kw) -> bool:
    """A block is admissible: the model fits the limit and, for a
    compiled (TPU) kernel, its element blocks are 128-lane aligned."""
    halo = kw.get("halo_lanes", 0)
    if tpu and (lanes % 128 or halo % 128):
        return False
    return (vmem_bytes(kind, n, lanes, itemsize=itemsize, **kw)
            <= VMEM_LIMIT_BYTES)


def _no_fit(kind: str, what: str) -> ValueError:
    return ValueError(
        f"no {kind} block of {what} fits: the modelled VMEM footprint "
        f"(autotune.vmem_bytes) must stay under {VMEM_LIMIT_BYTES >> 20} "
        "MiB and, on a TPU backend, element blocks must be 128-lane "
        "aligned; use a smaller n or EX*EY cross-section, or another "
        "pipeline")


# ---------------------------------------------------------------------------
# disk persistence
# ---------------------------------------------------------------------------

def cache_path() -> pathlib.Path:
    """Location of the on-disk autotune cache (JSON)."""
    root = os.environ.get("REPRO_CACHE_DIR") or os.path.join(
        os.path.expanduser("~"), ".cache", "repro")
    return pathlib.Path(root) / "autotune.json"


def _load_disk_locked() -> None:
    """Merge the disk cache into memory once per process (caller holds lock).

    Tolerates a missing, unreadable, or corrupt file — autotuning then just
    re-measures and rewrites it.
    """
    global _DISK_LOADED
    if _DISK_LOADED:
        return
    _DISK_LOADED = True
    try:
        raw = json.loads(cache_path().read_text())
        if raw.get("version") != _DISK_VERSION:
            return                     # an older schema: re-measure
        for item in raw["entries"]:
            key = tuple(item["key"])
            val = item["value"]
            # three value shapes live in the file: ints (block/slab sizes),
            # lists (joint (sz, grid_order) configs; tuples round-trip
            # through JSON as lists), and strings (pipeline picks).
            if isinstance(val, list):
                val, ok = tuple(val), len(val) > 0
            elif isinstance(val, str):
                ok = len(val) > 0
            else:
                val = int(val)
                ok = val >= 1
            if ok:
                _CACHE.setdefault(key, val)
                # the file only ever holds measured picks
                _SECONDS.setdefault(key, {str(c): float(t) for c, t in
                                          item["seconds"].items()})
    except Exception:
        pass


def _save_disk_locked() -> None:
    """Atomically rewrite the disk cache (caller holds lock).

    Only *measured* selections are written, each with its candidates'
    seconds: heuristic picks are a pure function of the budget constants
    and must recompute when those change.
    """
    try:
        path = cache_path()
        path.parent.mkdir(parents=True, exist_ok=True)
        entries = [{"key": list(k),
                    "value": list(v) if isinstance(v, tuple) else v,
                    "seconds": _SECONDS[k]}
                   for k, v in sorted(_CACHE.items(), key=lambda kv: str(kv[0]))
                   if k in _SECONDS]
        payload = {"version": _DISK_VERSION, "entries": entries}
        tmp = path.with_name(path.name + ".tmp")
        tmp.write_text(json.dumps(payload, indent=1))
        tmp.replace(path)
    except Exception:
        pass  # read-only cache dir: persistence is best-effort


# hit/miss totals for the telemetry layer (obs/metrics.SolveTelemetry
# reports the per-solve delta); guarded by _LOCK like the cache itself.
_STATS = {"hits": 0, "misses": 0}


def cache_stats() -> dict:
    """Process-lifetime autotune cache counters ``{"hits", "misses"}``."""
    with _LOCK:
        return dict(_STATS)


def _cached_pick(key: tuple, pick: Callable[[], tuple]):
    """Shared lookup -> pick -> memoize (+persist if measured) path.

    ``pick`` runs only on a cache miss — it may build an expensive measure
    closure (synthetic operands, device transfers), so the warm path must
    never touch it — and returns ``(best, seconds)``: every candidate's
    measured seconds, or ``None`` for a heuristic pick.
    """
    from repro.obs import trace

    with _LOCK:
        _load_disk_locked()
        if key in _CACHE:
            _STATS["hits"] += 1
            trace.count("autotune.cache_hits")
            return _CACHE[key]
        _STATS["misses"] += 1
    trace.count("autotune.cache_misses")

    best, seconds = pick()

    with _LOCK:
        _CACHE.setdefault(key, best)
        if seconds is not None:
            _SECONDS.setdefault(key, seconds)
            _save_disk_locked()
        return _CACHE[key]


# ---------------------------------------------------------------------------
# the measured sweep's estimator
# ---------------------------------------------------------------------------

SWEEP_ROUNDS = 5        # timed rounds per candidate, interleaved
SWEEP_CHAIN_S = 0.02    # a round's chain of kernel calls lasts at least this
SWEEP_MARGIN = 0.02     # a candidate must beat the first by more than this


def _config_name(cfg) -> str:
    """``4``, ``4/arbitrary``, ``pallas_fused_cg``: a candidate as the
    disk cache and the spans name it."""
    return "/".join(map(str, cfg)) if isinstance(cfg, tuple) else str(cfg)


def _sweep(key: tuple, cands: list, timed) -> tuple:
    """Pick among ``cands`` (the fixed order, established choice first)
    by ``timed(*config) -> seconds``, one reading per call.

    :data:`SWEEP_ROUNDS` rounds, each timing every candidate once, so slow
    drift of the machine falls on all candidates alike; the median round
    per candidate is its time.  The first candidate is kept unless the
    fastest beats it by more than :data:`SWEEP_MARGIN`, so a tie within
    the noise does not move the pick between processes.  Returns
    ``(best, {config name: seconds})``.
    """
    from repro.obs import trace

    rounds = [[] for _ in cands]
    with trace.span("autotune.sweep", key=_config_name(key),
                    candidates=len(cands)):
        for _ in range(SWEEP_ROUNDS):
            for cfg, ts in zip(cands, rounds):
                with trace.span("autotune.measure",
                                config=_config_name(cfg)) as sp:
                    t = timed(*cfg) if isinstance(cfg, tuple) else timed(cfg)
                    sp.set(seconds=t)
                ts.append(t)
    med = [_timing.median(ts) for ts in rounds]
    return (_fastest(cands, med),
            {_config_name(c): t for c, t in zip(cands, med)})


def _fastest(cands: list, med: list[float]):
    """The fastest of ``cands`` by their times ``med``, unless it beats the
    first by no more than :data:`SWEEP_MARGIN`: then the first."""
    best = min(range(len(cands)), key=med.__getitem__)
    return cands[best] if med[best] < med[0] * (1.0 - SWEEP_MARGIN) \
        else cands[0]


# kernel calls per dispatch of a sweep's device loop: enough that the
# device, not the host's dispatch of the next loop, sets the pace (a lone
# jitted call took about 0.6 ms to dispatch on a v5e host, longer than a
# 0.35-ms slab kernel runs).
_LOOP_CALLS = 16


def _device_loop(fn, args: tuple, aliases: dict | None = None):
    """``(f, calls)``: ``f()`` runs ``fn(*args)`` ``calls`` =
    :data:`_LOOP_CALLS` times back to back in one jitted loop on the
    device.  Each call's smallest operand
    takes a zero computed from the previous call's smallest output, so no
    call can be hoisted out of the loop or merged with another.

    ``aliases`` (operand index -> result index, the kernel's in-place map,
    e.g. ``nekbone_ax.SLAB_ALIASES``) threads those operands through the
    loop: call k+1 takes call k's result in that slot, as a solve's loop
    carries it, so the kernel is timed updating in place.  An operand
    that stayed the same across calls would make XLA copy it before
    every aliased call."""
    import jax.lax as lax

    aliases = aliases or {}
    i = min(range(len(args)), key=lambda j: args[j].size)

    @jax.jit
    def loop(*a):
        def body(_, c):
            t, carried = c
            b = list(a)
            for j, v in zip(aliases, carried):
                b[j] = v
            b[i] = b[i] + t.astype(b[i].dtype)
            res = fn(*b)
            out = min(jax.tree_util.tree_leaves(res), key=lambda x: x.size)
            return ((jnp.sum(out) * 0).astype(t.dtype),
                    tuple(res[k] for k in aliases.values()))

        return lax.fori_loop(0, _LOOP_CALLS, body,
                             (jnp.zeros((), jnp.float32),
                              tuple(a[j] for j in aliases)))[0]

    return (lambda: loop(*args)), _LOOP_CALLS


def _chained(make, *, timer=None, sync=None):
    """A sweep's ``timed`` over real kernel calls: ``make(*config)`` gives
    ``(f, calls)``, a zero-argument dispatch of ``calls`` kernel calls of
    one config (:func:`_device_loop`).  A config's first round compiles it
    and sizes a chain of back-to-back dispatches
    (:func:`kernels.timing.chain_length`) to :data:`SWEEP_CHAIN_S`; every
    round times one chain, per kernel call."""
    chains = {}

    def timed(*cfg):
        if cfg not in chains:
            f, calls = make(*cfg)
            chains[cfg] = (f, calls, _timing.chain_length(
                f, seconds=SWEEP_CHAIN_S, timer=timer, sync=sync))
        f, calls, chain = chains[cfg]
        return _timing.measure(f, reps=1, warmup=0, timer=timer, sync=sync,
                               chain=chain) / calls

    return timed


# ---------------------------------------------------------------------------
# flat element blocks (v1 kernels)
# ---------------------------------------------------------------------------

def vmem_block_e(E: int, n: int,
                 vmem_budget_bytes: int = VMEM_LIMIT_BYTES,
                 itemsize: int = 4) -> int:
    """Largest power-of-two divisor of ``E`` whose modelled v1 footprint
    (:func:`vmem_bytes` ``"flat"``) fits the budget (1 if none does)."""
    be = 1 << (E.bit_length() - 1)
    while be > 1 and (E % be or vmem_bytes("flat", n, be, itemsize=itemsize)
                      > vmem_budget_bytes):
        be //= 2
    return be


def candidate_blocks(E: int, n: int, itemsize: int = 4, *,
                     tpu: bool = False) -> list[int]:
    """Element-block candidates (descending) for the v1 kernels: divisors
    of ``E`` (no padding) that fit :func:`vmem_bytes` — powers of two, or
    with ``tpu`` multiples of 128 (possibly empty)."""
    if tpu:
        sizes = range(E - E % 128, 0, -128)
    else:
        sizes = [1 << k for k in range(E.bit_length() - 1, -1, -1)]
    return [be for be in sizes
            if E % be == 0 and _fits("flat", n, be, itemsize, tpu)]


def _default_measure(E: int, n: int, dtype,
                     acc_dtype=None) -> Callable[[int], float]:
    """Times the real Ax kernel on synthetic data for one block size."""
    import numpy as np

    from repro.core.sem import derivative_matrix
    from repro.kernels import nekbone_ax as _ax

    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(n, n * n, E)), dtype)
    g = jnp.asarray(rng.normal(size=(6, n, n * n, E)), dtype)
    D = jnp.asarray(derivative_matrix(n), dtype)
    Dt = D.T

    def make(block_e: int):
        return _device_loop(lambda *a: _ax.nekbone_ax_pallas(
            *a, n=n, block_e=block_e, interpret=False, acc_dtype=acc_dtype),
            (u, D, Dt, g))

    return _chained(make)


def _acc_name(dtype, acc_dtype) -> str:
    """Resolved accumulation-dtype name for cache keys.

    Mirrors ``kernels/nekbone_ax._accum``: an explicit precision-policy
    choice wins, else f64 storage accumulates in f64 and everything
    narrower in f32.  Keys carry the resolved pair so e.g. (bf16, f32) and
    (bf16, f64) — different VMEM working sets, different kernels — never
    collide.
    """
    if acc_dtype is not None:
        return jnp.dtype(acc_dtype).name
    return "float64" if jnp.dtype(dtype) == jnp.float64 else "float32"


def pick_block_e(E: int, n: int, dtype=jnp.float32, *,
                 acc_dtype=None, backend: str | None = None,
                 measure: Callable[[int], float] | None = None) -> int:
    """Best ``block_e`` for ``(E, n, storage/accum dtypes)``, memoized.

    On a TPU backend (or when an explicit ``measure`` callable is supplied)
    the candidates are timed and the fastest wins; elsewhere the VMEM
    heuristic decides directly — interpret-mode wall time reflects the
    emulator, not the hardware, so measuring it would tune for noise.
    Measured picks persist to :func:`cache_path`.
    """
    dtype = jnp.dtype(dtype)
    backend = backend or jax.default_backend()
    acc_name = _acc_name(dtype, acc_dtype)
    key = (n, E, dtype.name, acc_name, backend)
    # the live block arrays sit in VMEM in the *accumulation* dtype, so
    # candidates must be sized by the wider of the pair — a (bf16, f64)
    # policy holds 8-byte temporaries off 2-byte streams.
    size_item = max(dtype.itemsize, jnp.dtype(acc_name).itemsize)

    def pick() -> tuple[int, bool]:
        cands = candidate_blocks(E, n, itemsize=size_item,
                                 tpu=backend == "tpu")
        if not cands:
            raise _no_fit("v1 element", f"E={E} (n={n})")
        m = measure
        if m is None and backend == "tpu":
            m = _default_measure(E, n, dtype, acc_dtype)
        if m is None:
            return cands[0], None
        return _sweep(key, cands, m)

    return _cached_pick(key, pick)


# ---------------------------------------------------------------------------
# the slab-family kernels: one table, one pick (DESIGN.md §11)
# ---------------------------------------------------------------------------

def _slab_operands(grid: tuple[int, int, int], n: int, dtype, nrhs: int = 1):
    """Synthetic operands of a slab-family measure: ``p`` and ``r`` (with a
    leading RHS axis when ``nrhs > 1``), the packed metric diagonal, ``D``,
    and the box's per-axis ``(mask, c)`` factors, in ``dtype``."""
    import numpy as np

    from repro.core.geom import box_axis_factors
    from repro.core.sem import derivative_matrix

    ex, ey, ez = grid
    field = (n, n * n, ex * ey * ez)
    lead = (nrhs,) if nrhs != 1 else ()
    rng = np.random.default_rng(0)
    p = jnp.asarray(rng.normal(size=lead + field), dtype)
    r = jnp.asarray(rng.normal(size=lead + field), dtype)
    g3 = jnp.asarray(rng.normal(size=(3,) + field), dtype)
    D = jnp.asarray(derivative_matrix(n), dtype)
    factors = tuple(tuple(jnp.asarray(a, dtype) for a in f)
                    for f in box_axis_factors(grid, n))
    return p, r, g3, D, factors


def _measure_slab(grid, n, dtype, acc_dtype, nrhs, depth):
    """Times the v2 slab kernel (the batched one when ``nrhs > 1``)."""
    from repro.kernels import nekbone_ax as _ax

    p, r, g3, D, ((mx, my, mz), _) = _slab_operands(grid, n, dtype, nrhs)
    kernel = (_ax.nekbone_ax_slab_pallas if nrhs == 1
              else _ax.nekbone_ax_slab_block_pallas)
    # beta = 0: each call's p is r, so the threaded p stays bounded
    beta = jnp.zeros((1, nrhs), _ax._accum(jnp.dtype(dtype), acc_dtype))
    Dt = D.T

    def make(sz: int, grid_order: str):
        return _device_loop(lambda *a: kernel(
            *a, n=n, grid=grid, sz=sz, interpret=False, acc_dtype=acc_dtype,
            grid_order=grid_order), (p, r, D, Dt, g3, mx, my, mz, beta),
            _ax.SLAB_ALIASES)

    return _chained(make)


def _measure_sstep(grid, n, dtype, acc_dtype, nrhs, depth):
    """Times the v3 matrix-powers kernel at ``s = depth``."""
    from repro.kernels import nekbone_ax as _ax

    p, r, g3, D, ((mx, my, mz), (cx, cy, cz)) = _slab_operands(grid, n,
                                                               dtype)
    inv_theta = jnp.ones((1, 1), _ax._accum(jnp.dtype(dtype), acc_dtype))
    Dt = D.T

    def make(sz: int, grid_order: str):
        def ext(f):
            return _ax.sstep_extend_field(f, grid, sz, depth)

        return _device_loop(lambda *a: _ax.nekbone_ax_powers_pallas(
            *a, n=n, grid=grid, sz=sz, s=depth, interpret=False,
            acc_dtype=acc_dtype, grid_order=grid_order),
            (ext(p), ext(r), D, Dt, ext(g3), mx, my,
             _ax.sstep_extend_zfactor(mz, sz, depth), cx, cy, cz, inv_theta))

    return _chained(make)


def _measure_cheb(grid, n, dtype, acc_dtype, nrhs, depth):
    """Times the Chebyshev-apply kernel at order ``k = depth``."""
    from repro.kernels import nekbone_ax as _ax

    _, r, g3, D, ((mx, my, mz), (cx, cy, cz)) = _slab_operands(grid, n,
                                                               dtype)
    coef = jnp.ones((depth + 1, 2), _ax._accum(jnp.dtype(dtype), acc_dtype))
    Dt = D.T

    def make(sz: int, grid_order: str):
        def ext(f):
            return _ax.sstep_extend_field(f, grid, sz, depth)

        return _device_loop(lambda *a: _ax.nekbone_cheb_apply_pallas(
            *a, n=n, grid=grid, sz=sz, k=depth, interpret=False,
            acc_dtype=acc_dtype, grid_order=grid_order),
            (ext(r), D, Dt, ext(g3), mx, my,
             _ax.sstep_extend_zfactor(mz, sz, depth), cx, cy, cz, coef))

    return _chained(make)


# One row per slab-family kernel kind: ``(halo, measure)``.  The kind names
# its :func:`vmem_bytes` model and its cache keys.  A ``halo`` kind
# marches ``depth`` (s or k) extra slabs on each side of its block, so
# depth joins both its footprint and its key.  ``measure(grid, n, dtype,
# acc_dtype, nrhs, depth)`` builds the timed sweep's ``timed(sz,
# grid_order)`` over the real kernel.
_SLAB_KINDS = {
    "slab": (False, _measure_slab),     # v2 slab kernel (and its b > 1 twin)
    "sstep": (True, _measure_sstep),    # v3 matrix-powers kernel, depth = s
    "cheb": (True, _measure_cheb),      # Chebyshev apply, depth = k
}


def candidate_slab_sizes(kind: str, grid: tuple[int, int, int], n: int,
                         itemsize: int = 4, *, nrhs: int = 1,
                         depth: int = 0, tpu: bool = False) -> list[int]:
    """Slabs-per-block candidates (descending divisors of EZ) of ``kind``.

    A block holds ``sz * EX * EY`` elements, so the VMEM limit caps
    ``sz``; ``sz`` must divide ``EZ`` so every block covers whole slabs
    with no padding.  ``nrhs > 1`` (the multi-RHS block kernels) scales
    the per-RHS residents, and a halo kind's window of ``sz + 2*depth``
    slabs grows with ``depth``, so viable sz shrinks as either grows.
    ``tpu``: owned and halo blocks must also be 128-lane aligned.
    Possibly empty.
    """
    halo, _ = _SLAB_KINDS[kind]
    ex, ey, ez = grid
    halo_lanes = depth * ex * ey if halo else 0
    return [c for c in range(ez, 0, -1) if ez % c == 0 and _fits(
        kind, n, c * ex * ey, itemsize, tpu, nrhs=nrhs,
        halo_lanes=halo_lanes, depth=depth)]


def candidate_configs(sz_cands: list[int]) -> list[tuple[int, str]]:
    """The joint sweep space: every (sz, grid_order) pair.

    Ordered sz-major with the ``parallel`` point first per sz, so a
    measured tie keeps the established configuration.
    """
    from repro.kernels.nekbone_ax import GRID_ORDERS

    return [(sz, go) for sz in sz_cands for go in GRID_ORDERS]


def slab_config(kind: str, grid: tuple[int, int, int], n: int,
                dtype=jnp.float32, *, acc_dtype=None,
                precond: str | None = None, nrhs: int = 1, depth: int = 0,
                sz: int | None = None, grid_order: str | None = None,
                backend: str | None = None,
                measure=None) -> tuple[int, str]:
    """The ``(sz, grid_order)`` a slab-family kernel of ``kind`` runs.

    An explicit ``sz`` passes through, with ``grid_order`` defaulting to
    ``"parallel"``.  Otherwise the pick is one memoized sweep per key
    ``("cfg", kind, n, EX, EY, EZ[, depth], dtype, acc, backend
    [, "pc:<precond>"][, "rhs:<nrhs>"])``: on a TPU backend (or with an
    injected ``measure(sz, grid_order) -> seconds``) every
    :func:`candidate_configs` point is timed by :func:`_sweep`; elsewhere
    the heuristic takes the largest fitting sz, ``parallel``.  A caller
    that fixes ``grid_order`` (its kernel takes none, or pins it) gets
    that order's fastest size from the same sweep by the same
    :data:`SWEEP_MARGIN` rule, so no second sweep runs.

    ``precond`` keys the PCG update kernels apart (the Jacobi update holds
    the diagonal block live, DESIGN.md §9); ``nrhs`` switches the
    footprint and the sweep to the multi-RHS kernels (DESIGN.md §12);
    ``depth`` is a halo kind's s or k.  Keys carry the resolved (storage,
    accum) dtype pair, and candidates are sized in the wider of the two.
    """
    if sz is not None:
        return sz, grid_order or "parallel"
    halo, build = _SLAB_KINDS[kind]
    dtype = jnp.dtype(dtype)
    backend = backend or jax.default_backend()
    acc_name = _acc_name(dtype, acc_dtype)
    key = (("cfg", kind, n) + tuple(grid) + ((depth,) if halo else ())
           + (dtype.name, acc_name, backend))
    if precond is not None:
        key = key + (f"pc:{precond}",)
    if nrhs != 1:
        key = key + (f"rhs:{nrhs}",)
    # as in pick_block_e: VMEM residency is in the accumulation dtype
    size_item = max(dtype.itemsize, jnp.dtype(acc_name).itemsize)
    configs = candidate_configs(candidate_slab_sizes(
        kind, grid, n, size_item, nrhs=nrhs, depth=depth,
        tpu=backend == "tpu"))

    def pick() -> tuple:
        if not configs:
            raise _no_fit(kind, f"grid {tuple(grid)} (n={n}, b={nrhs}, "
                          f"depth={depth})")
        m = measure
        if m is None and backend == "tpu":
            m = build(grid, n, dtype, acc_dtype, nrhs, depth)
        if m is None:
            return configs[0], None
        return _sweep(key, configs, m)

    best = _cached_pick(key, pick)
    if grid_order is None:
        return best
    with _LOCK:
        seconds = _SECONDS.get(key)
    if seconds is None:                # a heuristic pick: largest sz
        return best[0], grid_order
    same = [c for c in configs if c[1] == grid_order]
    return _fastest(same, [seconds[_config_name(c)] for c in same])


# ---------------------------------------------------------------------------
# pipeline dispatch (NekboneCase ax_impl="auto"): measured-fastest pipeline
# per (backend, case key), with a documented E-threshold fallback
# ---------------------------------------------------------------------------

# Below this element count the v2 two-kernel slab pipeline loses to the v1
# single-call kernel on every backend we have measured: v2's fixed
# per-iteration overhead (a second pallas dispatch + the boundary-plane
# stitch between them) is amortized over E elements, and under ~16
# elements the amortization no longer covers it — the ROADMAP-cited
# E=8 inversion (3206 us v2 vs 2596 us v1 on the quick backend).  The
# heuristic only applies where wall time cannot be measured honestly
# (non-TPU backends run kernels in interpret mode); on TPU the dispatch is
# measured and cached instead.
AUTO_V2_MIN_E = 16


def _default_measure_pipeline(grid: tuple[int, int, int], n: int, dtype,
                              acc_dtype=None) -> Callable[[str], float]:
    """Times a one-iteration solve of each pipeline on the real case shape
    (manufactured right-hand side), through the :data:`repro.core.solvers.
    REGISTRY` row ``solve_case`` runs for it, so each candidate pays the
    per-solve host work its route pays: the case's one-time field check
    falls in the warm-up call."""
    from repro.core import solvers
    from repro.core.nekbone import NekboneCase

    case = NekboneCase(n=n, grid=grid, dtype=dtype)
    _, b = case.manufactured()

    def measure(pipeline: str) -> float:
        case.ax_impl = pipeline
        drive = solvers.REGISTRY[solvers.route_name(case, niter=1)]

        def f():
            return drive(case, b, b=1, niter=1, tol=None, max_iter=1,
                         pc_name=None).x

        return _timing.measure(f, reps=3, warmup=1)

    return measure


def pick_pipeline(grid: tuple[int, int, int], n: int, dtype=jnp.float32, *,
                  acc_dtype=None, backend: str | None = None,
                  precond: str | None = None, measure=None) -> str:
    """The measured-fastest fused-CG pipeline for a case, memoized.

    Returns an ``ax_impl`` name: ``"pallas_fused_cg"`` (v1) or
    ``"pallas_fused_cg_v2"``.  Preconditioned cases always resolve to v2 —
    the fused PCG drivers only exist there (DESIGN.md §9).  On TPU (or
    with an explicit ``measure(pipeline) -> seconds``) both pipelines are
    timed on the real case shape and the faster wins, persisted per
    backend; elsewhere the documented :data:`AUTO_V2_MIN_E` threshold
    decides (small E -> v1, the amortization argument above).
    """
    dtype = jnp.dtype(dtype)
    backend = backend or jax.default_backend()
    ex, ey, ez = grid
    if precond is not None:
        return "pallas_fused_cg_v2"
    acc_name = _acc_name(dtype, acc_dtype)
    key = ("pipeline", n, ex, ey, ez, dtype.name, acc_name, backend)

    def pick() -> tuple:
        m = measure
        if m is None and backend == "tpu":
            m = _default_measure_pipeline(grid, n, dtype, acc_dtype)
        if m is None:
            small = ex * ey * ez < AUTO_V2_MIN_E
            return ("pallas_fused_cg" if small
                    else "pallas_fused_cg_v2"), None
        seconds = {c: m(c) for c in ("pallas_fused_cg", "pallas_fused_cg_v2")}
        return min(seconds, key=seconds.get), seconds

    return _cached_pick(key, pick)


# ---------------------------------------------------------------------------
# cache maintenance
# ---------------------------------------------------------------------------

def clear_cache(*, disk: bool = True) -> None:
    """Forget all memoized selections; also removes the disk cache unless
    ``disk=False`` (tests use that to exercise the reload path)."""
    global _DISK_LOADED
    with _LOCK:
        _CACHE.clear()
        _SECONDS.clear()
        _DISK_LOADED = False           # next pick re-merges the file, if any
        if disk:
            try:
                cache_path().unlink(missing_ok=True)
            except Exception:
                pass


def cache_info() -> dict[tuple, int]:
    """Snapshot of the memoized selections (for tests / diagnostics)."""
    with _LOCK:
        return dict(_CACHE)

