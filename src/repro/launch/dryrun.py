import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count at first init); smoke tests and benches do NOT get 512 devices — only
this entry point does.

Per cell this produces a JSON artifact with:
  * ``cost_analysis()``  — per-device HLO flops / bytes accessed,
  * ``memory_analysis()``— per-device buffer sizes (proves it fits),
  * collective bytes     — parsed from the compiled HLO, summed per op kind
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute; result-shape bytes convention, '-done' ops skipped),
  * analytic input footprints (params / optimizer / cache per device).

Artifacts are written incrementally (restartable) to ``artifacts/dryrun``;
``launch/roofline.py`` turns them into EXPERIMENTS.md §Roofline.

Usage:
  python -m repro.launch.dryrun --arch gemma2-27b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--force]
  python -m repro.launch.dryrun --nekbone --mesh single      # paper's own app
"""
import argparse
import json
import pathlib
import re
import time
import traceback

import jax
import jax.numpy as jnp

from jax import set_mesh, shard_map
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get
from repro.configs.specs import input_specs
from repro.distributed import sharding as shd
from repro.launch import steps as St
from repro.launch.analytic import cell_cost
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.models import model as Mdl

ART_DIR = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s*([^=]*?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"([a-z]+\d*)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    out: dict[str, dict[str, float]] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str, op, _ = m.groups()
        if f"{op}-done" in line:
            continue
        b = _shape_bytes(shape_str)
        rec = out.setdefault(op, {"bytes": 0, "count": 0})
        rec["bytes"] += b
        rec["count"] += 1
    return out


def _sharded_bytes(aval, spec, mesh) -> int:
    """Per-device bytes of an array sharded by ``spec`` on ``mesh``."""
    denom = 1
    for entry in (spec or ()):  # PartitionSpec iterates entries
        if entry is None:
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        for a in axes:
            if a in mesh.axis_names:
                denom *= mesh.shape[a]
    return int(np.prod(aval.shape, dtype=np.int64)
               * jnp.dtype(aval.dtype).itemsize // max(denom, 1))


def _tree_device_bytes(avals, specs, mesh) -> int:
    flat_a = jax.tree.leaves(avals)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    return int(sum(_sharded_bytes(a, s, mesh)
                   for a, s in zip(flat_a, flat_s)))


def _memory_analysis_dict(compiled) -> dict | None:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    keys = ["argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes"]
    return {k: int(getattr(ma, k)) for k in keys if hasattr(ma, k)}


def _filter_spec(spec: P, mesh) -> P:
    """Drop axis names the mesh does not have (e.g. 'pod' on single-pod)."""
    def filt(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in mesh.axis_names)
            return kept if kept else None
        return entry if entry in mesh.axis_names else None

    return P(*(filt(e) for e in spec))


def _named(specs, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, _filter_spec(s, mesh)), specs,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
def run_cell(arch: str, shape: str, mesh_kind: str, *,
             verbose: bool = True) -> dict:
    cfg = get(arch)
    cell = SHAPES[shape]
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    shd.set_rules(fsdp_pod=multi and cfg.param_count() > 1e11)

    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
           "kind": cell.kind, "n_devices": mesh.devices.size,
           "params": cfg.param_count(),
           "active_params": cfg.active_param_count(),
           "tokens": cell.global_batch * (cell.seq_len if cell.kind != "decode"
                                          else 1)}

    if shape == "long_500k" and cfg.is_pure_full_attention:
        rec["skipped"] = "pure full attention (sub-quadratic rule)"
        return rec

    with set_mesh(mesh):
        avals, pspecs = input_specs(cfg, cell, mesh)
        params_aval = jax.eval_shape(
            lambda: Mdl.init_params(jax.random.PRNGKey(0), cfg))
        # Serving cells replicate params over the batch axes (TP only) when
        # they fit; >100B archs keep FSDP (EXPERIMENTS.md §Perf).
        dtype_bytes = jnp.dtype(cfg.param_dtype).itemsize
        serve_mode = (cell.kind != "train"
                      and cfg.param_count() * dtype_bytes / 16 < 8e9)
        param_spec = Mdl.param_specs(cfg, params_aval, mesh,
                                     serve=serve_mode)
        rec["serve_param_mode"] = "tp-replicated" if serve_mode else "fsdp"

        t0 = time.time()
        if cell.kind == "train":
            state_aval = jax.eval_shape(
                lambda p: St.TrainState(
                    params=p,
                    mu=jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                        a.shape, jnp.dtype(cfg.opt_moment_dtype)), p),
                    nu=jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                        a.shape, jnp.dtype(cfg.opt_moment_dtype)), p),
                    step=jax.ShapeDtypeStruct((), jnp.int32)),
                params_aval)
            state_spec = St.TrainState(params=param_spec, mu=param_spec,
                                       nu=param_spec, step=P())
            fn = St.make_train_step(cfg)
            metrics_spec = {"loss": P(), "lr": P(), "grad_norm": P(),
                            "step": P()}
            jitted = jax.jit(
                fn,
                in_shardings=(_named(state_spec, mesh),
                              _named(pspecs["batch"], mesh),
                              _named(pspecs["extra"], mesh)),
                out_shardings=(_named(state_spec, mesh),
                               _named(metrics_spec, mesh)),
                donate_argnums=(0,))
            lowered = jitted.lower(state_aval, avals["batch"], avals["extra"])
            rec["state_bytes_per_device"] = _tree_device_bytes(
                state_aval, state_spec, mesh)
        elif cell.kind == "prefill":
            fn = St.make_serve_prefill(cfg, max_len=cell.seq_len)
            from repro.configs.specs import cache_specs, _div
            out_cache_aval = jax.eval_shape(
                lambda: Mdl.init_cache(cfg, cell.global_batch, cell.seq_len))
            cspec = cache_specs(cfg, out_cache_aval, mesh,
                                context_parallel=False)
            logits_spec = P(_div(mesh, cell.global_batch, shd.RULES.dp),
                            None, None)
            # cache out_shardings left to the partitioner: forcing the spec
            # makes GSPMD re-shard the scan carry through an all-gather per
            # layer (measured on whisper; EXPERIMENTS.md §Perf) — inputs are
            # pinned, so the inferred output matches the declared input spec.
            jitted = jax.jit(
                fn,
                in_shardings=(_named(param_spec, mesh),
                              _named(pspecs["tokens"], mesh),
                              _named(pspecs["extra"], mesh)),
                out_shardings=(_named(logits_spec, mesh), None))
            lowered = jitted.lower(params_aval, avals["tokens"],
                                   avals["extra"])
            rec["cache_bytes_per_device"] = _tree_device_bytes(
                out_cache_aval, cspec, mesh)
        else:  # decode
            from repro.configs.specs import _div
            cp = cell.name == "long_500k"
            fn = St.make_serve_step(cfg, context_parallel=cp)
            logits_spec = P(_div(mesh, cell.global_batch, shd.RULES.dp),
                            None, None)
            jitted = jax.jit(
                fn,
                in_shardings=(_named(param_spec, mesh),
                              _named(pspecs["tokens"], mesh),
                              _named(pspecs["cache"], mesh),
                              NamedSharding(mesh, P())),
                out_shardings=(_named(logits_spec, mesh), None),
                donate_argnums=(2,))
            lowered = jitted.lower(params_aval, avals["tokens"],
                                   avals["cache"], avals["index"])
            rec["cache_bytes_per_device"] = _tree_device_bytes(
                avals["cache"], pspecs["cache"], mesh)

        rec["param_bytes_per_device"] = _tree_device_bytes(
            params_aval, param_spec, mesh)
        rec["time_lower_s"] = round(time.time() - t0, 2)

        t1 = time.time()
        compiled = lowered.compile()
        rec["time_compile_s"] = round(time.time() - t1, 2)

        ca = compiled.cost_analysis() or {}
        rec["flops_raw"] = float(ca.get("flops", -1))
        rec["bytes_accessed_raw"] = float(ca.get("bytes accessed", -1))
        rec["transcendentals"] = float(ca.get("transcendentals", -1))
        rec["memory_analysis"] = _memory_analysis_dict(compiled)
        try:
            hlo = compiled.as_text()
        except Exception:
            hlo = lowered.as_text()
        la = analyze_hlo(hlo)             # loop-corrected (see hlo_analysis)
        rec["dot_flops"] = la["dot_flops"]
        rec["collectives"] = la["collectives"]
        rec["collectives_raw"] = collective_bytes(hlo)
        rec["hlo_lines"] = hlo.count("\n")
        cc = cell_cost(cfg, cell, int(mesh.devices.size),
                       param_shards=(16 if rec.get("serve_param_mode")
                                     == "tp-replicated" else None))
        rec["model_flops_total"] = cc.model_flops_total
        rec["model_flops_per_dev"] = cc.model_flops_per_dev
        rec["analytic_hbm_bytes_per_dev"] = cc.hbm_bytes_per_dev
        if verbose:
            print(json.dumps({k: rec[k] for k in
                              ("arch", "shape", "mesh", "dot_flops",
                               "model_flops_per_dev", "bytes_accessed_raw",
                               "time_compile_s")}))
            print("memory_analysis:", rec["memory_analysis"])
            print("collectives:", {k: v["bytes"] for k, v in
                                   rec["collectives"].items()})
    return rec


def run_nekbone(mesh_kind: str, nelt_per_device: int = 1024,
                dtype=jnp.float32) -> dict:
    """Dry-run the paper's own app: sharded Nekbone CG step on the mesh.

    Elements shard along z over ('pod',)+('data',); 'model' participates via
    a second element-block axis fold — Nekbone is pure data-parallel + halo,
    so we flatten (data, model) into the element dimension.

    ``dtype=bfloat16`` is the beyond-paper variant: the operator is
    memory-bound (Eq. 2), so halving every stream doubles the attainable
    roofline; accumulation stays f32 inside the kernel and CG residual
    quality is recovered by iterative refinement (core/cg.py).
    """
    from repro.core.nekbone import NekboneCase

    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    n_dev = int(mesh.devices.size)
    # Global grid: every device's (16,16,4) block stacked along z.
    case = NekboneCase(n=10, grid=(16, 16, 4), dtype=dtype,
                       ax_impl="fused")
    axes = mesh.axis_names
    E = 16 * 16 * 4 * n_dev
    dt = jnp.dtype(dtype)
    u_aval = jax.ShapeDtypeStruct((E, 10, 10, 10), dt)
    g_aval = jax.ShapeDtypeStruct((E, 6, 10, 10, 10), dt)
    m_aval = jax.ShapeDtypeStruct((E, 10, 10, 10), dt)

    espec = P(axes)     # elements sharded over ALL mesh axes (z-major)
    with set_mesh(mesh):
        op = case.sharded_ax_full(axes)

        def cg_iter(u, g, mask, c):
            # one matrix-free CG-style application + the vector ops
            w = shard_map(
                lambda ul, gl, ml: op(ul, gl, ml, (16, 16, 4)),
                mesh=mesh,
                in_specs=(espec, P(axes, None), espec),
                out_specs=espec, check_vma=False)(u, g, mask)
            pap = jnp.sum(w * c * u)
            alpha = 1.0 / pap
            return u + alpha * w, pap

        jitted = jax.jit(cg_iter,
                         in_shardings=(NamedSharding(mesh, espec),
                                       NamedSharding(mesh, P(axes)),
                                       NamedSharding(mesh, espec),
                                       NamedSharding(mesh, espec)))
        t0 = time.time()
        lowered = jitted.lower(u_aval, g_aval, m_aval, m_aval)
        compiled = lowered.compile()
        ca = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        la = analyze_hlo(hlo)
        ndof_dev = E * 1000 // n_dev
        itemsize = dt.itemsize
        rec = {"arch": f"nekbone-{dt.name}", "shape": f"e{E}",
               "mesh": mesh_kind,
               "kind": "cg_iter", "n_devices": n_dev,
               "flops_raw": float(ca.get("flops", -1)),
               "bytes_accessed_raw": float(ca.get("bytes accessed", -1)),
               "dot_flops": la["dot_flops"],
               "collectives": la["collectives"],
               "memory_analysis": _memory_analysis_dict(compiled),
               "time_compile_s": round(time.time() - t0, 2),
               "ndof": E * 1000,
               # paper Eq. 1 / Eq. 2 per device (fp32)
               "model_flops_per_dev": float(ndof_dev * (12 * 10 + 34)),
               "model_flops_total": float(E * 1000 * (12 * 10 + 34)),
               "analytic_hbm_bytes_per_dev": float(30 * ndof_dev * itemsize)}
        print(json.dumps({k: rec[k] for k in ("arch", "shape", "mesh",
                                              "dot_flops",
                                              "bytes_accessed_raw")}))
    return rec


# ---------------------------------------------------------------------------
def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--nekbone", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out-dir", default=str(ART_DIR))
    args = ap.parse_args()

    out_dir = pathlib.Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.nekbone:
        for mk in meshes:
            for dtype in (jnp.float32, jnp.bfloat16):
                rec = run_nekbone(mk, dtype=dtype)
                name = f"nekbone-{jnp.dtype(dtype).name}__{mk}.json"
                (out_dir / name).write_text(json.dumps(rec))
        return

    cells = ([(args.arch, args.shape)] if args.arch and args.shape
             else [(a, s) for a in ARCHS for s in SHAPES])
    failures = []
    for arch, shape in cells:
        for mk in meshes:
            tag = f"{arch}__{shape}__{mk}".replace("/", "_")
            path = out_dir / f"{tag}.json"
            if path.exists() and not args.force:
                print(f"skip (exists): {tag}")
                continue
            print(f"=== {tag} ===", flush=True)
            try:
                rec = run_cell(arch, shape, mk)
            except Exception as e:  # record the failure, keep going
                rec = {"arch": arch, "shape": shape, "mesh": mk,
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]}
                failures.append(tag)
                print(f"FAILED: {tag}: {e}", flush=True)
            path.write_text(json.dumps(rec, indent=1))
            jax.clear_caches()          # keep the sweep's RSS bounded
    if failures:
        print(f"\n{len(failures)} FAILED cells: {failures}")
        raise SystemExit(1)
    print("\nall requested cells OK")


if __name__ == "__main__":
    main()
