"""What the systems share: the program's configuration of a case, its
stopping arguments, and the route inputs and autotune picks it resolved."""
from __future__ import annotations


def stop_kwargs(cfg: dict, tol: float | None) -> dict:
    """The program's stopping arguments: a fixed count, or the absolute
    ``tol`` on its residual measure with ``max_iter``."""
    stop = cfg["stop"]
    if "niter" in stop:
        return {"niter": int(stop["niter"])}
    return {"tol": float(tol), "max_iter": int(stop["max_iter"])}


def program_config(cfg: dict, solver: dict):
    """The program's ``NekboneConfig`` for a benchmark configuration."""
    import jax.numpy as jnp

    from repro.configs.nekbone import NekboneConfig

    if cfg["precision"] != "f32":
        raise ValueError(f"unsupported precision {cfg['precision']!r}")
    return NekboneConfig(
        name=cfg["name"], n=int(cfg["n"]), grid=tuple(cfg["grid"]),
        dtype=jnp.dtype("float32").name, ax_impl=solver["ax_impl"],
        precond=solver.get("precond"), s=int(solver.get("s", 4)))


def describe(case) -> dict:
    """Route inputs the program resolved, and its autotune picks."""
    from repro.kernels import autotune

    return {"ax_impl_requested": case.ax_impl_requested,
            "ax_impl": case.ax_impl,
            "autotune_picks": {"/".join(map(str, k)): v for k, v in
                               sorted(autotune.cache_info().items(),
                                      key=lambda kv: str(kv[0]))}}
