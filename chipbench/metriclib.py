"""What the metric readers share: counts of the window's work, and the
trace's per-device means.  A reader returns ``None`` when its cell gives it
nothing to read; it never stands a 0 in for a missing number."""
from __future__ import annotations


def items(run) -> list:
    """The window's answered solves."""
    return [r for r in run.loop["solves"] if "iters" in r]


def iterations(run) -> float:
    """CG iterations over all right-hand sides answered in the window."""
    return float(sum(r["iters"] for r in items(run)))


def device_mean_ns(run, key: str) -> float | None:
    """Mean over the traced devices of one summed quantity, or ``None``
    without a device trace."""
    t = run.trace
    if t is None or not t["n_devices"]:
        return None
    return t["mean"][key]


def per_iter_ms(run, key: str) -> float | None:
    ns = device_mean_ns(run, key)
    it = iterations(run)
    if ns is None or not it:
        return None
    return ns / 1e6 / it


def idle_share(run) -> float | None:
    busy = device_mean_ns(run, "busy_ns")
    if busy is None:
        return None
    return 1.0 - busy / run.trace["window_ns"]


def roofline_pct(run) -> float | None:
    """The problem's least time over the device's busy time, in %: each
    chip's share of the work against that chip's busy time, the shares
    averaged over the chips."""
    import roofline

    t = run.trace
    if t is None or not t["n_devices"] or run.peak is None:
        return None
    chips = t["n_devices"]
    t_roof, _ = roofline.t_roof_s(
        dofs=run.dofs / chips, n=run.n, iterations=iterations(run),
        solves=len(items(run)), peak=run.peak)
    shares = [t_roof / (d["busy_ns"] / 1e9) for d in t["devices"].values()
              if d["busy_ns"]]
    return 100.0 * sum(shares) / len(shares) if shares else None
