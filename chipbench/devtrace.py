"""Reduction of a JAX profiler trace to device busy time and its parts.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps,
for every TPU device plane, the events of its ``XLA Ops`` line, and the
host's events.  :func:`summarize` reduces that to what the per-layer
metrics read:

* ``busy_ns``: the union of the device's op intervals;
* ``pallas_ns``: the own time of the program's Pallas kernels, the ops
  named by the ``nekbone_`` prefix every kernel's ``name=`` carries;
* ``collective_ns``: all-reduce, all-gather and collective-permute ops;
* ``other_ns``: every other op (XLA fusions, copies, layout conversions).

An op's own time leaves out the ops nested in it: on a TPU's ``XLA Ops``
line a ``while`` op spans the ops of its body.  Events are named by their
HLO instruction (``nekbone_ax_slab_n8_sz4.6``, ``copy.44``), and the
breakdown sums them by name without the instance number;
* the ops that took most time, and the longest idle gaps, each named by
  the host event that covers most of it.

The loaded form is plain JSON (``{"window_ns", "devices": {name: [[op,
start, dur, kernel], ...]}, "host": [[name, start, dur], ...]}``), which
the tests keep as a fixture.
"""
from __future__ import annotations

import glob
import os

KERNEL_PREFIX = "nekbone_"
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "reduce-scatter", "all-to-all")


def op_name(event_name: str) -> str:
    """``nekbone_ax_slab_n8_sz4.6`` from the event's HLO text
    ``%nekbone_ax_slab_n8_sz4.6 = (...) custom-call(...)``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def base_name(op: str) -> str:
    """An op's name without its instance number: ``copy`` for ``copy.44``."""
    head, _, tail = op.rpartition(".")
    return head if head and tail.isdigit() else op


def latest_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, window_ns: float) -> dict:
    """Device op events and host events of one trace file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name.split(":")[-1].isdigit():
            ops = [line for line in plane.lines if line.name == "XLA Ops"]
            if not ops:
                names = [line.name for line in plane.lines]
                raise ValueError(f"{plane.name}: no 'XLA Ops' line in {names}")
            devices[plane.name] = [
                [op, ev.start_ns, ev.duration_ns,
                 op.startswith(KERNEL_PREFIX)]
                for ev in ops[0].events for op in [op_name(ev.name)]]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend([ev.name, ev.start_ns, ev.duration_ns]
                            for ev in line.events)
    return {"window_ns": window_ns, "devices": devices, "host": host}


def union_ns(intervals) -> tuple[float, list]:
    """Total length of the union of ``(start, end)`` intervals, and the
    merged intervals in order."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


def self_times(evs) -> list[float]:
    """Each op's own time: its duration less that of the ops nested in it
    (a ``while`` op spans its body's ops on the same line)."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][1], -evs[i][2]))
    own = [float(e[2]) for e in evs]
    stack: list[int] = []
    for i in order:
        start = evs[i][1]
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= evs[i][2]
        stack.append(i)
    return own


def summarize(trace: dict, top: int = 10) -> dict:
    """Per-device sums, their means over devices, the ``top`` ops by time
    and the ``top`` longest idle gaps of the first device."""
    per_dev = {}
    op_time: dict[str, float] = {}
    gaps = []
    for i, (dev, evs) in enumerate(sorted(trace["devices"].items())):
        busy, merged = union_ns((s, s + d) for _, s, d, _ in evs)
        own = self_times(evs)
        pallas = coll = other = 0.0
        for (name, _, _, k), t in zip(evs, own):
            if k:
                pallas += t
            elif _is_collective(name):
                coll += t
            else:
                other += t
            key = base_name(name)
            op_time[key] = op_time.get(key, 0.0) + t / len(trace["devices"])
        per_dev[dev] = {"busy_ns": busy, "pallas_ns": pallas,
                        "collective_ns": coll, "other_ns": other,
                        "ops": len(evs)}
        if i == 0:
            gaps = [(a[1], b[0]) for a, b in zip(merged[:-1], merged[1:])]
    n = max(1, len(per_dev))
    mean = {k: sum(d[k] for d in per_dev.values()) / n
            for k in ("busy_ns", "pallas_ns", "collective_ns", "other_ns")}
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {"devices": per_dev, "mean": mean, "n_devices": len(per_dev),
            "window_ns": trace["window_ns"],
            "top_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
            "idle_gaps": [(_host_cover(trace["host"], s, e), e - s)
                          for s, e in gaps[:top]]}


def _host_cover(host: list, s: float, e: float) -> str:
    """What the host was doing in the gap ``[s, e)``: the shortest host
    event that covers half of it or more (the innermost such span), else
    the one that overlaps it most."""
    best, best_key = "idle", (False, 0.0, 0.0)
    for name, hs, hd in host:
        ov = min(e, hs + hd) - max(s, hs)
        if ov <= 0:
            continue
        key = (2 * ov >= e - s, -hd if 2 * ov >= e - s else ov, -hd)
        if key > best_key:
            best, best_key = name, key
    return best
