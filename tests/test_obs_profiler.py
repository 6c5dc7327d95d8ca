"""Spans under a ``jax.profiler`` session (obs/trace.py): on the host
plane of the profiler's trace, counted in ``span_totals``; with no session
and no recorder, the shared no-op span."""
import glob

import jax
import jax.numpy as jnp
import jax.profiler

from repro.obs import trace


def _host_event_names(logdir) -> list[str]:
    from jax.profiler import ProfileData

    path = sorted(glob.glob(str(logdir / "plugins" / "profile" / "*" /
                                "*.xplane.pb")))[-1]
    return [ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for ev in line.events]


def _delta(after: dict, before: dict, name: str, field: str) -> int:
    return after.get(name, {}).get(field, 0) - \
        before.get(name, {}).get(field, 0)


def test_span_is_null_with_no_profiler_and_no_recorder():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert trace.active() is None
    before = trace.span_totals()
    sp = trace.span("driver.prepare", driver="fused_v2_tol")
    assert sp is trace.NULL_SPAN
    with sp as inner:
        inner.set(seconds=1.0)
    assert trace.span_totals() == before


def test_a_solve_under_the_profiler_lands_on_its_host_plane(tmp_path):
    from repro.core.nekbone import NekboneCase

    case = NekboneCase(n=4, grid=(2, 2, 2), dtype=jnp.float32,
                       ax_impl="pallas_fused_cg_v2", precond="jacobi")
    _, f = case.manufactured()
    jax.block_until_ready(case.solve(f, tol=1e-3, max_iter=4).x)  # compile
    before = trace.span_totals()
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(case.solve(f, tol=1e-3, max_iter=4).x)
    finally:
        jax.profiler.stop_trace()
    after = trace.span_totals()

    names = _host_event_names(tmp_path)
    for name in ("solve", "driver.prepare", "driver.validate"):
        assert name in names, name
        assert _delta(after, before, name, "count") == 1, name
    ns = {name: _delta(after, before, name, "ns")
          for name in ("solve", "driver.prepare", "driver.validate")}
    # nested: validation inside preparation inside the solve
    assert 0 < ns["driver.validate"] <= ns["driver.prepare"] <= ns["solve"]
    # the session is over: spans are off again
    assert trace.span("solve") is trace.NULL_SPAN
    assert trace.span_totals() == after


def test_under_profiler_and_recorder_a_span_feeds_both(tmp_path):
    before = trace.span_totals()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.recording() as rec:
            with trace.span("autotune.sweep", key="k", candidates=2):
                with trace.span("autotune.measure", config="2/parallel") \
                        as sp:
                    sp.set(seconds=0.5)
    finally:
        jax.profiler.stop_trace()
    after = trace.span_totals()
    assert [(r["name"], r["depth"], r.get("attrs")) for r in rec.records] \
        == [("autotune.measure", 1, {"config": "2/parallel",
                                     "seconds": 0.5}),
            ("autotune.sweep", 0, {"key": "k", "candidates": 2})]
    assert _delta(after, before, "autotune.sweep", "count") == 1
    assert _delta(after, before, "autotune.measure", "count") == 1
    assert {"autotune.sweep", "autotune.measure"} <= set(
        _host_event_names(tmp_path))
