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

    def make_case():
        return NekboneCase(n=4, grid=(2, 2, 2), dtype=jnp.float32,
                           ax_impl="pallas_fused_cg_v2", precond="jacobi")

    _, f = make_case().manufactured()
    jax.block_until_ready(make_case().solve(f, tol=1e-3, max_iter=4).x)
    case = make_case()        # fresh: its box fields are not checked yet
    names = ("solve", "driver.prepare", "driver.validate")
    totals = [trace.span_totals()]
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            jax.block_until_ready(case.solve(f, tol=1e-3, max_iter=4).x)
            totals.append(trace.span_totals())
    finally:
        jax.profiler.stop_trace()
    before, first, second = totals

    assert set(names) <= set(_host_event_names(tmp_path))
    # the first solve checks the case's fields: validation inside
    # preparation inside the solve
    assert _delta(first, before, "solve", "count") == 1
    assert _delta(first, before, "driver.validate", "count") == 1
    ns = {name: _delta(first, before, name, "ns") for name in names}
    assert 0 < ns["driver.validate"] <= ns["driver.prepare"] <= ns["solve"]
    # the repeated solve reuses them: prepared, not validated again
    assert _delta(second, first, "solve", "count") == 1
    assert _delta(second, first, "driver.prepare", "count") >= 1
    assert _delta(second, first, "driver.validate", "count") == 0
    # the session is over: spans are off again
    assert trace.span("solve") is trace.NULL_SPAN
    assert trace.span_totals() == second


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
