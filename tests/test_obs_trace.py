"""obs/trace.py: recorder lifecycle, span records, JSONL schema.

jax-free on purpose — the trace surface must import and run without jax
so log consumers (and the tracing-off hot path) never pay for it.
"""
import json

import numpy as np
import pytest

from repro.obs import trace


# ---------------------------------------------------------------------------
# off-path contract: no recorder, no allocation
# ---------------------------------------------------------------------------

def test_active_is_none_by_default():
    assert trace.active() is None


def test_module_span_is_null_singleton_when_off():
    s1 = trace.span("anything", attr=1)
    s2 = trace.span("else")
    assert s1 is trace.NULL_SPAN and s2 is trace.NULL_SPAN
    with s1:
        pass  # enters and exits without effect


def test_module_count_gauge_event_noop_when_off():
    trace.count("c")
    trace.gauge("g", 2.0)
    trace.event("e", k=1)  # nothing to assert beyond "does not raise"


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------

def test_recording_activates_and_restores():
    assert trace.active() is None
    with trace.recording() as rec:
        assert trace.active() is rec
        with trace.recording() as inner:   # nested shadows
            assert trace.active() is inner
        assert trace.active() is rec
    assert trace.active() is None


def test_span_records_on_exit_with_depth_and_attrs():
    with trace.recording() as rec:
        with rec.span("outer", a=1):
            with rec.span("inner"):
                pass
    # completion order: inner closes first
    names = [(r["name"], r["depth"]) for r in rec.records]
    assert names == [("inner", 1), ("outer", 0)]
    outer = rec.records[1]
    assert outer["attrs"] == {"a": 1}
    assert outer["dur_us"] >= 0
    assert outer["type"] == "span"


def test_counters_and_gauges_land_in_summary():
    with trace.recording() as rec:
        rec.count("solves")
        rec.count("solves")
        rec.count("bytes", 7)
        rec.gauge("depth", 3)
        rec.gauge("depth", 1)  # last value wins
    s = rec.summary()
    assert s["counters"] == {"solves": 2, "bytes": 7}
    assert s["gauges"] == {"depth": 1}
    assert s["spans"] == 0 and s["events"] == 0


def test_lines_are_valid_jsonl_with_header_and_summary():
    with trace.recording(meta={"case": "unit"}) as rec:
        with rec.span("s", x=2):
            rec.event("ev", y=np.int64(3))  # numpy attrs must serialize
    lines = rec.lines()
    head = json.loads(lines[0])
    tail = json.loads(lines[-1])
    assert head["type"] == "header"
    assert head["schema"] == trace.TRACE_SCHEMA
    assert head["meta"] == {"case": "unit"}
    assert set(head["provenance"]) >= {"machine", "python"}
    assert tail["type"] == "summary"
    assert tail["spans"] == 1 and tail["events"] == 1
    assert trace.validate_trace_lines(lines) == []


def test_write_and_validate_file(tmp_path):
    path = tmp_path / "sub" / "t.trace.jsonl"
    with trace.recording(path) as rec:
        with rec.span("s"):
            pass
    assert path.exists()  # parent dir created
    assert trace.validate_trace_file(path) == []
    # validation actually rejects: clobber the header schema
    lines = path.read_text().splitlines()
    head = json.loads(lines[0])
    head["schema"] = "not-a-trace/9"
    path.write_text("\n".join([json.dumps(head)] + lines[1:]) + "\n")
    assert trace.validate_trace_file(path) != []


def test_recording_writes_file_on_exception(tmp_path):
    path = tmp_path / "fail.trace.jsonl"
    with pytest.raises(RuntimeError):
        with trace.recording(path) as rec:
            with rec.span("doomed"):
                pass
            raise RuntimeError("solve blew up")
    assert path.exists()  # a failing solve still leaves its evidence
    assert trace.validate_trace_file(path) == []


# ---------------------------------------------------------------------------
# provenance
# ---------------------------------------------------------------------------

def test_machine_tag_is_hostname_free():
    import platform

    tag = trace.machine_tag()
    assert platform.node() not in tag or platform.node() == ""
    assert tag.startswith(platform.system().lower())


def test_provenance_keys():
    prov = trace.provenance()
    assert {"machine", "python", "backend"} <= set(prov)
