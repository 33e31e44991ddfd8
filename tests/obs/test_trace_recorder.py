"""Tracer spans under an injected clock, flight-recorder wraparound and
NDJSON dumps, and the ObsBus wiring that ties them together."""

import io
import json

import pytest

from repro.obs import FlightRecorder, ObsBus, Tracer


class _Clock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


# ---- tracer ------------------------------------------------------------------

def test_event_and_span_timing_under_injected_clock():
    clock, out = _Clock(), []
    tr = Tracer(clock=clock, sinks=[out.append])
    tr.event("request_submitted", uid=1)
    clock.now = 2.0
    with tr.span("prefill", uid=1) as sp:
        clock.now = 2.5
        sp.set(tokens=4)
    assert out[0] == {"kind": "event", "name": "request_submitted",
                      "t": 0.0, "uid": 1}
    assert out[1] == {"kind": "span", "name": "prefill", "t": 2.0,
                      "dur_s": 0.5, "id": 1, "parent": None, "uid": 1,
                      "tokens": 4}


def test_span_end_is_idempotent_and_exception_sets_error_attr():
    clock, out = _Clock(), []
    tr = Tracer(clock=clock, sinks=[out.append])
    sp = tr.span("decode")
    sp.end()
    sp.end()
    assert len(out) == 1
    with pytest.raises(RuntimeError):
        with tr.span("verify"):
            raise RuntimeError("boom")
    assert out[1]["error"] == "RuntimeError"


def test_disabled_tracer_emits_nothing_and_costs_no_sink_calls():
    out = []
    tr = Tracer(enabled=False, sinks=[out.append])
    tr.event("x")
    with tr.span("y") as sp:
        sp.set(a=1)
    assert out == []


def test_add_remove_sink():
    a, b = [], []
    tr = Tracer(clock=_Clock(), sinks=[a.append])
    tr.add_sink(b.append)
    tr.event("one")
    tr.remove_sink(a.append)      # bound methods compare equal by target
    tr.event("two")
    assert [e["name"] for e in a] == ["one"]
    assert [e["name"] for e in b] == ["one", "two"]


# ---- flight recorder ---------------------------------------------------------

def test_wraparound_keeps_last_capacity_events():
    rec = FlightRecorder(capacity=4)
    for i in range(10):
        rec.record({"kind": "event", "name": "e", "t": float(i), "i": i})
    assert len(rec) == 4
    assert rec.total_recorded == 10
    assert rec.dropped == 6
    assert [e["i"] for e in rec.to_list()] == [6, 7, 8, 9]   # oldest first


def test_dump_ndjson_roundtrip_filelike_and_path(tmp_path):
    rec = FlightRecorder(capacity=8)
    rec.record({"kind": "event", "name": "a", "t": 0.0})
    rec.record({"kind": "span", "name": "b", "t": 0.0, "dur_s": 1.0})
    buf = io.StringIO()
    assert rec.dump_ndjson(buf) == 2
    lines = buf.getvalue().strip().split("\n")
    assert [json.loads(ln)["name"] for ln in lines] == ["a", "b"]
    p = tmp_path / "flight.ndjson"
    assert rec.dump_ndjson(p) == 2
    assert [json.loads(ln)["kind"] for ln in p.read_text().splitlines()] \
        == ["event", "span"]


def test_clear_resets_ring_but_not_lifetime_count():
    rec = FlightRecorder(capacity=2)
    rec.record({"a": 1})
    rec.clear()
    assert len(rec) == 0 and rec.total_recorded == 1
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


# ---- bus ---------------------------------------------------------------------

def test_bus_routes_events_into_recorder_and_shares_clock():
    clock = _Clock()
    bus = ObsBus(clock=clock, recorder_capacity=16)
    assert bus.registry.clock is clock
    clock.now = 3.0
    bus.event("guard_detect", bad=2)
    ring = bus.recorder.to_list()
    assert ring == [{"kind": "event", "name": "guard_detect", "t": 3.0,
                     "bad": 2}]


def test_disabled_bus_keeps_registry_live_but_records_nothing():
    bus = ObsBus(enabled=False)
    bus.event("x")
    with bus.span("y"):
        pass
    assert len(bus.recorder) == 0
    bus.registry.counter("c").inc()       # registry still works
    assert "c 1" in bus.render_prometheus()


def test_trace_file_sink_streams_ndjson(tmp_path):
    clock = _Clock()
    bus = ObsBus(clock=clock)
    path = tmp_path / "trace.ndjson"
    bus.attach_trace_file(path)
    bus.event("one", uid=7)
    with bus.span("two"):
        clock.now = 1.0
    with pytest.raises(RuntimeError):
        bus.attach_trace_file(path)       # one sink at a time
    bus.close_trace()
    bus.event("after-close")              # must not land in the file
    rows = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [r["name"] for r in rows] == ["one", "two"]
    assert rows[1] == {"kind": "span", "name": "two", "t": 0.0, "dur_s": 1.0,
                       "id": 1, "parent": None}
