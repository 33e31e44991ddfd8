"""The readers of the engine's nested spans and compile events on
hand-built event lists (a window that cuts a span, a run with no compile,
events of a program without the spans), the profiler bridge's spans
nesting, and traced runs at smoke size that print the three metrics."""

import pytest

from bench import harness, steps
from bench.tests import smoke

WINDOW = (10.0, 20.0)


def _span(name, t, dur, id_, parent=None, **attrs):
    return {"kind": "span", "name": name, "t": t, "dur_s": dur, "id": id_,
            "parent": parent, **attrs}


def _rec(events, window=WINDOW, trace_window=(0.0, 0.0)):
    """A record as the harness makes it; an untraced run's profiled
    stretch is (0, 0)."""
    return {"events": events, "window": window, "trace_window": trace_window}


def _steps():
    """Three decode steps: before the window, and two inside it of 40 ms
    and 60 ms with 36 ms and 55 ms of device wait."""
    return [
        _span("device_wait", 9.0, 0.030, 2, 1),
        _span("decode_step", 9.0, 0.050, 1, None, step=0),
        _span("device_wait", 11.001, 0.036, 4, 3),
        _span("decode_step", 11.0, 0.040, 3, None, step=1),
        _span("device_wait", 12.001, 0.055, 7, 6),
        _span("decode_step", 12.0, 0.060, 6, None, step=2),
    ]


def test_decode_host_ms_reader():
    read = harness.reader("decode_host_ms")
    assert read(_rec(_steps())) == pytest.approx((4.0 + 5.0) / 2)
    # steps that overlap the profiled stretch are left out, even in part
    assert read(_rec(_steps(), trace_window=(12.05, 15.0))) == \
        pytest.approx(4.0)
    assert read(_rec(_steps(), trace_window=(11.0, 13.0))) is None
    # a program whose decode steps carry no device_wait child
    old = [{"kind": "span", "name": "decode_step", "t": 11.0, "dur_s": 0.04}]
    assert read(_rec(old)) is None
    assert read(_rec([])) is None


def test_admit_stall_share_reader():
    read = harness.reader("admit_stall_share")
    events = [
        # begins 1 s before the window with two live slots: 2 s inside
        _span("prefill", 9.0, 3.0, 1, live=2, uid=1),
        _span("prefill", 13.0, 1.0, 2, live=0, uid=2),   # nothing stalled
        _span("prefill", 15.0, 0.5, 3, live=1, uid=3),
        # runs past the window's close: 1 s inside
        _span("prefill", 19.0, 4.0, 4, live=3, uid=4),
        _span("prefill", 21.0, 1.0, 5, live=3, uid=5),   # after the window
    ]
    assert read(_rec(events)) == pytest.approx(100 * (2.0 + 0.5 + 1.0) / 10)
    assert read(_rec(events[1:2])) == 0.0
    # prefill spans of a program that does not count live slots
    old = [{"kind": "span", "name": "prefill", "t": 12.0, "dur_s": 1.0,
            "uid": 1, "slot": 0, "prompt_len": 16}]
    assert read(_rec(old)) is None


def test_compiles_in_window_reader():
    read = harness.reader("compiles_in_window")
    steps = [_span("engine_step", 9.0, 0.1, 1), _span("engine_step", 15.0,
                                                       0.1, 2)]
    comp = [{"kind": "event", "name": "jit_compile", "t": t,
             "fn": "jit(prefill)", "seconds": 0.5} for t in (9.05, 15.05,
                                                              15.07, 20.5)]
    assert read(_rec(steps + comp)) == 2
    none = read(_rec(steps))
    assert none == 0 and none is not None
    assert read(_rec(comp)) is None      # a program without engine steps


def test_profiler_bridge_spans_nest():
    """The benchmark's annotating tracer builds its spans directly: they
    still carry ids and parents."""
    out = []
    tr = harness.annotating_tracer(iter(range(100)).__next__)
    tr.add_sink(out.append)
    with tr.span("engine_step"):
        with tr.span("decode_step", step=0):
            with tr.span("device_wait"):
                pass
    s = {e["name"]: e for e in out}
    assert s["engine_step"]["parent"] is None
    assert s["decode_step"]["parent"] == s["engine_step"]["id"]
    assert s["device_wait"]["parent"] == s["decode_step"]["id"]


# ---- traced runs at smoke size --------------------------------------------------

@pytest.fixture
def root(tmp_path, monkeypatch):
    smoke.off_chip(monkeypatch)
    # profile part of the window, as on the chip, so that decode steps
    # outside the profiled stretch remain for decode_host_ms
    monkeypatch.setattr(harness, "TRACE_S", 0.8)
    return smoke.make_root(tmp_path)


@pytest.mark.parametrize("cell,want", [
    ("phi4.chat", {"decode_host_ms", "compiles_in_window", "decode_step_ms"}),
    ("zamba2.chat_closed", {"decode_host_ms", "admit_stall_share",
                            "decode_step_ms", "absorb_ms_per_tok"}),
])
def test_traced_run_prints_the_engine_metrics(root, cell, want):
    out = harness.run(cell, 2 ** 33 + 21, 2.0, True, root=root,
                      log=lambda m: None)
    assert out["correct"], out["checks"]
    assert want <= set(out["metrics"])
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 0 < m["decode_host_ms"] < m["decode_step_ms"]
    if "compiles_in_window" in want:     # every prompt length was warmed
        assert m["compiles_in_window"] == 0
    if "admit_stall_share" in want:
        assert 0 < m["admit_stall_share"] <= 100


def test_rebuilt_decode_rows_match_the_engine(root):
    """Every request the feeder submits has a uid of its own (a closed loop
    submits several at once), so the work rebuilt from the events has, in
    every batched step of the window, the rows the engine reports active."""
    cell = harness.find_cell(harness.load_benchmark(root),
                             "zamba2.chat_closed", root)
    vocab = cell.config["model"]["vocab_size"]
    _, params = harness.make_weights(cell.config, 3, root)
    engine, events = harness.build_engine(cell.config, params,
                                          annotate=False)
    harness.warm_up(engine, cell.mix, vocab, 3)
    win = harness.drive(engine, cell.mix, 3, 2.0, vocab)
    uids = [e["uid"] for e in events
            if e.get("name") == "request_submitted" and e["uid"] >= 0]
    assert len(uids) == len(set(uids)) == len(win.records)
    spans = steps.spans(events, "decode_step", win.t0, win.t1)
    keys = steps.work(events, win.t0, win.t1)["decode_keys"]
    assert spans and [len(k) for k in keys] == [e["active"] for e in spans]
