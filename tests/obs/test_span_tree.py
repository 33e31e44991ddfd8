"""Span nesting (ids, parents, one stack per thread, exceptions, tracer
subclasses that build their own spans) and compile events
(``repro.obs.compiles``: one ``jit_compile`` per new shape, attributed
only inside the block that names a bus)."""

import threading

import jax
import numpy as np
import pytest

from repro.obs import ObsBus, Span, Tracer, compiles


class _Clock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        self.now += 1.0
        return self.now


def _spans(out):
    return {e["name"]: e for e in out if e["kind"] == "span"}


def test_ids_and_parents_nest():
    out = []
    tr = Tracer(clock=_Clock(), sinks=[out.append])
    with tr.span("step"):
        with tr.span("prefill"):
            with tr.span("absorb"):
                pass
            with tr.span("inject"):
                pass
        with tr.span("decode"):
            pass
    with tr.span("next"):
        pass
    s = _spans(out)
    assert [e["name"] for e in out] == ["absorb", "inject", "prefill",
                                        "decode", "step", "next"]
    assert len({e["id"] for e in out}) == 6
    assert s["step"]["parent"] is None and s["next"]["parent"] is None
    assert s["prefill"]["parent"] == s["decode"]["parent"] == s["step"]["id"]
    assert s["absorb"]["parent"] == s["inject"]["parent"] == s["prefill"]["id"]
    for e in out:                        # every child inside its parent
        if e["parent"] is not None:
            p = next(q for q in out if q["id"] == e["parent"])
            assert p["t"] <= e["t"]
            assert e["t"] + e["dur_s"] <= p["t"] + p["dur_s"]


def test_threads_keep_separate_stacks():
    out, lock = [], threading.Lock()

    def sink(ev):
        with lock:
            out.append(ev)

    tr = Tracer(sinks=[sink])
    inside, release = threading.Event(), threading.Event()

    def other():
        with tr.span("pump"):
            inside.set()
            release.wait(5)
            with tr.span("pump_child"):
                pass

    t = threading.Thread(target=other)
    with tr.span("loop"):
        t.start()
        inside.wait(5)
        with tr.span("loop_child"):      # the pump's span is open meanwhile
            pass
        release.set()
        t.join(10)
    assert not t.is_alive()
    s = _spans(out)
    assert s["pump"]["parent"] is None and s["loop"]["parent"] is None
    assert s["pump_child"]["parent"] == s["pump"]["id"]
    assert s["loop_child"]["parent"] == s["loop"]["id"]


def test_span_closed_by_an_exception_pops_its_frame():
    out = []
    tr = Tracer(clock=_Clock(), sinks=[out.append])
    with pytest.raises(RuntimeError):
        with tr.span("outer"):
            with tr.span("inner"):
                raise RuntimeError("boom")
    assert tr._open_spans() == []
    with tr.span("after"):
        pass
    s = _spans(out)
    assert s["inner"]["error"] == s["outer"]["error"] == "RuntimeError"
    assert s["after"]["parent"] is None
    # a span ended out of order leaves the others on the stack
    a, b = tr.span("a"), tr.span("b")
    a.end()
    with tr.span("c"):
        pass
    b.end()
    assert _spans(out)["c"]["parent"] == b.id
    assert tr._open_spans() == []


def test_disabled_tracer_builds_no_span():
    tr = Tracer(enabled=False)
    assert not isinstance(tr.span("x"), Span)
    assert tr._open_spans() == []


def test_subclass_that_builds_its_own_spans_gets_ids_and_parents():
    """A tracer that overrides ``span`` and builds a ``Span`` subclass
    directly, as a profiler bridge does, still nests."""
    marks = []

    class MarkedSpan(Span):
        __slots__ = ("_mark",)

        def __init__(self, tracer, name, attrs):
            super().__init__(tracer, name, attrs)
            self._mark = name
            marks.append(("enter", name))

        def end(self):
            if not self._done:
                marks.append(("exit", self._mark))
            super().end()

    class MarkingTracer(Tracer):
        def span(self, name, **attrs):
            if not self.enabled:
                return super().span(name, **attrs)
            return MarkedSpan(self, name, attrs)

    out = []
    tr = MarkingTracer(clock=_Clock(), sinks=[out.append])
    with tr.span("decode_step", step=0):
        with tr.span("device_wait"):
            pass
    s = _spans(out)
    assert s["device_wait"]["parent"] == s["decode_step"]["id"]
    assert s["decode_step"]["parent"] is None and s["decode_step"]["step"] == 0
    assert marks == [("enter", "decode_step"), ("enter", "device_wait"),
                     ("exit", "device_wait"), ("exit", "decode_step")]


# ---- compile events ------------------------------------------------------------

def _compiles(out, fn):
    return [e for e in out if e["name"] == "jit_compile" and e["fn"] == fn]


def test_one_compile_event_per_new_shape_inside_the_block():
    compiles.install()
    bus, out = ObsBus(), []
    bus.tracer.add_sink(out.append)

    def plus_one(x):
        return x + 1

    f = jax.jit(plus_one)
    with compiles.attributed_to(bus):
        f(np.ones(3, np.float32))
        f(np.ones(3, np.float32))        # cached: no lowering
        f(np.ones(5, np.float32))
    f(np.ones(7, np.float32))            # outside the block
    with compiles.attributed_to(bus):    # a lowering on another thread
        t = threading.Thread(target=f, args=(np.ones(9, np.float32),))
        t.start()
        t.join(10)
    assert not t.is_alive()
    got = _compiles(out, "jit(plus_one)")
    assert len(got) == 2
    assert all(e["seconds"] >= 0 for e in got)
