"""decode_host_ms (layer: engine, serve/engine.py): mean over the window's
``decode_step`` spans of their length less that of their ``device_wait``
child, the host's part of a batched step: dispatch, the logits' copy to
the host, argmax and bookkeeping.  Steps that overlap the profiled
stretch are left out: there every span also opens a profiler annotation.
None where the program's decode steps have no ``device_wait`` child."""

from bench.steps import spans


def read(rec):
    wait = {e["parent"]: e["dur_s"] for e in rec["events"]
            if e.get("kind") == "span" and e.get("name") == "device_wait"}
    lo, hi = rec["trace_window"]
    host = [e["dur_s"] - wait[e["id"]]
            for e in spans(rec["events"], "decode_step", *rec["window"])
            if e.get("id") in wait
            and not (e["t"] < hi and e["t"] + e["dur_s"] > lo)]
    return 1e3 * sum(host) / len(host) if host else None
