"""admit_stall_share (layer: engine, serve/engine.py): share of the window
spent in ``prefill`` spans that began with live slots (``live > 0``:
requests that already had their first token and wait while another
prompt is absorbed), each clipped to the window.  None where the
program's ``prefill`` spans do not count live slots."""


def read(rec):
    lo, hi = rec["window"]
    pre = [e for e in rec["events"] if e.get("kind") == "span"
           and e.get("name") == "prefill" and "live" in e]
    if not pre:
        return None
    stalled = sum(max(0.0, min(e["t"] + e["dur_s"], hi) - max(e["t"], lo))
                  for e in pre if e["live"] > 0)
    return 100.0 * stalled / (hi - lo)
