"""absorb_ms_per_tok (layer: model step, device): device time of the
batch-1 decode steps that absorb prompts one token each, per token, in the
traced stretch.  None where prompts go through a prefill program."""

from bench.trace import absorb_steps, decode_variants, program_time


def read(rec):
    red = rec["trace"]
    if red is None or program_time(red, "jit_prefill")[1]:
        return None
    lo, hi = rec["trace_window"]
    batched = any(e.get("kind") == "span" and e.get("name") == "decode_step"
                  and e["t"] < hi and e["t"] + e["dur_s"] > lo
                  for e in rec["events"])
    if batched:
        t, n = absorb_steps(red)
    else:       # no batched step in the stretch: every decode step absorbs
        v = decode_variants(red)
        t, n = sum(x["device_s"] for x in v), sum(x["count"] for x in v)
    return 1e3 * t / n if n else None
