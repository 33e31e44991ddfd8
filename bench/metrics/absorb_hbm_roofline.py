"""absorb_hbm_roofline (layer: model step, device): the least time the
batch-1 steps that absorb prompts one token each could take at the chip's
HBM bandwidth, over their device time in the traced stretch.

Least bytes of one absorption step: every weight but the routed experts
once, of the routed experts only the held ones the step's tokens were
routed to (the engine's ``moe_experts_hit`` count, carried by each
``prefill`` span), the K/V of the positions the slot holds and the row it
writes, and its recurrent state read and written (``bench/cost.py``).  The
bytes per step are averaged over the prompts whose ``prefill`` span starts
in the traced stretch.  None where the program counts no expert routing or
prompts go through a prefill program."""

from bench import cost
from bench.trace import absorb_steps, decode_variants, program_time


def _bytes(model, prompt_len, hit):
    """Least bytes of absorbing a prompt of ``prompt_len`` tokens whose
    steps hit ``hit`` held experts in all: ``cost.decode_bytes`` of each
    step (step ``k`` attends ``k + 1`` positions, so the sum has a closed
    form), with the measured hit experts in place of the expected ones."""
    expert = cost.KINDS["experts"].expert(model) * cost.BF16
    expected = cost.expected_hit(model, 1) * prompt_len
    steps = (prompt_len * cost.decode_bytes(model, [0])
             + cost.kv_bytes_per_position(model)
             * prompt_len * (prompt_len + 1) // 2)
    return steps + (hit - expected) * expert


def read(rec):
    red, model = rec["trace"], rec["model"]
    if red is None or rec["peaks"] is None or program_time(red, "jit_prefill")[1]:
        return None
    lo, hi = rec["trace_window"]
    pre = [e for e in rec["events"] if e.get("kind") == "span"
           and e.get("name") == "prefill" and "moe_experts_hit" in e
           and lo <= e["t"] < hi]
    if not pre:
        return None
    batched = any(e.get("kind") == "span" and e.get("name") == "decode_step"
                  and e["t"] < hi and e["t"] + e["dur_s"] > lo
                  for e in rec["events"])
    if batched:
        t, n = absorb_steps(red)
    else:       # no batched step in the stretch: every decode step absorbs
        v = decode_variants(red)
        t, n = sum(x["device_s"] for x in v), sum(x["count"] for x in v)
    if not t:
        return None
    per_step = (sum(_bytes(model, e["prompt_len"], e["moe_experts_hit"])
                    for e in pre) / sum(e["prompt_len"] for e in pre))
    return 100.0 * per_step * n / rec["peaks"]["hbm_bytes_per_s"] / t
