"""prefill_mfu (layer: model step, device): useful FLOPs of the prefills
of the traced stretch (``bench/cost.py``) over the device time of the
prefill programs there times the chip's bf16 peak.  None where the
program absorbs prompts without a prefill program."""

from bench import cost
from bench.steps import work
from bench.trace import program_time


def read(rec):
    if rec["trace"] is None or rec["peaks"] is None:
        return None
    t, _ = program_time(rec["trace"], "jit_prefill")
    lens = work(rec["events"], *rec["trace_window"])["prefills"]
    if not t or not lens:
        return None
    flops = sum(cost.prefill_flops(rec["model"], n) for n in lens)
    return 100.0 * flops / (t * rec["peaks"]["bf16_flops_per_s"])
