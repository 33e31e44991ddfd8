"""prefill_ms_per_ktok (layer: model step, device): device time of the
prefill programs in the trace over the thousands of prompt tokens the
engine prefilled in the traced stretch.  None where the program absorbs
prompts without a prefill program."""

from bench.steps import work
from bench.trace import program_time


def read(rec):
    if rec["trace"] is None:
        return None
    t, n = program_time(rec["trace"], "jit_prefill")
    toks = sum(work(rec["events"], *rec["trace_window"])["prefills"])
    return 1e3 * t / (toks / 1e3) if t and toks else None
