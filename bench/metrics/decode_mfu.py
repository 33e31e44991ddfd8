"""decode_mfu (layer: model step, device): useful FLOPs of the batched
decode steps of the traced stretch (``bench/cost.py``, active slots only)
over their device time times the chip's bf16 peak."""

from bench import cost
from bench.steps import work
from bench.trace import batched_decode


def read(rec):
    if rec["trace"] is None or rec["peaks"] is None:
        return None
    keys = work(rec["events"], *rec["trace_window"])["decode_keys"]
    t, n = batched_decode(rec["trace"])
    if not keys or not t:
        return None
    per_step = sum(cost.decode_flops(rec["model"], k) for k in keys) / len(keys)
    return 100.0 * per_step * n / (t * rec["peaks"]["bf16_flops_per_s"])
