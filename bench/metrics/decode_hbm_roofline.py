"""decode_hbm_roofline (layer: model step, device): the least time the
batched decode steps of the traced stretch could take at the chip's HBM
bandwidth, over their device time.  Bytes per step are those
``bench/cost.py`` says the step needs for the slots it served."""

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
    per_step = sum(cost.decode_bytes(rec["model"], k) for k in keys) / len(keys)
    return 100.0 * per_step * n / rec["peaks"]["hbm_bytes_per_s"] / t
