"""``absorb_hbm_roofline`` on a hand-built traced run: the least bytes of
each absorption step (``bench/cost.py``, with the measured hit experts of
the ``prefill`` spans in place of the expected ones) over the device time
of the batch-1 decode steps; silent without expert counts."""

import json

import pytest

from bench import cost, harness

MODEL = json.loads((harness.ROOT / "bench" / "configs" /
                    "granite-4.0-h-small.json").read_text())["model"]


def _rec(spans, absorb=(0.5, 40), batched=(0.2, 4)):
    """A traced run: the decode program compiled at batch 1 (``absorb``:
    device seconds, executions) and at the batch of the slots."""
    programs = {"jit_decode_step": {"device_s": absorb[0] + batched[0],
                                    "count": absorb[1] + batched[1]},
                "jit_decode_step(1)": {"device_s": absorb[0],
                                       "count": absorb[1]},
                "jit_decode_step(2)": {"device_s": batched[0],
                                       "count": batched[1]}}
    events = [{"kind": "span", "name": "prefill", "t": t, "dur_s": 0.3,
               **attrs} for t, attrs in spans]
    events.append({"kind": "span", "name": "decode_step", "t": 11.0,
                   "dur_s": 0.05})
    return {"trace": {"programs": programs}, "events": events,
            "trace_window": (10.0, 18.0), "model": MODEL,
            "peaks": {"hbm_bytes_per_s": 819e9}}


def test_reads_the_least_bytes_of_each_step_over_its_time():
    spans = [(10.5, {"prompt_len": 16, "moe_experts_hit": 30}),
             (12.0, {"prompt_len": 24, "moe_experts_hit": 20}),
             (9.0, {"prompt_len": 500, "moe_experts_hit": 900})]  # before
    expert = cost.KINDS["experts"].expert(MODEL) * cost.BF16
    total = 0.0
    for n, hit in ((16, 30), (24, 20)):
        total += sum(cost.decode_bytes(MODEL, [k + 1]) for k in range(n))
        total += (hit - n * cost.expected_hit(MODEL, 1)) * expert
    want = 100.0 * total / 40 * 40 / 819e9 / 0.5
    got = harness.reader("absorb_hbm_roofline")(_rec(spans))
    assert got == pytest.approx(want, rel=1e-9)
    # fewer hit experts are fewer least bytes
    fewer = [(t, {**a, "moe_experts_hit": 0}) for t, a in spans]
    assert harness.reader("absorb_hbm_roofline")(_rec(fewer)) < got


def test_silent_without_expert_counts():
    """A program whose spans carry no routing counts (every configuration
    before granite's, and the parent commit) reads nothing."""
    read = harness.reader("absorb_hbm_roofline")
    assert read(_rec([(10.5, {"prompt_len": 16})])) is None
    assert read({**_rec([]), "trace": None}) is None
    assert read({**_rec([(10.5, {"prompt_len": 16, "moe_experts_hit": 3})]),
                 "peaks": None}) is None
