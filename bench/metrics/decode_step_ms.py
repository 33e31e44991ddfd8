"""decode_step_ms (layer: engine, serve/engine.py): mean length of the
engine's ``decode_step`` span over the window (the batched step, the
logits' copy to the host and the host's argmax)."""

from bench.steps import spans


def read(rec):
    s = spans(rec["events"], "decode_step", *rec["window"])
    return 1e3 * sum(e["dur_s"] for e in s) / len(s) if s else None
