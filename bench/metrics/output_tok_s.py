"""output_tok_s: tokens the engine emitted inside the window, over the
window's length."""


def read(rec):
    t0, t1 = rec["window"]
    n = sum(1 for r in rec["requests"] for t in r["times"] if t0 <= t <= t1)
    return n / (t1 - t0)
