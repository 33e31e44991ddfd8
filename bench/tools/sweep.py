"""Knee sweep of an open-loop cell: one set-up, one window per rate.

    python bench/tools/sweep.py --workload phi4.chat --rates 1.2,1.6,2.0 \
        --seconds 30 --seed 11

For each rate the cell's mix is served at that rate (same lengths, seeds
``seed + i``) and one JSON line reports TTFT median and p90, ITL p99,
tokens/s, requests attempted and failed, and the backlog: the median TTFT
of the last third of the window's arrivals over that of the first third.
The knee is the highest rate whose backlog does not grow (ratio near 1).
Run on the chip, as one process.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from bench import harness, records  # noqa: E402
from bench.stats import nearest_rank  # noqa: E402


def summary(win: harness.Window) -> dict:
    recs = sorted(win.records, key=lambda r: r["due"])
    tt = [records.ttft(r) for r in recs if r["times"]]
    third = max(1, len(recs) // 3)
    first = [records.ttft(r) for r in recs[:third] if r["times"]]
    last = [records.ttft(r) for r in recs[-third:] if r["times"]]
    gaps = [g for r in recs for g in records.gaps(r)]
    t0, t1 = win.t0, win.t1
    toks = sum(1 for r in recs for t in r["times"] if t0 <= t <= t1)
    return {"attempted": len(recs),
            "failed": sum(1 for r in recs if not records.ok(r)),
            "ttft_p50_s": float(np.median(tt)) if tt else None,
            "ttft_p90_s": nearest_rank(tt, 0.9) if tt else None,
            "itl_p99_s": nearest_rank(gaps, 0.99) if gaps else None,
            "output_tok_s": toks / (t1 - t0),
            "backlog": (float(np.median(last)) / float(np.median(first))
                        if first and last else None)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--root", default=str(harness.ROOT),
                    help="checkout that holds BENCHMARK.json")
    args = ap.parse_args()
    root = Path(args.root)
    cell = harness.find_cell(harness.load_benchmark(root), args.workload,
                             root)
    harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    vocab = int(cell.config["model"]["vocab_size"])
    _, params = harness.make_weights(cell.config, args.seed)
    engine, _ = harness.build_engine(cell.config, params, annotate=False)
    harness.warm_up(engine, cell.mix, vocab, args.seed)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_rps=rate)
        win = harness.drive(engine, mix, args.seed + i, args.seconds, vocab)
        print(json.dumps({"workload": args.workload, "rate_rps": rate,
                          **summary(win)}), flush=True)


if __name__ == "__main__":
    main()
