"""Readings that set a configuration's ``limits.logit_gap``.

    python bench/tools/readings.py --workload phi4.chat --seeds 1,2,3 \
        --control 3 --seconds 12

One process.  For each seed: weights from the seed, the engine warmed, a
window of ``--seconds`` at the cell's own load (the harness's ``drive``), then
the check's sample compared with the float32 reference (the program's
reading, ``logit_gap``).  For the first ``--control`` seeds the same
sample is also read with the int8 control in the program's place
(``control_gap``: the gap, under the reference, of the token the control
puts first).  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from pathlib import Path
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness, records  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--root", default=str(harness.ROOT),
                    help="checkout that holds BENCHMARK.json")
    args = ap.parse_args()
    root = Path(args.root)
    cell = harness.find_cell(harness.load_benchmark(root), args.workload,
                             root)
    harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    model = cell.config["model"]
    vocab = int(model["vocab_size"])
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.time()
        ref, params = harness.make_weights(cell.config, seed)
        engine, _ = harness.build_engine(cell.config, params, annotate=False)
        harness.warm_up(engine, cell.mix, vocab, seed)
        win = harness.drive(engine, cell.mix, seed, args.seconds, vocab)
        engine._state = None
        del engine
        gc.collect()
        picked = harness.sample(win.records, seed)
        out = harness.gap_readings(ref, params, model, picked,
                              control=i < args.control)
        print(json.dumps({"workload": args.workload, "seed": seed, **out,
                          "requests": len(picked),
                          "attempted": len(win.records),
                          "failed": sum(1 for r in win.records
                                        if not records.ok(r)),
                          "seconds": time.time() - t}), flush=True)
        del params, ref
        gc.collect()


if __name__ == "__main__":
    main()
