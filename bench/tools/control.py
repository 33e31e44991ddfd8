"""The control of the check that decides ``correct``, through a whole run.

    python bench/tools/control.py --workload phi4.chat --seeds 1,2,3 \
        --seconds 20

One process.  For each seed, the run the command makes (``harness.run``:
weights, warm-up, a window of ``--seconds`` at the cell's own load, the
check) with the int8 control's first tokens judged in place of the served
ones (``harness.control_compare``).  One JSON line per seed with
``correct`` and every number compared beside its limit; each must read
``correct: false``.  Exits 1 if any does not.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()
    harness.compare = harness.control_compare
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run(args.workload, seed, args.seconds, False,
                          log=lambda m: print(m, file=sys.stderr, flush=True))
        passed += out["correct"]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
