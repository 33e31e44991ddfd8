"""Run one cell of the chip benchmark and print its result.

    python bench/run.py --workload phi4.chat --seed 7 --seconds 51 --trace 0

One process, one chip per the cell.  With ``--trace 0`` the result holds
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
from a profiler trace of part of the window.  The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``[, ``breakdown``], ``checks``); the numbers compared
for ``correct`` are also the last lines of standard error.  Without the
chips the cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), log=lambda m: print(m, flush=True))
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
