#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload mam_bench.ground --seed 7 --seconds 10 \
        --trace 0

The cell, its configuration, its traffic and its metrics come from
``BENCHMARK.json`` and the files under ``bench/``. The run needs as many
TPU chips as the cell names: without them it exits non-zero and prints no
result. ``--control`` judges the bfloat16 control (the reference in the
place of the program, one precision lower) by the same checks and limits
instead of the program, so the run must come out as not correct; the
benchmark's own runs leave it off.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

_HERE = Path(__file__).resolve().parent
# Run as a script, this directory would shadow the standard library (the
# trace module); the repository root and the program's sources go first.
if sys.path and Path(sys.path[0]).resolve() == _HERE:
    sys.path.pop(0)
sys.path[:0] = [str(_HERE.parent), str(_HERE.parent / "src")]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    from bench import harness

    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), control=args.control)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
