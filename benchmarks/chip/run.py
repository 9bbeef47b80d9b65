#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and per-layer metric readers are
found by name from ``BENCHMARK.json`` (``chipbench/spec.py``).  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The last line of standard output is one JSON object; the numbers
that decided ``correct`` are the last lines of standard error.  Exits
non-zero, with no result line, where JAX finds no TPU or fewer chips than
the cell asks for, or where anything fails.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="also copy the trace (.xplane.pb) into this "
                         "directory")
    args = ap.parse_args(argv)

    from chipbench import harness, spec
    cell = spec.resolve(args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             t_start=T_START, keep_trace=args.keep_trace)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
