#!/usr/bin/env python3
"""Find the load an open-loop cell can carry: one run per vehicle count.

    python3 benchmarks/chip/sweep.py --workload <name> --seed <n> \\
        --seconds <s> --vehicles <n> [<n> ...]

Runs the cell's traffic mix once for each vehicle count, in this one
process, and prints one JSON line per count with the end-to-end metrics
(the turnaround lines on standard error show whether the tail grows over
the window).  The cell's own traffic file is not changed: the count a
sweep finds is written there by hand, at about four fifths of the highest
count that keeps the p95 turnaround inside the segment without a growing
backlog.
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
    ap.add_argument("--vehicles", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from chipbench import harness, spec
    t = T_START
    for n in args.vehicles:
        cell = spec.resolve(args.workload)
        cell.traffic["vehicles"] = n
        cell.end_to_end = [{"name": k, "unit": u} for k, u in (
            ("frames_per_s", "frames/s"), ("turnaround_p50_ms", "ms"),
            ("turnaround_p95_ms", "ms"), ("setup_s", "s"))]
        try:
            res = harness.run(cell, args.seed, args.seconds, False,
                              t_start=t)
        except harness.NoChip as e:
            print(f"sweep.py: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"vehicles": n, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          **{k: v["value"] for k, v in
                             res["metrics"].items()}}), flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
