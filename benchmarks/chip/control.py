#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program's and the control's.

    python3 benchmarks/chip/control.py --workload <name> --seconds <s> \\
        --seeds <n> [<n> ...]

Runs the cell once per seed in this one process, each with a short window
at the cell's own load, and prints for each seed the numbers the
comparison reads off the program's outputs and off the control's (the
reference at lower precision put in the program's place, on the same kept
ticks), each judged by the configuration's limits.  A sound program comes
out correct and the control does not; the exit code is 1 where either
fails to.  The benchmark's own runs never run the control.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from chipbench import harness, spec
    cell = spec.resolve(args.workload)
    t = T_START
    as_expected = True
    for seed in args.seeds:
        try:
            res = harness.run(cell, seed, args.seconds, False, t_start=t,
                              control=True)
        except harness.NoChip as e:
            print(f"control.py: {e}", file=sys.stderr)
            return 2
        ctl = res["control"]
        as_expected &= res["correct"] and not ctl["correct"]
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "control_correct": ctl["correct"],
            "program": {k: v["value"] for k, v in res["checks"].items()},
            "control": {k: v["value"] for k, v in ctl["checks"].items()}}),
            flush=True)
        t = time.perf_counter()
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())
