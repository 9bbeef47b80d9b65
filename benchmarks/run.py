"""Benchmark orchestrator: one module per paper table/figure + the
scale/roofline deliverables.  Prints a final ``name,value,derived`` CSV,
optionally writes it to a file and/or a ``BENCH_*.json`` snapshot, and can
gate against a committed baseline (the CI perf gate).

    PYTHONPATH=src python -m benchmarks.run [--only turnaround,...]
        [--csv out.csv] [--json out.json] [--gate BENCH_fleet.json]

Each benchmark module appends ``(name, value, derived)`` rows; a module
that raises is reported and *skipped* — the remaining modules still run,
the partial CSV is still printed, and the exit code goes non-zero at the
end (so CI fails without losing every other module's rows).

JSON schema (``--json``): ``{name: {value, derived, tolerance,
direction}}``.  ``tolerance``/``direction`` come from ``GATE_RULES`` and
say how ``--gate`` compares a current run against the committed baseline:

    higher  regression when value < baseline * (1 - tolerance)
    lower   regression when value > baseline * (1 + tolerance) + floor
    equal   regression when |value - baseline| > tolerance * max(|b|, fl)

Ratio metrics (speedups, parity bits, skip rates) are self-normalising and
get tight tolerances; absolute wall-clock metrics vary wildly across CI
runners and get generous ones.  Metrics without a rule are informational:
recorded in the JSON, never gated.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

from repro.compile_cache import enable_compile_cache

MODULES = ["turnaround", "energy", "esd_sweep", "kernel_micro",
           "serve_bench", "fleet_bench", "scenario_soak",
           "roofline_report"]

# (name-prefix, direction, relative tolerance, absolute floor) — first
# matching prefix wins.  Floors keep near-zero baselines (parity errors)
# from turning a rounding wiggle into a "regression".
GATE_RULES = [
    # correctness bits: exact
    ("fleet_parallel_parity", "equal", 0.0, 0.0),
    ("fleet_mixed_tier_parity", "equal", 0.0, 0.0),
    ("fleet_ingest_parity", "equal", 0.0, 0.0),
    ("fleet_obs_parity", "equal", 0.0, 0.0),
    ("fleet_event_parity", "equal", 0.0, 0.0),
    ("fleet_scale_parity", "equal", 0.0, 0.0),
    ("scenario_soak_deterministic", "equal", 0.0, 0.0),
    ("scenario_soak_violations", "equal", 0.0, 0.0),
    # obs-overhead wall ratio: generous tolerance (tiny CPU workload,
    # registry updates are a visible tick fraction) — catches the obs
    # plane ever turning into a per-tick multiplier
    ("fleet_obs_overhead", "lower", 0.50, 0.0),
    # event-plane wall ratio: same shape as the obs gate — envelope
    # construction + cooldown checks + the per-tick pump are host-side
    # dict work; the gate catches the plane becoming a tick multiplier
    ("fleet_event_overhead", "lower", 0.50, 0.0),
    # spool-drain throughput (partition -> reconnect -> flush): pure
    # host-side dict/deque work, machine-class dependent — catastrophic
    # slowdowns only
    ("fleet_event_drain_eps", "higher", 0.75, 0.0),
    # self-normalising ratios: the core perf-trajectory signals
    ("fleet_parallel_speedup", "higher", 0.30, 0.0),
    ("fleet_batching_speedup", "higher", 0.35, 0.0),
    ("fleet_gate_speedup", "higher", 0.35, 0.0),
    ("serve_batching_speedup", "higher", 0.35, 0.0),
    ("serve_paged_batching_speedup", "higher", 0.35, 0.0),
    # paged-vs-dense greedy token streams must stay identical (within the
    # sliding window, where the contiguous ring is exact)
    ("serve_paged_token_parity", "equal", 0.0, 0.02),
    ("fleet_gate_skip_rate", "equal", 0.15, 0.0),
    ("ingest_bytes_reduction_", "equal", 0.02, 0.0),
    ("ingest_parity_max_abs_err", "lower", 1.0, 1e-5),
    # absolute wall-clock / throughput: the committed baseline and a CI
    # runner are different machine classes, so these only catch
    # catastrophic (several-x) slowdowns — the ratio metrics above are
    # the real per-PR signal
    ("fleet_serial_fps", "higher", 0.75, 0.0),
    ("fleet_parallel_fps", "higher", 0.75, 0.0),
    ("fleet_mixed_tier_fps", "higher", 0.75, 0.0),
    ("fleet_slots", "lower", 2.0, 0.0),
    # hierarchical host time per frame: absolute wall on a CI runner, so
    # catastrophic-only — the in-bench 64r <= 2x 8r sublinearity assert
    # is the hard bar
    ("fleet_host_us_per_frame", "lower", 3.0, 0.0),
    ("fleet_streams", "higher", 0.75, 0.0),
    ("fleet_ingest_", "higher", 0.75, 0.0),
    ("ingest_cpu_3pass", "lower", 3.0, 0.0),
    ("fa_", "lower", 3.0, 0.0),
    # token-engine wall-clock (serve_bench): catastrophic-only, like the
    # other absolute metrics; the esd skip rates stay informational (they
    # depend on measured per-token cost, which is machine-class noise)
    ("serve_decode_us_per_token", "lower", 3.0, 0.0),
    ("serve_paged_decode_us_per_token", "lower", 3.0, 0.0),
    ("serve_ttft_", "lower", 3.0, 0.0),
    ("serve_turnaround_", "lower", 3.0, 0.0),
]


def rule_for(name: str):
    for prefix, direction, tol, floor in GATE_RULES:
        if name.startswith(prefix):
            return direction, tol, floor
    return None


def gate(rows, baseline: dict):
    """Compare current rows to a committed baseline.  Returns (regressions,
    verdict lines).  A gated baseline metric that is *missing* from the
    current run counts as a regression — a refactor that silently stops
    emitting fleet_parallel_speedup must not turn the gate green — so a
    --gate run has to select the same module set the baseline was built
    from (see benchmarks/README.md).  Metrics new in the current run are
    informational (they land with their own fresh baseline)."""
    current = {name: value for name, value, _ in rows}
    regressions, lines = [], []
    for name, entry in sorted(baseline.items()):
        base = float(entry["value"])
        rule = rule_for(name)
        if rule is None:
            continue
        direction, tol, floor = rule
        if name not in current:
            lines.append(f"  REGRESSION {name}: missing from current run "
                         f"(baseline {base:g}) — a gated metric vanished")
            regressions.append(name)
            continue
        cur = float(current[name])
        if direction == "higher":
            bad = cur < base * (1.0 - tol) - floor
        elif direction == "lower":
            bad = cur > base * (1.0 + tol) + floor
        else:
            bad = abs(cur - base) > tol * max(abs(base), floor) \
                + (floor if tol == 0.0 else 0.0)
        verdict = "REGRESSION" if bad else "ok"
        lines.append(f"  {verdict:10s} {name}: {cur:g} vs baseline "
                     f"{base:g} ({direction}, tol {tol:g})")
        if bad:
            regressions.append(name)
    fresh = sorted(set(current) - set(baseline))
    for name in fresh:
        lines.append(f"  NEW        {name}: {current[name]:g} "
                     f"(no baseline yet)")
    return regressions, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated subset of: " + ",".join(MODULES))
    ap.add_argument("--csv", default="",
                    help="also write the CSV rows to this file")
    ap.add_argument("--json", default="",
                    help="write {name: {value, derived, tolerance, "
                         "direction}} snapshot (the BENCH_*.json schema)")
    ap.add_argument("--gate", default="",
                    help="baseline JSON to compare against; exits non-zero "
                         "on any per-metric tolerance regression")
    args = ap.parse_args(argv)
    only = [s for s in args.only.split(",") if s]
    enable_compile_cache()

    rows = []
    failures = []
    for name in (only or MODULES):
        print(f"\n######## {name} ########")
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main(rows)
        except Exception:
            # one failing module must not swallow the others' rows
            traceback.print_exc()
            failures.append(name)
            print(f"[{name}: FAILED after {time.time() - t0:.1f}s — "
                  f"continuing with remaining modules]")
            continue
        print(f"[{name}: {time.time() - t0:.1f}s]")

    print("\n======== CSV ========")
    csv_lines = ["name,value,derived"]
    csv_lines += [f"{name},{value},{derived}" for name, value, derived in rows]
    print("\n".join(csv_lines))
    if args.csv:
        with open(args.csv, "w") as f:
            f.write("\n".join(csv_lines) + "\n")

    if args.json:
        snapshot = {}
        for name, value, derived in rows:
            rule = rule_for(name)
            snapshot[name] = {
                "value": float(value), "derived": str(derived),
                "tolerance": rule[1] if rule else None,
                "direction": rule[0] if rule else None,
            }
        with open(args.json, "w") as f:
            json.dump(snapshot, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"[wrote {len(snapshot)} metrics to {args.json}]")

    regressions = []
    if args.gate:
        with open(args.gate) as f:
            baseline = json.load(f)
        regressions, lines = gate(rows, baseline)
        print(f"\n======== gate vs {args.gate} ========")
        print("\n".join(lines))
        print(f"[{len(regressions)} regression(s), "
              f"{len(failures)} failed module(s)]")

    if failures:
        print(f"\nFAILED modules: {', '.join(failures)}", file=sys.stderr)
    if regressions:
        print(f"PERF REGRESSIONS: {', '.join(regressions)}",
              file=sys.stderr)
    return 1 if (failures or regressions) else 0


if __name__ == "__main__":
    sys.exit(main())
