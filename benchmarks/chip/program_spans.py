#!/usr/bin/env python3
"""Traced windows with the program's own spans on the device trace's clock.

    python3 benchmarks/chip/program_spans.py --workload <name> \\
        --seconds <s> --seeds <n> [<n> ...] [--tracer on|off ...] \\
        [--sample-every <k>] [--keep <dir>] [--out <file>]

Runs the cell once per seed in this one process, each a ``--trace 1`` run
of the harness (the profiler records the device over the window).  Where
``--tracer`` says ``on`` for that run (the list repeats to the seeds'
length), the program's ``SpanTracer`` is attached to the fleet at the
window's start and detached at its end (``FleetGateway.attach_obs``);
``off`` runs the same traced run without it, which prices the tracer;
so does ``--sample-every 2``, within one window: the line then gives the
host time of the ticks the tracer recorded and of those it skipped.
Prints one JSON line a run: the cell's per-layer metrics and, with the
tracer on, what ``chipbench/spans.py`` reads off the spans (the idle
split inside ``gateway.tick``, each fused program against its dispatch
span, the anchors, the stalls) and the values of the readers
``metrics/{stage_ms,commit_ms,upload_lag_ms,readback_lag_ms}.py``.
``--keep`` keeps each run's device trace, the driver's host spans and
the program's span export there; ``--out`` appends the lines to a file.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]

READERS = ("stage_ms", "commit_ms", "upload_lag_ms", "readback_lag_ms")


@contextlib.contextmanager
def window_tracing(on: bool, sample_every: int = 1):
    """While open, the harness's driver attaches a fresh ``SpanTracer``
    over the measured window (the ``run`` call with a finite window
    start) when ``on``; yields a namespace whose ``export`` is then the
    tracer's ``to_chrome()``, taken just after it is detached, and whose
    ``host_s`` holds each window tick's wall time less its dispatch,
    under ``True`` where the tracer recorded the tick."""
    from chipbench import drive
    from repro.obs import NULL_TRACER, SpanTracer

    got = SimpleNamespace(export=None, host_s={True: [], False: []})
    base = drive.Driver

    class Driver(base):
        tracer = None

        def run(self, until, window_from, rec):
            if not (on and math.isfinite(window_from)):
                return super().run(until, window_from, rec)
            self.tracer = SpanTracer(sample_every=sample_every)
            self.gw.attach_obs(tracer=self.tracer)
            try:
                return super().run(until, window_from, rec)
            finally:
                self.gw.attach_obs(tracer=NULL_TRACER)
                got.export = self.tracer.to_chrome()
                self.tracer = None

        def tick(self, rec):
            if self.tracer is None:
                return super().tick(rec)
            lead = (self.gw.live_replicas() or self.gw.replicas)[0]
            sampled = lead.ticks % sample_every == 0
            n = len(rec.ticks)
            super().tick(rec)
            if len(rec.ticks) > n:
                wall, dispatch = rec.ticks[-1]
                got.host_s[sampled].append(wall - dispatch)

    drive.Driver = Driver
    try:
        yield got
    finally:
        drive.Driver = base


def reduce_run(cell, keep: Path, export):
    """The span reduction of a run whose trace ``harness.run`` kept in
    ``keep``, or None."""
    from chipbench import spans, trace
    window, host, ticks = trace.load_host(keep / f"{cell.name}.host.json")
    return spans.reduce(keep / f"{cell.name}.xplane.pb", cell.chips,
                        window, host, ticks, export)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--tracer", nargs="+", choices=("on", "off"),
                    default=["on"])
    ap.add_argument("--sample-every", type=int, default=1)
    ap.add_argument("--keep", type=Path, default=None)
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)

    from chipbench import harness, spans, spec
    cell = spec.resolve(args.workload)
    t = T_START
    for seed, mode in zip(args.seeds, itertools.cycle(args.tracer)):
        with contextlib.ExitStack() as stack:
            if args.keep is not None:
                keep = args.keep / f"{seed}-{mode}"
            else:
                keep = Path(stack.enter_context(tempfile.TemporaryDirectory(
                    prefix="chipbench-spans-")))
            got = stack.enter_context(window_tracing(mode == "on",
                                                     args.sample_every))
            try:
                res = harness.run(cell, seed, args.seconds, True, t_start=t,
                                  keep_trace=keep)
            except harness.NoChip as e:
                print(f"program_spans.py: {e}", file=sys.stderr)
                return 2
            line = {"seed": seed, "tracer": mode, "correct": res["correct"],
                    "metrics": {k: v["value"]
                                for k, v in res["metrics"].items()},
                    "device": res["device"],
                    "idle_gaps": res["breakdown"]["idle_gaps"]}
            for sampled, xs in got.host_s.items():
                if xs:
                    key = "recorded" if sampled else "skipped"
                    line[f"host_ms_{key}"] = 1e3 * sum(xs) / len(xs)
                    line[f"ticks_{key}"] = len(xs)
            if got.export is not None:
                if args.keep is not None:
                    (keep / f"{cell.name}.spans.json").write_text(
                        json.dumps(got.export))
                red = reduce_run(cell, keep, got.export)
                if red is not None:
                    view = SimpleNamespace(spans=red)
                    line["readers"] = {n: getattr(spans, n)(view)
                                       for n in READERS}
                    line["spans"] = spans.summary(red)
        text = json.dumps(line)
        print(text, flush=True)
        if args.out is not None:
            args.out.parent.mkdir(parents=True, exist_ok=True)
            with open(args.out, "a") as f:
                f.write(text + "\n")
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
