"""Live fleet dashboard: watch a scenario run through the obs plane.

Drives any scenario from the library with the full observability plane
attached (MetricsRegistry + SpanTracer) and repaints a FleetStatus text
dashboard every N ticks via the runner's read-only ``on_tick`` hook —
per-replica occupancy, backlogs, adaptive gate thresholds, the
fused-dispatch and jit-recompile counters, and the lowest-headroom
vehicle batteries.  The run is bit-identical to an unobserved one (the
obs plane only reads clocks), so what you watch IS the golden behaviour.

    PYTHONPATH=src python examples/fleet_dashboard.py
    PYTHONPATH=src python examples/fleet_dashboard.py \\
        --scenario poisson_churn --every 25 --follow
    PYTHONPATH=src python examples/fleet_dashboard.py \\
        --scenario mixed_serving --trace /tmp/trace.json \\
        --metrics /tmp/metrics.prom

``--follow`` redraws in place (ANSI home+clear) for a top-style live
view; the default appends snapshots.  ``--trace`` dumps the Perfetto/
chrome://tracing JSON at the end; ``--metrics`` dumps the Prometheus
text exposition.

Hierarchical scenarios (``city_scale``: 64 replicas in 8 cells) render
bounded: one aggregate row per cell plus the ``--top-k``
highest-pressure replicas — the repaint stays O(cells + K), not
O(fleet), so ``--follow`` keeps up at 10k streams.
"""
import argparse

from repro.compile_cache import enable_compile_cache
from repro.obs import FleetStatus, MetricsRegistry, SpanTracer
from repro.simulate import get_scenario, list_scenarios
from repro.simulate.runner import ScenarioRunner


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--list", action="store_true",
                    help="list the scenario library and exit")
    ap.add_argument("--scenario", default="golden_churn")
    ap.add_argument("--seed", type=int, default=None,
                    help="override the scenario's seed")
    ap.add_argument("--ticks", type=int, default=None,
                    help="override the scenario's virtual tick count")
    ap.add_argument("--every", type=int, default=20, metavar="N",
                    help="repaint the dashboard every N virtual ticks")
    ap.add_argument("--sample-every", type=int, default=1,
                    help="trace 1 tick in N (1 = trace every tick)")
    ap.add_argument("--follow", action="store_true",
                    help="redraw in place (ANSI) instead of appending")
    ap.add_argument("--top-k", type=int, default=8,
                    help="replica rows to keep when the snapshot is "
                         "bounded (hierarchical or 64+ replica fleets)")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="write the Chrome trace-event JSON here at the "
                         "end (open in https://ui.perfetto.dev)")
    ap.add_argument("--metrics", default="", metavar="PATH",
                    help="write the Prometheus text exposition here")
    args = ap.parse_args()

    if args.list:
        for name, desc in list_scenarios().items():
            print(f"{name:22s} {desc}")
        return

    overrides = {}
    if args.seed is not None:
        overrides["seed"] = args.seed
    if args.ticks is not None:
        overrides["ticks"] = args.ticks
    scenario = get_scenario(args.scenario, **overrides)

    metrics = MetricsRegistry()
    tracer = SpanTracer(sample_every=args.sample_every)
    runner = ScenarioRunner(scenario, metrics=metrics, tracer=tracer)

    def paint(tick: int, r: ScenarioRunner) -> None:
        if tick % args.every:
            return
        energy = {name: (v.energy_j, v.profile.battery_j)
                  for name, v in r.vehicles.items()}
        fs = FleetStatus.from_gateway(r.gw, vehicle_energy=energy,
                                      top_k=args.top_k)
        if args.follow:
            print("\x1b[H\x1b[2J", end="")
        print(f"=== {scenario.name} @ tick {tick}/{scenario.ticks} ===")
        print(fs.render())
        print()

    res = runner.run(on_tick=paint)

    s = res.summary
    print(f"done: {s['ticks']} ticks  {s['joined']} joined  "
          f"{s['adm']} admitted  {s['gate']} gated  "
          f"{s['violations']} violations  digest {res.digest[:12]}")
    print(f"trace: {len(tracer)} events ({tracer.dropped} dropped)   "
          f"metrics: {len(metrics)} instruments")
    print("\nfleet percentiles (sketch-backed):")
    for key, val in sorted(res.ledger.sketch_percentiles().items()):
        print(f"  {key:24s} {val:10.2f}")
    if args.trace:
        tracer.dump(args.trace)
        print(f"wrote {args.trace}")
    if args.metrics:
        with open(args.metrics, "w") as f:
            f.write(metrics.expose())
        print(f"wrote {args.metrics}")


if __name__ == "__main__":
    main()
