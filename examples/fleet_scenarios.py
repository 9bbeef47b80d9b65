"""Run declarative fleet scenarios against the real serving stack.

List the library, run one scenario by name (optionally overriding seed or
tick count), and print its summary, invariant report, ledger table, and
canonical trace digest.  Same seed ⇒ identical digest — reproduce any
reported run exactly:

    PYTHONPATH=src python examples/fleet_scenarios.py --list
    PYTHONPATH=src python examples/fleet_scenarios.py --scenario replica_failure
    PYTHONPATH=src python examples/fleet_scenarios.py \\
        --scenario poisson_churn --seed 7 --ticks 600 --show-trace 12
"""
import argparse

from repro.compile_cache import enable_compile_cache
from repro.simulate import get_scenario, list_scenarios, run_scenario


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
    ap.add_argument("--show-trace", type=int, default=8, metavar="N",
                    help="print the last N trace events")
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
    print(f"scenario {scenario.name} (seed={scenario.seed}, "
          f"ticks={scenario.ticks}): {scenario.description}\n")
    res = run_scenario(scenario)

    s = res.summary
    print(f"joined {s['joined']}  refused {s['refused']}  "
          f"rebinds {s['rebinds']}  battery departures "
          f"{s['battery_departures']}")
    print(f"frames: offered {s['off']}  admitted {s['adm']}  "
          f"gated {s['gate']}  dropped {s['drop']} "
          f"(deadline {s['ddl']})\n")
    print(res.ledger.table())
    if args.show_trace:
        print(f"\nlast {args.show_trace} trace events:")
        print(res.trace.tail(args.show_trace))
    print(f"\ninvariants: {'all held' if res.ok else 'VIOLATED'}")
    for v in res.violations:
        print(f"  !! {v}")
    print(f"trace digest: {res.digest}")
    print(f"reproduce: PYTHONPATH=src python examples/fleet_scenarios.py "
          f"--scenario {scenario.name} --seed {scenario.seed} "
          f"--ticks {scenario.ticks}")


if __name__ == "__main__":
    main()
