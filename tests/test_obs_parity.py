"""Obs-neutrality certificate: the observability plane never perturbs
behaviour.

Tracing reads clocks, metrics update host-side dicts — neither charges
virtual time, touches RNG, or reorders scheduling, so a scenario run
with the full obs plane attached (MetricsRegistry + unsampled
SpanTracer) must produce a BIT-IDENTICAL golden trace digest to an
obs-off run.  These tests pin that against the committed golden pin, on
both the serial and the fused mesh-parallel fleet paths, and sweep the
whole scenario library in the slow (scenario-soak) tier.

They also sanity-check that the obs plane actually observed something:
a parity certificate for a tracer that recorded zero spans would be
vacuous.
"""
import json
import pathlib

import pytest

from repro.obs import FleetStatus, MetricsRegistry, SpanTracer

GOLDEN_PATH = (pathlib.Path(__file__).parent
               / "golden" / "fleet_scenario_v1.json")


def _golden_digest() -> str:
    with open(GOLDEN_PATH) as f:
        return json.load(f)["digest"]


def _obs_run(name: str, *, parallel: bool = False, **kw):
    from repro.simulate import get_scenario, run_scenario
    metrics, tracer = MetricsRegistry(), SpanTracer()
    res = run_scenario(get_scenario(name, **kw), parallel=parallel,
                       metrics=metrics, tracer=tracer)
    return res, metrics, tracer


def test_golden_digest_identical_with_obs_on_serial():
    res, metrics, tracer = _obs_run("golden_churn")
    assert not res.violations, "\n".join(map(str, res.violations))
    assert res.digest == _golden_digest(), (
        "obs-on run drifted from the committed golden pin — the obs "
        "plane perturbed behaviour (it must only read clocks)")
    # non-vacuous: the plane really was live on this run
    assert len(tracer.spans("tick")) > 0
    assert len(tracer.spans("forward")) > 0
    assert any(child.value > 0 for _, child
               in metrics.get("engine_ticks_total")._series())
    assert "engine_tick_ms" in metrics.expose()


def test_golden_digest_identical_with_obs_on_parallel():
    """Same pin through the fused mesh-parallel tick: the obs plane must
    not perturb the shard_map/vmap path either, and the fused-dispatch
    span shows up on the fleet swimlane."""
    res, _, tracer = _obs_run("golden_churn", parallel=True)
    assert not res.violations, "\n".join(map(str, res.violations))
    assert res.digest == _golden_digest()
    for name in ("fleet.tick", "fused_dispatch", "fleet.gather",
                 "fleet.call", "fleet.wait", "fleet.readback",
                 "fleet.commit", "fleet.end", "rebalance", "stage",
                 "commit"):
        assert len(tracer.spans(name)) > 0, name


def test_parallel_obs_export_is_deterministic_per_seed():
    """Under virtual clocks every fleet span, fused_dispatch included,
    takes its start and length from the lead replica's clock: two
    same-seed runs export the same trace, with no clock anchor."""
    exports = [json.dumps(_obs_run("golden_churn", parallel=True)[2]
                          .to_chrome(), sort_keys=True)
               for _ in range(2)]
    assert exports[0] == exports[1]
    assert json.loads(exports[0])["otherData"]["clock_anchors"] == []


def test_sampled_tracer_keeps_digest_on_the_parallel_path():
    """sample_every thins the fleet lane as it thins the replicas'."""
    from repro.simulate import get_scenario, run_scenario
    sampled = SpanTracer(sample_every=8)
    res = run_scenario(get_scenario("golden_churn"), parallel=True,
                       metrics=MetricsRegistry(), tracer=sampled)
    assert res.digest == _golden_digest()
    ticks = sampled.spans("fleet.tick")
    assert ticks and all(e["args"]["tick"] % 8 == 0 for e in ticks)
    assert 0 < len(sampled.spans("fused_dispatch")) <= len(ticks)


def test_sampled_tracer_keeps_digest_and_drops_events():
    """sample_every=N records 1-in-N ticks through the same code path —
    digests still identical, strictly fewer events."""
    from repro.simulate import get_scenario, run_scenario
    full = SpanTracer()
    run_scenario(get_scenario("golden_churn"),
                 metrics=MetricsRegistry(), tracer=full)
    sampled = SpanTracer(sample_every=8)
    res = run_scenario(get_scenario("golden_churn"),
                       metrics=MetricsRegistry(), tracer=sampled)
    assert res.digest == _golden_digest()
    assert 0 < len(sampled.spans("tick")) < len(full.spans("tick"))


@pytest.mark.parametrize("name,span", [("traffic_spike", "tiers"),
                                       ("partitioned_reconnect",
                                        "events.pump")])
def test_gateway_lane_records_the_director_and_the_pump(name, span):
    """The gateway lane holds a span for each tier-director round and
    each event-pump round (the scenario's ticks and its drain), and
    observes only."""
    from repro.simulate import get_scenario, run_scenario
    scenario = get_scenario(name, ticks=30)
    plain = run_scenario(scenario)
    obs, _, tracer = _obs_run(name, ticks=30)
    assert obs.digest == plain.digest
    assert len(tracer.spans(span)) >= 30
    lanes = {e["tid"]: e["args"]["name"] for e in tracer.events
             if e["ph"] == "M"}
    assert {lanes[e["tid"]] for e in tracer.spans(span)} == {"gateway"}


def test_ledger_sketch_parity_on_golden_scenario():
    """End-to-end sketch parity: the scenario ledger's sketch-backed
    percentiles agree with its exact row-backed percentiles within the
    sketch rel_err bound — on real fleet telemetry, not synthetic data."""
    res, _, _ = _obs_run("golden_churn")
    led = res.ledger
    exact = led.percentiles()
    sketch = led.sketch_percentiles()
    for key, want in exact.items():
        got = sketch[key]
        assert abs(got - want) <= 0.0102 * abs(want) + 1e-9, \
            f"{key}: sketch {got} vs exact {want}"


def test_metrics_conservation_against_ledger():
    """The obs invariant the simulator also checks every run: sketch
    counts/sums reconcile with the exact ledger totals."""
    res, _, _ = _obs_run("golden_churn")
    led = res.ledger
    assert led.sketches["turnaround_ms"].count == len(led)
    assert led.sketches["skip_rate"].count == len(led)
    assert led.sketches["ttft_ms"].count == led.totals["ttft_records"]
    exact_sum = sum(r.turnaround_ms for r in led.records)
    assert led.sketches["turnaround_ms"].sum == pytest.approx(exact_sum)


def test_fleet_status_render_after_obs_run():
    from repro.simulate import get_scenario
    from repro.simulate.runner import ScenarioRunner
    metrics, tracer = MetricsRegistry(), SpanTracer()
    runner = ScenarioRunner(get_scenario("mixed_serving"),
                            metrics=metrics, tracer=tracer)
    runner.run()
    fs = FleetStatus.from_gateway(runner.gw)
    text = fs.render()
    assert "token" in text and "vision" in text
    assert fs.token_done > 0
    assert "serve_ttft_ms" in metrics.expose()


@pytest.mark.slow
@pytest.mark.parametrize("name", ["battery_drain", "burst_duplicates",
                                  "deadline_pressure", "heterogeneous_fleet",
                                  "poisson_churn", "replica_failure"])
def test_obs_neutral_across_scenario_library(name):
    """Full-length library sweep (scenario-soak tier): obs-on == obs-off
    digest for every scenario shape — churn, failures, deadlines,
    batteries, bursts."""
    from repro.simulate import get_scenario, run_scenario
    plain = run_scenario(get_scenario(name))
    obs, _, _ = _obs_run(name)
    assert obs.digest == plain.digest, f"{name}: obs plane perturbed run"
    assert not obs.violations
