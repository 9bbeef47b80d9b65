"""Observability plane unit tests: sketch, metrics registry, tracer,
and the FleetStatus snapshot.

The sketch properties (rank-statistic error bound, merge == concat) are
the guarantees the fleet roll-up story rests on; the registry tests pin
the get-or-create / label / merge / exposition contracts; the tracer
tests pin sampling, the null fast path, and the bounded-memory drop
behaviour; the FleetStatus tests snapshot a live scenario mid-run.
"""
import json

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_stub import given, settings, strategies as st

from repro.obs import (
    NULL_SPAN,
    NULL_TRACER,
    FleetStatus,
    MetricsRegistry,
    QuantileSketch,
    SpanTracer,
)
from repro.core.telemetry import percentile


# ----------------------------------------------------------------------
# QuantileSketch
# ----------------------------------------------------------------------
def test_sketch_empty_and_single():
    sk = QuantileSketch()
    assert sk.count == 0 and sk.quantile(50) == 0.0 and sk.mean == 0.0
    sk.add(42.0)
    for q in (0, 50, 100):
        assert sk.quantile(q) == pytest.approx(42.0, rel=0.01)
    assert sk.min == sk.max == 42.0 and sk.sum == 42.0


def test_sketch_rejects_bad_input():
    sk = QuantileSketch()
    with pytest.raises(ValueError):
        sk.add(-1.0)
    with pytest.raises(ValueError):
        sk.add(float("nan"))
    with pytest.raises(ValueError):
        sk.add(1.0, count=0)
    with pytest.raises(ValueError):
        sk.quantile(101)
    with pytest.raises(ValueError):
        QuantileSketch(rel_err=0.0)
    with pytest.raises(ValueError):
        QuantileSketch(max_buckets=1)


def test_sketch_zero_bucket_exact():
    """Values at/below min_value land in an exact zero bucket — a fleet
    of 0.0 skip rates must answer p50 == 0.0 exactly."""
    sk = QuantileSketch()
    for _ in range(90):
        sk.add(0.0)
    for _ in range(10):
        sk.add(5.0)
    assert sk.quantile(50) == 0.0
    assert sk.quantile(99) == pytest.approx(5.0, rel=0.02)


@settings(max_examples=30)
@given(st.lists(st.floats(min_value=0.0, max_value=1e6),
                min_size=1, max_size=200),
       st.sampled_from([50.0, 90.0, 95.0, 99.0, 0.0, 100.0]))
def test_sketch_quantile_within_rel_err_of_exact(values, q):
    """Every quantile answer is within rel_err of the exact interpolated
    percentile (the telemetry.percentile convention) — the parity bound
    the ledger aggregate mode depends on."""
    sk = QuantileSketch(rel_err=0.01)
    sk.extend(values)
    exact = percentile(values, q)
    got = sk.quantile(q)
    # + min_value: values in (0, 1e-9] land in the exact-zero bucket
    assert abs(got - exact) <= 0.0101 * abs(exact) + sk.min_value + 1e-12


@settings(max_examples=20)
@given(st.lists(st.floats(min_value=0.0, max_value=1e5), max_size=100),
       st.lists(st.floats(min_value=0.0, max_value=1e5), max_size=100))
def test_sketch_merge_equals_concat(a_vals, b_vals):
    """merge(a, b) is bit-identical to the sketch of the concatenated
    stream — the property that makes per-replica -> fleet roll-up
    loss-free relative to one global sketch."""
    a, b, ab = QuantileSketch(), QuantileSketch(), QuantileSketch()
    a.extend(a_vals)
    b.extend(b_vals)
    ab.extend(a_vals + b_vals)
    a.merge(b)
    assert a.buckets == ab.buckets
    assert a.count == ab.count and a.zero_count == ab.zero_count
    assert a.sum == pytest.approx(ab.sum)
    for q in (0, 50, 95, 100):
        assert a.quantile(q) == pytest.approx(ab.quantile(q))


def test_sketch_merge_rejects_mismatched_rel_err():
    with pytest.raises(ValueError):
        QuantileSketch(rel_err=0.01).merge(QuantileSketch(rel_err=0.02))


def test_sketch_max_buckets_collapse_keeps_tail():
    """The bucket cap collapses LOW buckets: memory stays bounded and
    high quantiles keep the error guarantee."""
    sk = QuantileSketch(rel_err=0.01, max_buckets=64)
    values = [1e-6 * (1.03 ** i) for i in range(500)]
    sk.extend(values)
    assert len(sk.buckets) <= 64
    exact = percentile(values, 99)
    assert sk.quantile(99) == pytest.approx(exact, rel=0.011)


def test_sketch_roundtrip_serialisation():
    sk = QuantileSketch()
    sk.extend([0.0, 1.5, 200.0, 3e4])
    back = QuantileSketch.from_dict(json.loads(json.dumps(sk.to_dict())))
    assert back.buckets == sk.buckets
    assert back.count == sk.count and back.sum == sk.sum
    assert back.quantile(95) == sk.quantile(95)


# ----------------------------------------------------------------------
# MetricsRegistry
# ----------------------------------------------------------------------
def test_registry_get_or_create_and_conflicts():
    m = MetricsRegistry()
    c = m.counter("ticks_total", "ticks")
    assert m.counter("ticks_total") is c
    with pytest.raises(ValueError):
        m.gauge("ticks_total")                 # type conflict
    with pytest.raises(ValueError):
        m.counter("ticks_total", label_names=("engine",))  # label conflict
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_registry_labels_and_reserved():
    m = MetricsRegistry()
    c = m.counter("frames_total", "frames", label_names=("engine",))
    with pytest.raises(ValueError):
        c.inc()                                # parent of a labeled metric
    c.labels(engine="r0").inc(3)
    c.labels(engine="r1").inc(5)
    assert c.labels(engine="r0").value == 3
    with pytest.raises(ValueError):
        c.labels(wrong="x")
    with pytest.raises(ValueError):
        m.histogram("h", label_names=("quantile",))  # exposition-owned


def test_gauge_probe_mode_reads_fresh():
    m = MetricsRegistry()
    g = m.gauge("backlog")
    g.set(4)
    assert g.value == 4.0
    g.dec()
    assert g.value == 3.0
    state = {"n": 7}
    g.set_function(lambda: state["n"])
    assert g.value == 7.0
    state["n"] = 11
    assert g.value == 11.0


def test_registry_merge_semantics():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("c").inc(1)
    b.counter("c").inc(2)
    a.gauge("g").set(5)
    b.gauge("g").set(9)
    a.histogram("h").observe(1.0)
    b.histogram("h").observe(100.0)
    b.counter("only_b").inc(4)
    a.merge(b)
    assert a.counter("c").value == 3            # counters add
    assert a.gauge("g").value == 9              # gauges take incoming
    assert a.histogram("h").count == 2          # sketches merge
    assert a.counter("only_b").value == 4       # union
    b2 = MetricsRegistry()
    b2.gauge("c")
    with pytest.raises(ValueError):
        a.merge(b2)                             # cross-type merge refused


def test_exposition_format():
    m = MetricsRegistry()
    m.counter("ticks_total", "tick count").inc(3)
    h = m.histogram("lat_ms", "latency", label_names=("engine",))
    h.labels(engine="r0").observe(10.0)
    text = m.expose()
    assert "# TYPE ticks_total counter" in text
    assert "ticks_total 3" in text
    assert "# TYPE lat_ms summary" in text
    assert 'lat_ms{engine="r0",quantile="0.5"}' in text
    assert 'lat_ms_count{engine="r0"} 1' in text
    assert text.endswith("\n")


# ----------------------------------------------------------------------
# SpanTracer
# ----------------------------------------------------------------------
class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def now_s(self):
        self.t += 0.001
        return self.t


def test_tracer_spans_and_instants():
    tr = SpanTracer()
    clock = _FakeClock()
    with tr.span(clock, "tick", tid="r0", tick=1):
        with tr.span(clock, "forward", tid="r0"):
            pass
    tr.instant(clock, "admit", tid="r0", n=3)
    spans = tr.spans()
    assert [e["name"] for e in spans] == ["forward", "tick"]
    assert all(e["dur"] > 0 for e in spans)
    assert tr.spans("tick")[0]["args"] == {"tick": 1}
    chrome = tr.to_chrome()
    names = {e["name"] for e in chrome["traceEvents"]}
    assert {"thread_name", "tick", "forward", "admit"} <= names
    json.dumps(chrome)                          # Perfetto-loadable JSON


def test_tracer_sampling_and_null_path():
    tr = SpanTracer(sample_every=4)
    assert tr.for_tick(0) is tr and tr.for_tick(4) is tr
    assert tr.for_tick(1) is NULL_TRACER and tr.for_tick(3) is NULL_TRACER
    # the null path allocates nothing and records nothing
    assert NULL_TRACER.for_tick(123) is NULL_TRACER
    assert NULL_TRACER.span(None, "x") is NULL_SPAN
    with NULL_TRACER.span(None, "x"):
        pass
    NULL_TRACER.instant(None, "x")
    NULL_TRACER.complete("x", "t", 0.0, 1.0)
    assert NULL_TRACER.events == () and not NULL_TRACER.enabled
    with pytest.raises(ValueError):
        SpanTracer(sample_every=0)


def test_tracer_max_events_drops_not_grows():
    tr = SpanTracer(max_events=5)
    clock = _FakeClock()
    for i in range(10):
        tr.instant(clock, "e", tid="t", i=i)
    assert len(tr.events) == 5
    assert tr.dropped == 10 - (5 - 1)           # 1 slot went to metadata


def test_clock_anchors_map_perf_counter_to_unix_time():
    """Attached to a wall-clocked fleet, the tracer exports an anchor
    from attach and one from export; through them a perf_counter reading
    lands within the reads' gaps of time.time_ns()."""
    import time
    from repro.obs.tracing import to_unix_ns
    tr = SpanTracer()
    tr.attach(wall_clock=True)
    try:
        time.sleep(0.01)
        p0 = time.perf_counter_ns()
        unix = time.time_ns()
        p1 = time.perf_counter_ns()
        anchors = tr.to_chrome()["otherData"]["clock_anchors"]
    finally:
        tr.detach()
    assert len(anchors) == 2
    assert anchors[0]["perf_ns"] < p0 < p1 < anchors[1]["perf_ns"]
    assert all(0 <= a["gap_ns"] < 1_000_000 for a in anchors)
    got = to_unix_ns(anchors, (p0 + p1) / 2 * 1e-9)
    assert abs(got - unix) <= (p1 - p0) + max(a["gap_ns"] for a in anchors)
    json.dumps(tr.to_chrome())


def test_to_unix_ns_interpolates_between_first_and_last_anchor():
    from repro.obs.tracing import to_unix_ns
    anchors = [{"perf_ns": 1_000, "unix_ns": 5_000_000, "gap_ns": 10},
               {"perf_ns": 2_000, "unix_ns": 9_999_999, "gap_ns": 10},
               {"perf_ns": 1_001_000, "unix_ns": 6_000_010, "gap_ns": 10}]
    # the outer pair only: 1e6 + 10 unix ns over 1e6 perf ns
    assert to_unix_ns(anchors, 1_000e-9) == 5_000_000
    assert to_unix_ns(anchors, 501_000e-9) == 5_000_000 + 500_005
    assert to_unix_ns(anchors[:1], 3_000e-9) == 5_002_000


def test_virtual_clocked_attach_exports_no_anchor_and_no_gc_lane():
    import gc
    tr = SpanTracer()
    tr.attach(wall_clock=False)
    assert tr.to_chrome()["otherData"]["clock_anchors"] == []
    gc.collect()
    assert tr.spans("gc") == []
    tr.detach()


def test_gc_lane_records_collections_while_attached_and_sampled():
    import gc
    tr = SpanTracer(sample_every=2)
    tr.attach(wall_clock=True)
    hooks = len(gc.callbacks)
    tr.attach(wall_clock=True)                 # a second fleet: one hook
    assert len(gc.callbacks) == hooks
    gc.collect()
    (ev,) = tr.spans("gc")
    assert ev["args"]["generation"] == 2 and ev["dur"] >= 0
    tr.for_tick(1)                             # an unsampled tick
    gc.collect()
    assert len(tr.spans("gc")) == 1
    tr.for_tick(2)
    tr.detach()
    gc.collect()                               # one fleet still holds it
    assert len(tr.spans("gc")) == 2
    tr.detach()
    assert len(gc.callbacks) == hooks - 1
    gc.collect()
    assert len(tr.spans("gc")) == 2


def test_tracer_dump(tmp_path):
    tr = SpanTracer()
    tr.complete("tick", "r0", 1.0, 0.5, tick=7)
    path = tmp_path / "trace.json"
    tr.dump(str(path))
    loaded = json.loads(path.read_text())
    assert loaded["traceEvents"][-1]["name"] == "tick"
    assert loaded["traceEvents"][-1]["dur"] == pytest.approx(0.5e6)


# ----------------------------------------------------------------------
# FleetStatus on a live scenario
# ----------------------------------------------------------------------
def test_fleet_status_snapshot_mid_scenario():
    from repro.simulate import get_scenario
    from repro.simulate.runner import ScenarioRunner

    snaps = []

    def on_tick(tick, runner):
        if tick == 40:
            snaps.append(FleetStatus.from_gateway(runner.gw))

    runner = ScenarioRunner(get_scenario("steady_state"))
    runner.run(on_tick=on_tick)
    assert len(snaps) == 1
    fs = snaps[0]
    assert fs.sessions > 0
    assert all(r.kind in ("vision", "token") for r in fs.replicas)
    vision = [r for r in fs.replicas if r.kind == "vision"]
    assert vision and all(0.0 <= r.occupancy <= 1.0 for r in vision)
    assert all(len(r.lane_binds) == r.slots for r in vision)
    d = fs.to_dict()
    json.dumps(d)
    assert len(d["replicas"]) == len(fs.replicas)
    text = fs.render()
    assert "replica" in text and "fleet:" in text
    for r in fs.replicas:
        assert r.name in text


def test_fleet_status_battery_footer():
    fs = FleetStatus(replicas=[], sessions=0, refused=0, rebinds=0,
                     fused_dispatches=0, jit_cache=0,
                     vehicle_energy={"v00": (90.0, 100.0),
                                     "v01": (10.0, 100.0)})
    text = fs.render()
    assert "battery" in text
    assert "v00 10%" in text                    # lowest headroom first
