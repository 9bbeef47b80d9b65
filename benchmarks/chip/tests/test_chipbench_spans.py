"""The program's spans on the device trace's clock (``chipbench/spans.py``):
exact on made-up planes, per device on four, silent where a run has no
span, and the window-only tracer of ``program_spans.py`` on a shrunk
cell."""
from __future__ import annotations

import gc
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parents[1] / "src"), str(BENCH)]

from chipbench import spans, spec, trace  # noqa: E402

US = 1e-6
START_NS = 1_792_242_911_969_493_152      # the session's start, unix ns


def _ev(name, start_us, dur_us):
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3)


def _device(n, ops, fused):
    return NS(name=f"/device:TPU:{n}", stats=[],
              lines=[NS(name="XLA Modules",
                        events=[_ev("jit_fused(1)", *f) for f in fused]),
                     NS(name="XLA Ops",
                        events=[_ev(f"%op.{i} = f32[] x()", s, d)
                                for i, (s, d) in enumerate(ops)])])


def _planes(devices, start_ns=START_NS):
    env = NS(name="Task Environment", lines=[],
             stats=[("profile_start_time", start_ns),
                    ("profile_stop_time", start_ns + 10 ** 9)])
    return [NS(name="/host:CPU", lines=[], stats=[]), env, *devices]


# host clock: perf = device time + HOST_AT (device events count from the
# session's start); the anchors say so, one second apart
def _anchors(host_at_s, drift_ns=0):
    p0 = round(host_at_s * 1e9)
    return [{"perf_ns": p0, "unix_ns": START_NS, "gap_ns": 80},
            {"perf_ns": p0 + 10 ** 9, "unix_ns": START_NS + 10 ** 9
             + drift_ns, "gap_ns": 120}]


# one tick, in us of the host clock past HOST_AT
PROGRAM = [("fleet", "fleet.tick", 210, 790),
           ("r0", "rebalance", 215, 225),
           ("r0", "stage", 230, 280),
           ("fleet", "fused_dispatch", 300, 700),
           ("fleet", "fleet.gather", 300, 320),
           ("fleet", "fleet.call", 320, 420),
           ("fleet", "fleet.wait", 420, 690),
           ("fleet", "fleet.readback", 700, 710),
           ("fleet", "fleet.commit", 710, 760),
           ("r0", "commit", 715, 755),
           ("python", "gc", 730, 740),
           ("fleet", "fleet.end", 760, 780)]
DRIVER = [("push", 100, 200), ("gateway.tick", 200, 800),
          ("bookkeeping", 800, 900), ("wait", 900, 950)]
WINDOW_US = (100, 1100)
FUSED = [(400, 200)]                       # centred in its tick
OPS = [(400, 50), (470, 130), (1050, 100)]


def _export(host_at_s, program=PROGRAM, drift_ns=0):
    lanes = {}
    events = []
    for lane, name, s, e in program:
        if lane not in lanes:
            lanes[lane] = len(lanes)
            events.append({"ph": "M", "name": "thread_name", "pid": 0,
                           "tid": lanes[lane], "args": {"name": lane}})
        events.append({"ph": "X", "name": name, "pid": 0,
                       "tid": lanes[lane], "ts": host_at_s * 1e6 + s,
                       "dur": e - s})
    return {"traceEvents": events, "otherData": {
        "dropped_events": 0, "clock_anchors": _anchors(host_at_s, drift_ns)}}


def _host(host_at_s):
    window = tuple(host_at_s + x * US for x in WINDOW_US)
    driver = [(n, host_at_s + s * US, host_at_s + e * US)
              for n, s, e in DRIVER]
    ticks = [(s, e) for n, s, e in driver if n == "gateway.tick"]
    return window, driver, ticks


@pytest.mark.parametrize("host_at_s", [0.0, 5.0, 63.5])
def test_idle_in_the_tick_splits_by_innermost_span_and_sums_to_it(host_at_s):
    window, driver, ticks = _host(host_at_s)
    planes = _planes([_device(0, OPS, FUSED)])
    red = spans.reduce_planes(planes, 1, window, driver, ticks,
                              _export(host_at_s))
    # idle inside the tick [200, 800): [200, 400) [450, 470) [600, 800)
    want = {"gateway.tick": 20, "fleet.tick": 40, "rebalance": 10,
            "stage": 50, "fleet.gather": 20, "fleet.call": 80,
            "fleet.wait": 110, "fused_dispatch": 10, "fleet.readback": 10,
            "fleet.commit": 10, "commit": 30, "gc": 10, "fleet.end": 20}
    got = {k.split(":", 1)[-1]: v / US for k, v in red.idle_split.items()}
    assert got == pytest.approx(want, abs=1e-3)
    assert set(red.idle_split) - {"gateway.tick"} == {
        f"gateway.tick:{n}" for n in want if n != "gateway.tick"}
    # the split sums to the total trace.py gives on the same clock
    old = trace.reduce_planes(planes, 1, window, driver, ticks)
    assert red.tick_idle_s == pytest.approx(
        old.idle_by_host["gateway.tick"], abs=1e-12)
    assert red.remainder_share == pytest.approx(20 / 420)
    # the fused program against its dispatch span
    assert (red.runs, red.paired, red.inside) == (1, 1, 1)
    assert red.upload_lag_s[0] / US == pytest.approx(100, abs=1e-3)
    assert red.device_s[0] / US == pytest.approx(200, abs=1e-3)
    assert red.readback_lag_s[0] / US == pytest.approx(100, abs=1e-3)
    assert red.dispatch_s[0] / US == pytest.approx(400, abs=1e-3)
    # anchored and fitted offsets agree (the run sits centred)
    assert red.anchored_offset_s == pytest.approx(-host_at_s, abs=1e-9)
    assert red.fitted_offset_s == pytest.approx(-host_at_s, abs=1e-9)
    assert red.anchor_gaps_ns == [80, 120] and red.drift_ppm == 0
    run = NS(spans=red)
    assert spans.stage_ms(run) == pytest.approx(0.05, abs=1e-6)
    assert spans.commit_ms(run) == pytest.approx(0.05, abs=1e-6)
    assert spans.upload_lag_ms(run) == pytest.approx(0.1, abs=1e-6)
    assert spans.readback_lag_ms(run) == pytest.approx(0.1, abs=1e-6)
    line = spans.summary(red)
    assert line["anchored_minus_fitted_s"] == pytest.approx(0, abs=1e-9)
    assert line["inside_share"] == 1.0 and line["stalls"] == []


def test_anchors_carry_the_clocks_drift():
    """A unix clock that runs 100 ppm fast over the anchors: the device's
    events (unix-dated) land 100 ppm earlier on the host clock."""
    shared = spans.SharedClock(_anchors(2.0, drift_ns=100_000), START_NS)
    assert shared.host_s(0) == pytest.approx(2.0, abs=1e-12)
    assert shared.host_s(1e9) == pytest.approx(2.0 + 1 / 1.0001, abs=1e-12)
    assert shared.device_ns(shared.host_s(5e8)) == pytest.approx(5e8)


def test_a_program_fused_run_outside_its_dispatch_is_counted_out():
    window, driver, ticks = _host(1.0)
    late = [(650, 100)]                      # ends past the dispatch
    red = spans.reduce_planes(_planes([_device(0, OPS, late)]), 1, window,
                              driver, ticks, _export(1.0))
    assert (red.runs, red.paired, red.inside) == (1, 1, 0)
    assert red.inside_share == 0.0
    early = [(250, 100)]                     # starts before any dispatch
    red = spans.reduce_planes(_planes([_device(0, OPS, early)]), 1, window,
                              driver, ticks, _export(1.0))
    assert (red.runs, red.paired) == (1, 0)


def test_four_devices_reduce_per_device():
    """Each device's idle time is split and summed; each device's fused
    program pairs with the one dispatch; only the cell's chips count."""
    window, driver, ticks = _host(3.0)
    specs = [(OPS, FUSED), ([(400, 200)], FUSED),
             ([(300, 400)], [(310, 380)]), ([], [(420, 100)])]
    planes = _planes([_device(n, *sp) for n, sp in enumerate(specs)])
    four = spans.reduce_planes(planes, 4, window, driver, ticks,
                               _export(3.0))
    assert (four.runs, four.paired, four.inside) == (4, 4, 4)
    old = trace.reduce_planes(planes, 4, window, driver, ticks)
    assert four.tick_idle_s == pytest.approx(
        old.idle_by_host["gateway.tick"], abs=1e-12)
    per = [spans.reduce_planes(_planes([_device(0, *sp)]), 1, window,
                               driver, ticks, _export(3.0)).tick_idle_s
           for sp in specs]
    # device 3 ran no operation: the whole tick is idle there
    assert per[3] == pytest.approx(600 * US)
    assert four.tick_idle_s == pytest.approx(sum(per), abs=1e-12)
    two = spans.reduce_planes(planes, 2, window, driver, ticks,
                              _export(3.0))
    assert two.runs == 2
    assert two.tick_idle_s == pytest.approx(sum(per[:2]), abs=1e-12)


def test_a_stall_is_put_down_to_the_span_that_holds_its_excess():
    period, ticks_n, slow = 10_000, 12, 7
    program, driver = [], []
    for k in range(ticks_n):
        t = k * period
        extra = 5000 if k == slow else 0
        driver += [("gateway.tick", t + 10, t + 600 + extra)]
        program += [("fleet", "fleet.tick", t + 20, t + 590 + extra),
                    ("r0", "stage", t + 30, t + 80 + extra),
                    ("fleet", "fused_dispatch", t + 100 + extra,
                     t + 500 + extra)]
    end = ticks_n * period + 5000
    host_at = 4.0
    driver = [(n, host_at + s * US, host_at + e * US) for n, s, e in driver]
    window = (host_at, host_at + end * US)
    fused = [(k * period + 200 + (5000 if k == slow else 0), 200)
             for k in range(ticks_n)]
    export = _export(host_at, program=program)
    red = spans.reduce_planes(_planes([_device(0, fused, fused)]), 1,
                              window, driver,
                              [(s, e) for _, s, e in driver], export)
    (stall,) = red.stalls
    assert stall["span"] == "stage"
    assert stall["excess_ms"] == pytest.approx(5.0, abs=1e-6)
    # its dispatch [5100, 5500) ran the program [5200, 5400)
    assert stall["upload_lag_ms"] == pytest.approx(0.1, abs=1e-6)
    assert stall["readback_lag_ms"] == pytest.approx(0.1, abs=1e-6)
    assert stall["at_s"] == pytest.approx((slow * period + 10) * US)
    assert red.ticks == ticks_n


def test_a_run_without_program_spans_reads_nothing():
    window, driver, ticks = _host(0.0)
    planes = _planes([_device(0, OPS, FUSED)])
    assert spans.reduce_planes(planes, 1, window, driver, ticks,
                               None) is None
    bare = _export(0.0)
    bare["otherData"]["clock_anchors"] = []
    assert spans.reduce_planes(planes, 1, window, driver, ticks,
                               bare) is None
    no_start = [p for p in planes if p.name != "Task Environment"]
    assert spans.reduce_planes(no_start, 1, window, driver, ticks,
                               _export(0.0)) is None
    for name in ("stage_ms", "commit_ms", "upload_lag_ms",
                 "readback_lag_ms"):
        read = spec.load_reader(spec.find_reader(name))
        assert read(NS(spans=None)) is None
        assert read(NS()) is None             # a RunView with no such field


def test_the_readers_read_a_reduction():
    window, driver, ticks = _host(0.0)
    red = spans.reduce_planes(_planes([_device(0, OPS, FUSED)]), 1, window,
                              driver, ticks, _export(0.0))
    got = {name: spec.load_reader(spec.find_reader(name))(NS(spans=red))
           for name in ("stage_ms", "commit_ms", "upload_lag_ms",
                        "readback_lag_ms")}
    assert got == pytest.approx({"stage_ms": 0.05, "commit_ms": 0.05,
                                 "upload_lag_ms": 0.1,
                                 "readback_lag_ms": 0.1}, abs=1e-6)


# trace.py's reading of the first kept trace, as recorded before the
# program had spans on the device's clock
PINNED = {"idle_share": 0.8505002469714769,
          "idle_by_host": {"push": 0.00286499899999626,
                           "gateway.tick": 0.429426776009187,
                           "bookkeeping": 0.0016989830000042616,
                           "other": 0.0006072290000176395},
          "ops": ["vmap_jit__ingest_frame_jit__",
                  "vmap_jit__ingest_frame_jit__.1", "fusion.215",
                  "fusion.212", "fusion.8", "fusion.7", "copy.506",
                  "copy.533", "copy.508", "copy.535"]}


def test_the_kept_trace_without_program_spans_reduces_as_before():
    """The first kept chip trace has no program span: the new reduction
    reads nothing, and trace.py's numbers are what they were."""
    window, host, ticks = trace.load_host(
        BENCH / "tests" / "data" / "eda192-motion-sat.host.json")
    xplane = BENCH / "tests" / "data" / "eda192-motion-sat.xplane.pb"
    assert spans.reduce(xplane, 1, window, host, ticks, None) is None
    red = trace.reduce(xplane, 1, window, host, ticks)
    assert red.idle_share == pytest.approx(PINNED["idle_share"], rel=1e-12)
    assert red.idle_by_host == pytest.approx(PINNED["idle_by_host"],
                                             rel=1e-9)
    ops = trace.breakdown(red)["device_ops"]
    assert [k for k, _ in ops] == PINNED["ops"]


def test_a_kept_chip_trace_with_program_spans_shares_one_clock():
    """A 0.5 s window of ``eda192-motion-sat`` traced on a TPU v5e with
    the program's spans: every fused program lies inside its dispatch
    span on the anchored clock, the anchors are tight, the program's
    spans cover the device's idle time in the tick, and the anchored
    offset lies inside the range trace.py's fit leaves open."""
    import json
    data = BENCH / "tests" / "data"
    window, host, ticks = trace.load_host(
        data / "eda192-motion-sat-spans.host.json")
    export = json.loads(
        (data / "eda192-motion-sat-spans.program.json").read_text())
    xplane = data / "eda192-motion-sat-spans.xplane.pb"
    red = spans.reduce(xplane, 1, window, host, ticks, export)
    assert red.runs >= 10 and red.inside == red.paired == red.runs
    assert all(g < 20_000 for g in red.anchor_gaps_ns)
    assert red.remainder_share < 0.02
    old = trace.reduce(xplane, 1, window, host, ticks)
    assert red.tick_idle_s == pytest.approx(
        old.idle_by_host["gateway.tick"], abs=0.02)
    assert (abs(red.anchored_offset_s - red.fitted_offset_s)
            <= red.fitted_slack_s / 2)
    for u, d, b, w in zip(red.upload_lag_s, red.device_s,
                          red.readback_lag_s, red.dispatch_s):
        assert u > 0 and d > 0 and b > 0
        assert u + d + b == pytest.approx(w, abs=1e-9)
    run = NS(spans=red)
    assert 0 < spans.stage_ms(run) < spans.upload_lag_ms(run)
    assert 0 < spans.commit_ms(run) < 5 and 0 < spans.readback_lag_ms(run)
    names = {k.split(":", 1)[-1] for k in red.idle_split}
    assert {"stage", "fleet.wait", "fleet.call"} <= names


def test_the_window_tracer_attaches_over_the_window_only():
    """``program_spans.window_tracing`` on a shrunk cell, on the CPU: the
    export holds the window's sampled fleet ticks with two clock anchors,
    and the fleet lets the tracer go when the window ends."""
    import chipbench_tiny as tiny
    mod = spec.load_module(BENCH / "program_spans.py")
    hooks = len(gc.callbacks)
    with mod.window_tracing(True, sample_every=2) as got:
        res = tiny.run(tiny.cell())
    assert res["correct"]
    assert len(gc.callbacks) == hooks
    export = got.export
    prog = spans.program_spans(export)
    # every other window tick recorded, and only those
    rec, skip = got.host_s[True], got.host_s[False]
    assert rec and skip and abs(len(rec) - len(skip)) <= 1
    assert len(rec) == sum(sp.name == "fleet.tick" for sp in prog)
    assert {"fleet.tick", "fused_dispatch", "fleet.wait", "stage",
            "fleet.commit"} <= {sp.name for sp in prog}
    a = export["otherData"]["clock_anchors"]
    assert len(a) == 2
    assert all(a[0]["perf_ns"] * 1e-9 <= sp.start
               and sp.end <= a[-1]["perf_ns"] * 1e-9 for sp in prog)
