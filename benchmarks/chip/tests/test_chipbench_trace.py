"""The reduction from a device trace and the driver's host spans to busy
time, idle share, kernel time and idle time by host activity: exact on
made-up planes, and sound on a short trace recorded on a TPU v5e."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parents[1] / "src"), str(BENCH)]

from chipbench import trace  # noqa: E402

DATA = BENCH / "tests" / "data"
NS_ = 1e-9


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _device(n, ops, modules):
    return NS(name=f"/device:TPU:{n}",
              lines=[NS(name="XLA Modules", events=modules),
                     NS(name="XLA Ops", events=ops)])


# device clock = host clock + 5000 ns
SHIFT = 5000


def _planes(ops, extra=()):
    modules = [_ev("jit_fused(123)", 400 + SHIFT, 200)]
    planes = [NS(name="/host:CPU", lines=[]),
              _device(0, [_ev(n, s + SHIFT, d) for n, s, d in ops], modules)]
    for i, more in enumerate(extra, start=1):
        planes.append(_device(i, [_ev(n, s + SHIFT, d) for n, s, d in more],
                              modules))
    return planes


WINDOW = (100 * NS_, 1100 * NS_)
SPANS = [("push", 100 * NS_, 200 * NS_),
         ("gateway.tick", 200 * NS_, 800 * NS_),
         ("bookkeeping", 800 * NS_, 900 * NS_),
         ("wait", 900 * NS_, 950 * NS_)]
TICKS = [(200 * NS_, 800 * NS_)]


def test_busy_union_kernel_time_and_idle_attribution_are_exact():
    ops = [("%fusion.1 = f32[2] fusion(...)", 50, 200),           # clipped
           ("%vmap_jit__ingest_frame_jit__ = (f32[2]) custom-call()",
            400, 100),
           ("%vmap_jit__ingest_frame_jit__.1 = (f32[2]) custom-call()",
            450, 150),                                             # overlaps
           ("%convolution.3 = f32[2] convolution()", 1050, 100)]
    red = trace.reduce_planes(_planes(ops), 1, WINDOW, SPANS, TICKS)
    assert red.window_s == pytest.approx(1000 * NS_)
    # the one fused run [400, 600) fits the tick [200, 800) with the
    # device offset anywhere in [-200, +200] ns around the true one
    assert red.offset_slack_s == pytest.approx(400 * NS_)
    # busy: [100,250) + [400,600) + [1050,1100)
    assert red.busy_s == [pytest.approx(400 * NS_)]
    assert red.idle_share == pytest.approx(0.6)
    assert red.kernel_s("_ingest_frame_jit") == pytest.approx(250 * NS_)
    assert red.op_s["fusion.1"] == pytest.approx(150 * NS_)
    # idle [250,400) and [600,1050): tick 150+200, bookkeeping 100,
    # wait 50, and [950,1050) outside every span
    idle = red.idle_by_host
    assert idle["gateway.tick"] == pytest.approx(350 * NS_)
    assert idle["bookkeeping"] == pytest.approx(100 * NS_)
    assert idle["wait"] == pytest.approx(50 * NS_)
    assert idle["other"] == pytest.approx(100 * NS_)
    assert "push" not in idle
    assert sum(idle.values()) == pytest.approx(600 * NS_)
    b = trace.breakdown(red)
    assert {k for k, _ in b["device_ops"]} == {
        "fusion.1", "vmap_jit__ingest_frame_jit__",
        "vmap_jit__ingest_frame_jit__.1", "convolution.3"}
    assert b["idle_gaps"][0][0] == "gateway.tick"


def test_only_the_cells_chips_count_and_busy_is_their_mean():
    planes = _planes([("a", 100, 500)], extra=[[("b", 100, 1000)],
                                               [("c", 100, 10)]])
    one = trace.reduce_planes(planes, 1, WINDOW, SPANS, TICKS)
    assert one.busy_s == [pytest.approx(500 * NS_)]
    two = trace.reduce_planes(planes, 2, WINDOW, SPANS, TICKS)
    assert two.mean_busy_s == pytest.approx(750 * NS_)
    assert two.idle_share == pytest.approx(0.25)


def test_a_trace_without_a_device_or_a_fused_tick_is_refused():
    host = NS(name="/host:CPU", lines=[])
    with pytest.raises(ValueError, match="TPU device plane"):
        trace.reduce_planes([host], 1, WINDOW, SPANS, TICKS)
    with pytest.raises(ValueError, match="fused tick"):
        trace.reduce_planes([host, _device(0, [], [])], 1, WINDOW, SPANS,
                            TICKS)


def test_a_trace_recorded_on_the_chip_reduces_to_sound_numbers():
    window, spans, ticks = trace.load_host(
        DATA / "eda192-motion-sat.host.json")
    red = trace.reduce(DATA / "eda192-motion-sat.xplane.pb", 1, window,
                       spans, ticks)
    assert 0 < red.mean_busy_s < red.window_s
    assert 0 < red.idle_share < 1
    # the offset is pinned to well under a tick
    assert 0 <= red.offset_slack_s < 0.02
    assert red.kernel_s("_ingest_frame_jit") > 0
    idle = sum(red.idle_by_host.values())
    assert idle == pytest.approx(red.window_s - red.mean_busy_s, rel=1e-6)
    assert set(red.idle_by_host) <= set(trace.HOST_NAMES) | {"other"}
    assert max(red.idle_by_host, key=red.idle_by_host.get) == "gateway.tick"
    b = trace.breakdown(red)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
