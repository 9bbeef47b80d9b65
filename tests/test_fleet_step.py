"""Differential harness: serial fleet tick vs the mesh-parallel tick.

``FleetGateway(parallel=True)`` must be *bit-identical* to the serial
reference under virtual clocks: same admit decisions, same ledger records,
same golden-trace digests — across the scenario library, replica-count
sweeps (1/2/8), uneven lane occupancy, and mid-run replica fail/restore
rebinds.  The fast tests run shortened scenarios through the vmap mode
(single CPU device); the slow tests run the full-length library and the
shard_map mode on a forced 8-device host mesh in a subprocess.
"""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.simulate import (ReplicaSpec, Scenario, ScriptedEvent,
                            VehicleProfile, get_scenario, run_scenario)

FAST = [
    ("steady_state", dict(ticks=40)),
    ("golden_churn", dict(ticks=60)),
    ("replica_failure", dict(ticks=80)),      # fail_replica fires at 60
    ("pallas_ingest", {}),                    # fused kernels, full length
    ("priority_inversion", dict(ticks=40)),   # 8 streams on 2 lanes
]


def _record_key(r):
    return (r.video_id, r.stream, r.device, r.frames_total,
            r.frames_processed, r.frames_gated, r.frames_dropped,
            r.frames_deadline_dropped, r.processing_ms, r.turnaround_ms)


def assert_bit_identical(serial, parallel):
    assert not serial.violations, "\n".join(map(str, serial.violations))
    assert not parallel.violations, "\n".join(map(str, parallel.violations))
    assert [_record_key(r) for r in serial.ledger.records] \
        == [_record_key(r) for r in parallel.ledger.records], \
        "ledger records diverged between serial and parallel ticks"
    assert serial.summary == parallel.summary
    if serial.digest != parallel.digest:          # pragma: no cover
        sa, pa = serial.trace.canonical(), parallel.trace.canonical()
        for i, (a, b) in enumerate(zip(sa.splitlines(), pa.splitlines())):
            assert a == b, f"first trace divergence at event {i}:\n" \
                           f"  serial:   {a}\n  parallel: {b}"
        raise AssertionError("trace lengths diverged")


@pytest.mark.parametrize("name,overrides", FAST,
                         ids=[n for n, _ in FAST])
def test_parallel_tick_matches_serial(name, overrides):
    s = get_scenario(name, **overrides)
    assert_bit_identical(run_scenario(s),
                         run_scenario(s, parallel=True, fleet_mode="vmap"))


def _sweep_scenario(n_replicas: int, **kw) -> Scenario:
    """Churny sweep scenario: 3 initial vehicles over ``n_replicas``
    uniform replicas — at R=8 most lane masks are empty (uneven
    occupancy), at R=1 the lanes are oversubscribed (quantum rotation)."""
    base = dict(
        name=f"sweep_r{n_replicas}", seed=7_000 + n_replicas, ticks=50,
        replicas=tuple(ReplicaSpec(f"r{i}", slots=4)
                       for i in range(n_replicas)),
        profiles=(VehicleProfile(duplicate_prob=0.4),
                  VehicleProfile(name="burst", frames_per_tick=2,
                                 dup_pattern=(0, 1))),
        initial_vehicles=3, join_rate=0.3, leave_rate=0.03,
        max_vehicles=3 * n_replicas + 1, overcommit=2.0)
    base.update(kw)
    return Scenario(**base)


@pytest.mark.parametrize("n_replicas", [1, 2, 8])
def test_parallel_tick_replica_count_sweep(n_replicas):
    s = _sweep_scenario(n_replicas)
    assert_bit_identical(run_scenario(s),
                         run_scenario(s, parallel=True, fleet_mode="vmap"))


def test_parallel_tick_midrun_fail_restore_rebind():
    """Rebinds mid-run: gate state travels, trace digests stay equal."""
    s = _sweep_scenario(
        3, name="sweep_fail", ticks=70,
        scripted=(ScriptedEvent(20, "fail_replica", "r1"),
                  ScriptedEvent(45, "restore_replica", "r1")))
    ser = run_scenario(s)
    par = run_scenario(s, parallel=True, fleet_mode="vmap")
    assert ser.summary["rebinds"] > 0, "scenario must actually rebind"
    assert_bit_identical(ser, par)


def test_wall_clock_parallel_gateway_admit_parity():
    """Under wall clocks timing differs but admit/gate/flag decisions are
    clock-independent: a parallel gateway must process exactly the frames
    the serial gateway processes."""
    import jax
    from repro.data import DashCamSource
    from repro.streams import FleetGateway, VisionServeEngine

    def drive(parallel):
        replicas = [VisionServeEngine(f"r{i}", slots=2, frame_res=32,
                                      input_res=16, use_gate=True,
                                      rng=jax.random.key(i))
                    for i in range(3)]
        gw = FleetGateway(replicas, parallel=parallel)
        src = DashCamSource(granularity_s=0.4, fps=30, res=32, seed=3)
        for v in range(2):
            gw.join(f"v{v}")
            pair = src.pair(v)
            for outer, inner in zip(pair.outer[:8], pair.inner[:8]):
                gw.push(f"v{v}", outer, inner)
        gw.drain()
        out = []
        for v in range(2):
            for rec in gw.leave(f"v{v}"):
                out.append((rec.video_id, rec.stream, rec.frames_total,
                            rec.frames_processed, rec.frames_gated))
        return sorted(out)

    assert drive(False) == drive(True)


def test_fleet_step_rejects_non_uniform_geometry():
    import jax
    from repro.streams import VisionServeEngine
    from repro.streams.fleet_step import FleetStep
    a = VisionServeEngine("a", slots=2, frame_res=32, input_res=16,
                          rng=jax.random.key(0))
    b = VisionServeEngine("b", slots=4, frame_res=32, input_res=16,
                          rng=jax.random.key(1))
    with pytest.raises(ValueError, match="uniform engine geometry"):
        FleetStep([a, b], warm=False)


def test_parallel_tick_single_fused_dispatch_per_tick():
    """The whole point: one device dispatch per fleet tick, regardless of
    replica count or which lanes are live."""
    import jax
    from repro.streams import FleetGateway, VisionServeEngine
    replicas = [VisionServeEngine(f"r{i}", slots=2, frame_res=32,
                                  input_res=16, use_gate=True,
                                  rng=jax.random.key(i)) for i in range(4)]
    gw = FleetGateway(replicas, parallel=True)
    gw.join("v0")
    frame = np.random.default_rng(0).random((32, 32, 3)).astype(np.float32)
    for _ in range(5):
        gw.push("v0", frame, frame)
    before = gw._fleet.dispatches
    ticks = 0
    while any(r.has_work() for r in gw.live_replicas()):
        gw.tick()
        ticks += 1
    assert gw._fleet.dispatches - before == ticks


@pytest.mark.parametrize("input_res", [(16, 16, 16), (16, 8, 16)],
                         ids=["uniform", "mixed_tier"])
def test_engine_stage_is_a_view_of_its_group_planes(input_res):
    """Each tier group stages into one (members, slots, H, W*3) buffer;
    an engine's ``_stage`` is a (slots, H, W, 3) view of its row, so a
    frame staged through ``stage_class`` lands at ``[lane, h, w*3 + c]``
    of the buffer the fused call uploads."""
    import jax
    from repro.streams import VisionServeEngine
    from repro.streams.fleet_step import FleetStep
    from repro.streams.vision_engine import OUTER
    res, slots = 32, 2
    replicas = [VisionServeEngine(f"r{i}", slots=slots, frame_res=res,
                                  input_res=ires, use_gate=True,
                                  rng=jax.random.key(i))
                for i, ires in enumerate(input_res)]
    fleet = FleetStep(replicas, warm=False)
    assert len(fleet._stage_groups) == len(set(input_res))
    rng = np.random.default_rng(0)
    for buf, mem in zip(fleet._stage_groups, fleet._members):
        assert buf.shape == (len(mem), slots, res, res * 3)
        assert buf.dtype == np.float32 and buf.flags.c_contiguous
        for j, i in enumerate(mem):
            r = replicas[i]
            assert r._stage.shape == (slots, res, res, 3)
            assert np.shares_memory(r._stage, buf[j])
            r.open_stream(f"cam{i}", OUTER)
            frame = rng.random((res, res, 3)).astype(np.float32)
            assert r.push(f"cam{i}", frame)
            active = r.stage_class(OUTER)
            lane = int(np.flatnonzero(active)[0])
            h, w, c = 5, 7, 2
            assert buf[j, lane, h, w * 3 + c] == frame[h, w, c]
            np.testing.assert_array_equal(buf[j, lane],
                                          frame.reshape(res, res * 3))


# ---------------------------------------------------------------------------
# spans of the fused tick (obs.tracing) on a wall-clocked fleet
# ---------------------------------------------------------------------------

RES = 64


def _traced_fleet(tracer=None, clock=None, frames=6):
    """Two wall-clocked replicas, two vehicles with ``frames`` distinct
    frame pairs each queued; returns the gateway."""
    import jax
    from repro.streams import FleetGateway, VisionServeEngine
    replicas = [VisionServeEngine(f"r{i}", slots=2, frame_res=RES,
                                  input_res=RES // 2, use_gate=True,
                                  rng=jax.random.key(i),
                                  clock=clock() if clock else None)
                for i in range(2)]
    gw = FleetGateway(replicas, parallel=True, tracer=tracer)
    rng = np.random.default_rng(0)
    for v in range(2):
        gw.join(f"v{v}")
        for _ in range(frames):
            gw.push(f"v{v}",
                    rng.random((RES, RES, 3)).astype(np.float32),
                    rng.random((RES, RES, 3)).astype(np.float32))
    return gw


def _drain(gw) -> int:
    ticks = 0
    while any(r.has_work() for r in gw.live_replicas()):
        gw.tick()
        ticks += 1
    return ticks


def _lane(tr, lane):
    tids = {e["args"]["name"]: e["tid"] for e in tr.events
            if e["ph"] == "M"}
    return [e for e in tr.spans() if e["tid"] == tids[lane]]


def _end(e):
    return e["ts"] + e["dur"]


EPS_US = 0.002          # span times are rounded to the ns


def _inside(child, parent):
    return (parent["ts"] - EPS_US <= child["ts"]
            and _end(child) <= _end(parent) + EPS_US)


def test_fused_tick_spans_nest_in_order_and_cover_the_tick():
    from repro.obs import SpanTracer
    tr = SpanTracer()
    gw = _traced_fleet(tr)
    ticks = _drain(gw)
    fleet = _lane(tr, "fleet")
    ft = [e for e in fleet if e["name"] == "fleet.tick"]
    fd = [e for e in fleet if e["name"] == "fused_dispatch"]
    assert len(ft) == ticks and len(fd) == gw._fleet.dispatches > 0
    # fused_dispatch is the very interval last_dispatch_s measured
    assert fd[-1]["dur"] == pytest.approx(
        gw._fleet.last_dispatch_s * 1e6, abs=EPS_US)
    assert tr.spans("tick") == []     # fleet.tick replaces R copies
    order = ("fleet.gather", "fleet.call", "fleet.wait")
    after = ("fleet.readback", "fleet.commit", "fleet.end")
    for tick in ft:
        inner = {e["name"]: e for e in fleet
                 if e is not tick and _inside(e, tick)}
        assert "fleet.end" in inner
        if "fused_dispatch" not in inner:
            continue
        d = inner["fused_dispatch"]
        assert all(_inside(inner[n], d) for n in order)
        seq = [inner[n] for n in order] + [d] + [inner[n] for n in after]
        for a, b in zip(seq, seq[1:]):
            if b is d:
                continue
            assert _end(a) <= b["ts"] + EPS_US, (a["name"], b["name"])
        assert inner["fleet.call"]["args"]["bytes"] == 2 * 2 * RES * RES * 3 * 4
        covered = sum(inner[n]["dur"] for n in ("fused_dispatch",) + after)
        assert covered >= 0.8 * tick["dur"]
        for r in ("r0", "r1"):
            lane = [e for e in _lane(tr, r) if _inside(e, tick)]
            assert {"rebalance", "stage", "commit"} <= {
                e["name"] for e in lane}


def test_sample_every_thins_the_fleet_spans():
    from repro.obs import SpanTracer
    tr = SpanTracer(sample_every=3)
    ticks = _drain(_traced_fleet(tr))
    ft = tr.spans("fleet.tick")
    assert [e["args"]["tick"] for e in ft] == list(range(0, ticks, 3))
    assert all(any(_inside(e, t) for t in ft)
               for e in _lane(tr, "fleet"))
    assert len(tr.spans("fused_dispatch")) <= len(ft) < ticks


def test_an_unsampled_or_detached_tick_reads_no_more_clock_than_untraced():
    """The null path: ticks that record nothing read the engine clocks
    exactly as often as a fleet that never had a tracer."""
    from repro.core.clock import WallClock
    from repro.obs import NULL_TRACER, SpanTracer

    reads = []

    class Counting(WallClock):
        def now_s(self):
            reads[-1] += 1
            return super().now_s()

    def per_tick(gw, n):
        out = []
        for _ in range(n):
            reads.append(0)
            gw.tick()
            out.append(reads.pop())
        return out

    reads.append(0)
    plain = per_tick(_traced_fleet(clock=Counting), 6)
    tr = SpanTracer(sample_every=1000)
    reads.append(0)
    gw = _traced_fleet(tr, clock=Counting)
    traced = per_tick(gw, 3)
    gw.attach_obs(tracer=NULL_TRACER)
    assert gw.tracer is NULL_TRACER and gw._fleet.tracer is NULL_TRACER
    assert all(r.tracer is NULL_TRACER for r in gw.replicas)
    traced += per_tick(gw, 3)
    assert traced[0] > plain[0]                # tick 0 is sampled
    assert traced[1:] == plain[1:]
    assert len(tr.spans("fleet.tick")) == 1


# ---------------------------------------------------------------------------
# slow: full-length library + shard_map on a forced multi-device host mesh
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_parallel_tick_full_scenario_library():
    from repro.simulate import SCENARIOS
    for name in sorted(SCENARIOS):
        if name in ("soak_churn",   # 2000 ticks x2: soak job budget
                    "city_scale"):  # 10k streams x2: parity is pinned at
            continue                # cell granularity in test_cells.py
        s = get_scenario(name)
        try:
            assert_bit_identical(run_scenario(s),
                                 run_scenario(s, parallel=True))
        except AssertionError as e:
            raise AssertionError(f"scenario {name!r}: {e}") from e


_SHARD_MAP_PROBE = """
import jax
assert len(jax.devices()) == 8, jax.devices()
from repro.simulate import get_scenario, run_scenario
s = get_scenario("heterogeneous_fleet", ticks=60)
ser = run_scenario(s)
par = run_scenario(s, parallel=True, fleet_mode="shard_map")
assert par.scenario is s
assert not par.violations, par.violations
assert ser.digest == par.digest, (ser.digest, par.digest)
print("SHARD_MAP_PARITY_OK")
"""


@pytest.mark.slow
def test_shard_map_mode_parity_on_forced_device_mesh():
    """shard_map over a real ("replica",) mesh (8 forced host devices)
    must match the serial digest bit-for-bit, like vmap does."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = (os.path.abspath("src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _SHARD_MAP_PROBE],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHARD_MAP_PARITY_OK" in proc.stdout


_SPREAD_PROBE = """
import jax
import pytest
assert len(jax.devices()) == 2, jax.devices()
from repro.simulate import ReplicaSpec, get_scenario, run_scenario
from repro.simulate.runner import ScenarioRunner
from repro.streams.fleet_step import replica_mesh
# 4 replicas on 2 devices: each device vmaps two; r1 fails at tick 60 and
# its streams rebind onto replicas of both devices, gate refs included
s = get_scenario("replica_failure", ticks=80,
                 replicas=tuple(ReplicaSpec(f"r{i}", slots=4)
                                for i in range(4)))
ser = run_scenario(s)
runner = ScenarioRunner(s, parallel=True, fleet_mode="shard_map")
par = runner.run()
assert not par.violations, par.violations
assert ser.summary["rebinds"] > 0
assert ser.digest == par.digest, (ser.digest, par.digest)
devs = [sorted(d.id for d in r.batches["outer"].devices())
        for r in runner.gw.replicas]
assert devs == [[0], [0], [1], [1]], devs
with pytest.raises(ValueError, match="spread evenly"):
    replica_mesh(3)
print("SPREAD_PARITY_OK")
"""


@pytest.mark.slow
def test_shard_map_spreads_replicas_over_fewer_devices():
    """A fleet larger than the device count spreads R/D replicas per
    device (never all on device 0), keeps each replica's state on its
    device across rebinds, matches the serial digest, and refuses a fleet
    that does not divide evenly."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2")
    env["PYTHONPATH"] = (os.path.abspath("src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _SPREAD_PROBE],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SPREAD_PARITY_OK" in proc.stdout
