"""The benchmark's arithmetic, on the CPU: turnaround and frame rate from
the per-stream counters, the work functions against the shapes the
program reports, and the peaks table."""
from __future__ import annotations

import sys
from collections import deque
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parents[1] / "src"), str(BENCH)]

from chipbench import drive, harness, layers, peaks, spec, work  # noqa: E402

REF = spec.load_module(BENCH / "references" / "eda_vision.py")


class Clock:
    def __init__(self) -> None:
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def sleep(self, dt: float) -> None:
        self.t += dt


class FakeGateway:
    """Duck-typed ``FleetGateway``: each tick takes ``tick_s`` on the
    clock and resolves the oldest frame of every stream; every third
    frame of a stream is gated; a stream holding more than ``limit``
    frames sheds the next push; ``trim`` drops all but the newest frame
    of a stream before it resolves one (a deadline trim)."""

    def __init__(self, clock, tick_s: float, limit: int = 99,
                 trim: bool = False) -> None:
        self.clock, self.tick_s, self.limit, self.trim = (clock, tick_s,
                                                          limit, trim)
        self.replicas = [SimpleNamespace(name="r0", streams={})]
        self._fleet = SimpleNamespace(dispatches=0, last_dispatch_s=0.0,
                                      last_masks=None)
        self.sessions = {}

    def join(self, vehicle):
        pair = []
        for kind in drive.KINDS:
            key = f"{vehicle}/{kind}"
            self.replicas[0].streams[key] = SimpleNamespace(
                processed=0, gated=0, deadline_dropped=0, lane=len(
                    self.replicas[0].streams), bound=True, pending=deque())
            pair.append(SimpleNamespace(engine="r0", key=key))
        self.sessions[vehicle] = pair
        return pair

    def push(self, vehicle, outer, inner):
        ok = []
        for sess in self.sessions[vehicle]:
            st = self.replicas[0].streams[sess.key]
            if len(st.pending) >= self.limit:
                ok.append(False)
            else:
                st.pending.append(1)
                ok.append(True)
        return tuple(ok)

    def tick(self):
        self.clock.t += self.tick_s
        self._fleet.dispatches += 1
        self._fleet.last_dispatch_s = self.tick_s / 2
        for st in self.replicas[0].streams.values():
            if self.trim:
                while len(st.pending) > 1:
                    st.pending.popleft()
                    st.deadline_dropped += 1
            if st.pending:
                st.pending.popleft()
                n = st.processed + st.gated
                if n % 3 == 2:
                    st.gated += 1
                else:
                    st.processed += 1


def _driver(gw, clock, traffic):
    cfg = {"replicas": 1, "slots": 4, "fps": 30}
    pools = {k: np.zeros((5, 1, 1, 3), np.float32) for k in drive.KINDS}
    return drive.Driver(gw, cfg, traffic, pools, clock=clock,
                        sleep=clock.sleep)


def test_closed_loop_counts_every_frame_and_times_it_from_its_push():
    clock = Clock()
    gw = FakeGateway(clock, tick_s=0.02)
    d = _driver(gw, clock, {"loop": "closed", "vehicles": 2})
    d.join()
    rec = drive.Record(start=clock())
    d.run(until=1.0, window_from=0.0, rec=rec)
    rec.end = clock()
    d.drain(rec, limit_s=1.0)
    # 50 ticks of 20 ms, 4 streams resolved per tick
    assert len(rec.ticks) == 50 and rec.resolved == 200
    assert rec.attempted == 200 and rec.failed == 0
    assert harness.end_to_end("frames_per_s", rec, 0.0) == pytest.approx(200)
    assert np.allclose(rec.turnaround_s, 0.02)
    assert rec.admitted["outer"] + rec.admitted["inner"] == 4 * 34
    assert layers.host_ms_per_tick(_view(rec)) == pytest.approx(10.0)
    assert layers.dispatch_ms(_view(rec)) == pytest.approx(10.0)


def test_open_loop_times_from_the_schedule_and_counts_shed_frames_failed():
    clock = Clock()
    # ticks of 50 ms against captures every 1/30 s: the backlog grows,
    # and a stream holding 3 frames sheds the next
    gw = FakeGateway(clock, tick_s=0.05, limit=3)
    d = _driver(gw, clock, {"loop": "open", "vehicles": 1, "fps": 30})
    d.join()
    d.start_schedule()
    rec = drive.Record(start=clock())
    d.run(until=1.0, window_from=0.0, rec=rec)
    rec.end = clock()
    d.drain(rec, limit_s=10.0)
    # every capture due before the last tick started, both cameras
    assert rec.attempted == 2 * d.next_j and d.next_j >= 29
    shed = rec.attempted - len(rec.turnaround_s)
    assert shed > 0 and rec.failed == shed
    # every resolved frame waited at least one tick from its schedule
    assert min(rec.turnaround_s) >= 0.05 - 1e-9
    lat = np.asarray(rec.turnaround_s)
    p95 = harness.end_to_end("turnaround_p95_ms", rec, 0.0)
    assert p95 == pytest.approx(1e3 * np.percentile(lat, 95))
    assert harness.end_to_end("turnaround_p50_ms", rec, 0.0) <= p95
    assert max(rec.lateness_s) < 0.05 + 1e-9


def test_deadline_trims_fail_the_oldest_frames():
    clock = Clock()
    gw = FakeGateway(clock, tick_s=0.1, trim=True)
    d = _driver(gw, clock, {"loop": "open", "vehicles": 1, "fps": 30})
    d.join()
    d.start_schedule()
    rec = drive.Record(start=clock())
    d.run(until=1.0, window_from=0.0, rec=rec)
    rec.end = clock()
    d.drain(rec, limit_s=10.0)
    assert rec.failed > 0
    assert rec.failed + len(rec.turnaround_s) == rec.attempted
    # only the newest frame survives a trim: its wait is under two ticks
    assert max(rec.turnaround_s) < 0.2 + 1e-9


def test_a_schedule_started_anew_after_the_warm_traffic_carries_no_backlog():
    clock = Clock()
    # ticks of 50 ms against captures every 1/30 s: the warm traffic
    # leaves a backlog behind
    gw = FakeGateway(clock, tick_s=0.05)
    d = _driver(gw, clock, {"loop": "open", "vehicles": 1, "fps": 30})
    d.join()
    d.start_schedule()
    warm = drive.Record()
    d.run(until=1.0, window_from=float("inf"), rec=warm)
    assert d.has_work()
    d.drain(warm, limit_s=10.0)
    d.start_schedule()
    rec = drive.Record(start=clock())
    d.run(until=rec.start + 0.2, window_from=rec.start, rec=rec)
    assert d.next_j >= 4 and rec.attempted == 2 * d.next_j
    assert max(rec.lateness_s) < 0.05 + 1e-9


def test_the_sampler_keeps_one_tick_in_each_stretch_of_the_window():
    import jax
    import jax.numpy as jnp
    clock = Clock()
    gw = FakeGateway(clock, tick_s=0.02)
    r = gw.replicas[0]
    r.batches = {k: jnp.zeros((4, 2, 2, 3)) for k in drive.KINDS}
    r.gates = {k: SimpleNamespace(refs=jnp.zeros((4, 2, 2)),
                                  thresh=np.zeros(4),
                                  has_ref=np.zeros(4, bool))
               for k in drive.KINDS}
    gw._fleet.last_masks = np.zeros((4, 4), bool)

    class Timed(drive.Sampler):
        at: list = []

        def _state(self, before: bool) -> list:
            if before:
                self.at.append(self.clock())
            return super()._state(before)

    picks = []
    for seed in (3, 3, 4):
        s = Timed(gw, 4, seed, clock=clock)
        s.at = []
        d = _driver(gw, clock, {"loop": "closed", "vehicles": 2})
        d.sampler = s
        d.join()
        rec = drive.Record(start=clock())
        s.arm(rec.start, 1.0)
        due = [t - rec.start for t in s.due]
        d.run(until=rec.start + 1.0, window_from=rec.start, rec=rec)
        s.stop()
        d.drain(rec, limit_s=1.0)
        kept = s.host()
        assert len(kept) == 4
        assert [int(t // 0.25) for t in due] == [0, 1, 2, 3]
        # the first tick to start after each drawn time is kept
        assert all(0 <= a - rec.start - t <= 0.02 + 1e-9
                   for a, t in zip(s.at, due))
        assert not any(isinstance(x, jax.Array)
                       for x in jax.tree.leaves(kept))
        picks.append(due)
    assert np.allclose(picks[0], picks[1])
    assert not np.allclose(picks[0], picks[2])


def _view(rec, red=None):
    cfg = {"frame_res": 32, "input_res": 16, "gate_res": 8,
           "detector": {"channels": [16, 32, 64, 128, 256],
                        "num_classes": 10, "num_anchors": 4},
           "pose": {"channels": [16, 32, 64, 128, 256],
                    "num_keypoints": 17}}
    return harness.run_view(cfg, REF, rec, red, peaks.peaks("TPU v5 lite"),
                            chips=1)


def test_readers_return_nothing_without_a_trace():
    rec = drive.Record(ticks=[(0.01, 0.005)],
                       active={"outer": 2, "inner": 2},
                       admitted={"outer": 2, "inner": 1})
    view = _view(rec)
    assert layers.device_idle_share(view) is None
    assert layers.ingest_roofline(view, "_ingest_frame_jit") is None
    assert layers.mfu(view) > 0


def test_work_functions_match_the_shapes_the_program_reports():
    import jax.numpy as jnp
    from repro.kernels import vision_ops
    S, H, m, g = 3, 32, 16, 8
    frames = jnp.zeros((S, H, H, 3), jnp.float32)
    refs = jnp.zeros((S, g, g, 3), jnp.float32)
    model, gate, scores = vision_ops.ingest_frame(
        frames, refs, model_res=m, gate_res=g, block=4, interpret=True)
    moved = sum(x.nbytes for x in (frames, refs, model, gate, scores))
    assert work.ingest_bytes(S, H, m, g) == moved


@pytest.mark.parametrize("res", [64, 192, 256])
def test_model_flops_copy_matches_the_program(res):
    """The reference's count is ``repro.models.vision.model_flops``, for
    the program's own models and at the configuration's widths."""
    import dataclasses
    from repro.configs.eda_vision import detector_config, pose_config
    from repro.models import vision as V
    cfg = spec.resolve("eda192-motion-sat").config
    coco = dataclasses.replace(detector_config(res),
                               channels=tuple(cfg["detector"]["channels"]),
                               num_classes=90, num_anchors=6)
    wide_pose = dataclasses.replace(pose_config(res),
                                    channels=coco.channels)
    for det, pose in ((detector_config(res), pose_config(res)),
                      (coco, wide_pose)):
        models = {"input_res": res,
                  "detector": {"channels": list(det.channels),
                               "num_classes": det.num_classes,
                               "num_anchors": det.num_anchors},
                  "pose": {"channels": list(pose.channels),
                           "num_keypoints": pose.num_keypoints}}
        assert REF.model_flops(models, "outer") == V.model_flops(det)
        assert REF.model_flops(models, "inner") == V.model_flops(pose)


@pytest.mark.parametrize("cell", ["eda192-motion-sat", "eda192-motion-25fps",
                                  "eda192x4-motion-sat"])
def test_eda_fleet_192_reads_its_golden_work_counts(cell):
    """Operations of a detector and of a pose frame, and bytes of one
    frame's ingest, as the cells have read them since they were set."""
    cfg = spec.resolve(cell).config
    ref = spec.load_module(spec.resolve(cell).reference)
    assert ref.model_flops(cfg, "outer") == 2_463_547_392
    assert ref.model_flops(cfg, "inner") == 995_770_368
    for kind in drive.KINDS:
        assert work.ingest_bytes(1, cfg["frame_res"], spec.model_res(cfg, kind),
                                 cfg["gate_res"]) == 2_236_420
    # the window's ingest: each camera's frames at its resolution
    rec = drive.Record(active={"outer": 3, "inner": 2})
    red = SimpleNamespace(kernel_s=lambda part: 1.0)
    peak = peaks.peaks("TPU v5 lite")
    view = harness.run_view(cfg, ref, rec, red, peak, chips=1)
    least, bound = work.least_time_s(5 * 2_236_420,
                                     work.ingest_flops(5, 32), peak)
    assert bound == "memory"
    assert layers.ingest_roofline(view, "x") == 100.0 * least


def test_least_time_names_its_bound():
    p = peaks.peaks("TPU v5 lite")
    t, bound = work.least_time_s(819e9, 1.0, p)
    assert bound == "memory" and t == pytest.approx(1.0)
    t, bound = work.least_time_s(1.0, 197e12, p)
    assert bound == "compute" and t == pytest.approx(1.0)


def test_peaks_refuse_an_unknown_device_kind():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v99")
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
