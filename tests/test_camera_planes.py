"""A model plane per camera: each camera's batch pool and ingest take the
resolution of the model installed for it, and the fused fleet tick keeps
bit parity with the serial tick when the two cameras' planes differ (here
an SSD at 48 px beside a MoveNet at 32 px, neither of which divides the
64 px frames evenly in both)."""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.eda_vision import (detector_config,
                                      movenet_lightning_config, pose_config,
                                      ssd_mobilenet_v1_config)
from repro.models import vision as V
from repro.streams import FleetGateway, VisionServeEngine
from repro.streams.vision_engine import INNER, OUTER

FRAME = 64


def tiny_ssd(res: int = 48):
    """An SSD-MobileNetV1 of a CPU test's depth and widths: three blocks,
    maps at the last two and two extra layers."""
    return dataclasses.replace(
        ssd_mobilenet_v1_config(res), channels=(8, 16, 16, 32),
        stride_blocks=(1, 2, 3), ssd_taps=(2, 3), ssd_extras=(16, 8),
        ssd_boxes=(3, 6, 6, 6), num_classes=10)


def tiny_movenet(res: int = 32):
    """A MoveNet of a CPU test's depth and widths: three inverted-residual
    rows under a two-level pyramid."""
    return dataclasses.replace(
        movenet_lightning_config(res), channels=(8,), fpn_width=8,
        bottlenecks=((1, 8, 1, 1), (6, 8, 2, 2), (6, 16, 1, 2)),
        fpn_taps=(2, 3))


def weights(init, cfg, seed: int):
    """Seeded normal weights of the tree ``init`` builds, made on the
    host (no compile for each leaf's shape)."""
    rng = np.random.default_rng(seed)
    tree = jax.eval_shape(lambda k: init(cfg, k), jax.random.key(0))
    return jax.tree.map(
        lambda s: (rng.standard_normal(s.shape)
                   / np.sqrt(np.prod(s.shape[:-1]) or 1)).astype(np.float32),
        tree)


def _engine(name="r0", **kw):
    return VisionServeEngine(name, slots=2, frame_res=FRAME, input_res=32,
                             use_pallas=True, use_gate=True,
                             rng=jax.random.key(0), **kw)


def test_installing_a_model_shapes_its_cameras_pool():
    r = _engine()
    assert r.batches[OUTER].shape == r.batches[INNER].shape == (2, 32, 32, 3)
    r.dc = tiny_ssd(48)
    assert r.model_res(OUTER) == 48 and r.model_res(INNER) == 32
    assert r.batches[OUTER].shape == (2, 48, 48, 3)
    assert r.batches[INNER].shape == (2, 32, 32, 3)
    pool = r.batches[INNER]
    r.pc = tiny_movenet(32)              # the same plane: the pool stays
    assert r.batches[INNER] is pool


def test_grid_models_and_tiers_keep_the_engines_plane():
    """The program's grid-head models take the engine's resolution (a
    tier scales them by it), and a tier pins one plane for both cameras
    whatever model is installed."""
    r = _engine()
    r.dc = dataclasses.replace(detector_config(32), input_res=48)
    assert r.model_res(OUTER) == 32
    assert r.batches[OUTER].shape == (2, 32, 32, 3)
    t = _engine(tier="high")
    t.dc = tiny_ssd(32)
    assert t.model_res(OUTER) == t.input_res == 48
    assert t.batches[OUTER].shape == (2, 48, 48, 3)


def _fleet(parallel: bool):
    reps = []
    for i in range(2):
        r = _engine(f"r{i}")
        r.dc, r.pc = tiny_ssd(48), tiny_movenet(32)
        r.dp = weights(V.init_detector, r.dc, 10 + i)
        r.pp = weights(V.init_pose, r.pc, 20 + i)
        reps.append(r)
    return FleetGateway(reps, parallel=parallel, fleet_mode="vmap")


def _run(parallel: bool):
    gw = _fleet(parallel)
    rng = np.random.default_rng(3)
    for v in range(2):
        gw.join(f"v{v}")
        for _ in range(3):
            o, n = (np.round(rng.random((FRAME, FRAME, 3)) * 255) / 255
                    for _ in range(2))
            gw.push(f"v{v}", o.astype(np.float32), n.astype(np.float32))
    gw.drain()
    return gw


@pytest.fixture(scope="module")
def serial_and_fused():
    """The serial and the fused gateway after the same pushes, run at
    set-up: each compiles its own ticks."""
    return _run(False), _run(True)


def test_fused_tick_matches_serial_with_a_plane_per_camera(serial_and_fused):
    ser, par = serial_and_fused
    assert par._fleet.dispatches > 0
    for a, b in zip(ser.replicas, par.replicas):
        for kind, res in ((OUTER, 48), (INNER, 32)):
            assert b.batches[kind].shape == (2, res, res, 3)
            np.testing.assert_array_equal(a.batches[kind], b.batches[kind])
            np.testing.assert_array_equal(a.gates[kind].refs,
                                          b.gates[kind].refs)
        assert a.streams.keys() == b.streams.keys()
        for key, st in a.streams.items():
            other = b.streams[key]
            assert (st.processed, st.gated, st.flagged) == (
                other.processed, other.gated, other.flagged)
            assert list(a.results[key]) == list(b.results[key])
    assert sum(st.processed for r in ser.replicas
               for st in r.streams.values()) > 0


@pytest.mark.parametrize("kind", [OUTER, INNER])
def test_each_camera_ingests_at_its_own_plane(kind):
    """The serial ingest writes the installed model's plane: the pool row
    of an admitted frame is the frame resampled to that resolution."""
    r = _engine()
    r.dc, r.pc = tiny_ssd(48), tiny_movenet(32)
    r.dp = weights(V.init_detector, r.dc, 1)
    r.pp = weights(V.init_pose, r.pc, 2)
    res = 48 if kind == OUTER else 32
    r.open_stream("cam", kind)
    frame = np.random.default_rng(1).random((FRAME, FRAME, 3)).astype(
        np.float32)
    r.push("cam", frame)
    r.step()
    lane = r.streams["cam"].lane
    want = V.downscale(frame[None], res)[0]
    np.testing.assert_array_equal(r.batches[kind][lane], want)
