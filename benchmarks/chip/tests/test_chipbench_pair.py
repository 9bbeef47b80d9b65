"""The paper's published pair as a configuration of its own
(``eda-fleet-ssd300-movenet``): the program's SSD-MobileNetV1 and MoveNet
against the plain reference ``references/ssd300_movenet.py`` at small
sizes on the CPU, the reference's work counts against the papers' tables,
its weights against the program's tree at the published sizes, the new
metric reader, and a whole shrunk run of the cell."""
from __future__ import annotations

import copy
import dataclasses
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny as tiny

from chipbench import drive, fleet, frames, spec

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
CELL = "ssd300-movenet-motion-sat"
REF = spec.load_module(BENCH / "references" / "ssd300_movenet.py")
CFG = json.loads((BENCH / "configs" / "eda-fleet-ssd300-movenet.json")
                 .read_text())
# the layouts at a CPU test's depth and widths: SSD with three blocks, maps
# at the last two and two extra layers; MoveNet with three rows under a
# two-level pyramid
SMALL = {"detector": dict(input_res=48, channels=[8, 16, 16, 32],
                          stride_blocks=[1, 2, 3], ssd_taps=[2, 3],
                          ssd_extras=[16, 8], ssd_boxes=[3, 6, 6, 6]),
         "pose": dict(input_res=32, channels=[8], fpn_width=8,
                      bottlenecks=[[1, 8, 1, 1], [6, 8, 2, 2], [6, 16, 1, 2]],
                      fpn_taps=[2, 3])}
# every convolution in float32, as the CPU runs the program's
FLOAT32 = {"default": ["float32", "float32"]}


def _small(precision=None) -> dict:
    cfg = copy.deepcopy(CFG)
    for section, sizes in SMALL.items():
        cfg[section].update(sizes)
    cfg["precision"]["conv_operands"] = precision or FLOAT32
    return cfg


def _models(cfg):
    """The program's model configs, as the harness installs them."""
    from repro.configs.eda_vision import detector_config, pose_config
    r = SimpleNamespace(dc=detector_config(64), pc=pose_config(64))
    fleet.set_models(cfg, r)
    return r.dc, r.pc


@pytest.fixture(scope="module")
def small():
    cfg = _small()
    mix = json.loads((BENCH / "traffic" / "motion-sat-pair.json").read_text())
    rows = {k: REF.downscale(jnp.asarray(frames.make_pool(
        mix["cameras"][k], frames=24, res=96, seed=3 + i)),
        spec.model_res(cfg, k)) for i, k in enumerate(drive.KINDS)}
    return cfg, rows, REF.make_params(cfg, 11, rows)


def test_the_detector_matches_the_reference(small):
    """Class scores and box encodings of every anchor, and each frame's
    hazard flag.  Both sides compute float32 convolutions on the CPU, so
    they agree to float32 rounding (1e-5 relative); a flag is compared
    where the reference's margin clears 1e-4, which that rounding cannot
    cross."""
    from repro.models import vision as V
    cfg, rows, params = small
    dc, _ = _models(cfg)
    raw = REF.ssd_raw(params["outer"], rows["outer"], cfg["detector"],
                      REF.operand_precision(cfg), False)
    got = V.detector_apply(dc, params["outer"], rows["outer"])
    C = dc.num_classes + 1
    np.testing.assert_allclose(got["boxes"], raw[..., C:], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(got["scores"],
                               jax.nn.softmax(raw[..., :C], axis=-1),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_array_equal(V.ssd_anchors(dc),
                                  REF.anchors(cfg["detector"], dc.input_res))
    margin = np.asarray(REF.margins(params, rows["outer"], cfg, "outer"))
    flags = np.asarray(V.analyse_outer(dc, params["outer"],
                                       rows["outer"])[0]).any(axis=1)
    clear = np.abs(margin) > 1e-4
    assert clear.mean() > 0.8 and 0 < flags.mean() < 1
    np.testing.assert_array_equal(flags[clear], margin[clear] > 0)


def test_the_pose_model_matches_the_reference(small):
    """The four heads' outputs and each frame's distraction flag, with the
    tolerances of the detector's test and for its reasons."""
    from repro.models import vision as V
    cfg, rows, params = small
    _, pc = _models(cfg)
    want = REF.movenet_heads(params["inner"], rows["inner"], cfg["pose"],
                             REF.operand_precision(cfg), False)
    got = V.centernet_heads(pc, params["inner"], rows["inner"])
    for name in REF.HEADS:
        np.testing.assert_allclose(got[name], want[name], rtol=1e-5,
                                   atol=1e-6, err_msg=name)
    margin = np.asarray(REF.margins(params, rows["inner"], cfg, "inner"))
    flags = np.asarray(V.analyse_inner(pc, params["inner"],
                                       rows["inner"])[0])
    clear = np.abs(margin) > 1e-4
    assert clear.mean() > 0.5 and 0 < flags.mean() < 1
    np.testing.assert_array_equal(flags[clear], margin[clear] > 0)


def test_the_reference_counts_the_papers_operations():
    """SSD 300 on MobileNet: 1.2 billion multiply-adds (arXiv:1704.04861
    Table 13).  MobileNetV2 1.0 at 224: 300 million (arXiv:1801.04381
    Table 4), which counts the 1x1 convolution to 1280 and the classifier
    that MoveNet's trunk does without.  The program counts the same."""
    from repro.models import vision as V
    assert abs(REF.model_flops(CFG, "outer") / 2.4e9 - 1) < 0.05
    tail = 2 * 7 * 7 * 320 * 1280 + 2 * 1280 * 1000
    assert abs((REF.trunk_flops(CFG["pose"], 224) + tail) / 600e6 - 1) < 0.05
    dc, pc = _models(CFG)
    assert REF.model_flops(CFG, "outer") == V.model_flops(dc)
    assert REF.model_flops(CFG, "inner") == V.model_flops(pc)


def test_the_reference_weights_fit_the_program_at_the_published_sizes():
    from repro.models import vision as V
    dc, pc = _models(CFG)
    key = jax.random.key(0)
    want = REF.shapes(CFG)
    shape = lambda t: jax.tree.map(np.shape, t)  # noqa: E731
    as_tuples = lambda t: jax.tree.map(  # noqa: E731
        tuple, t, is_leaf=lambda x: isinstance(x, tuple))
    assert as_tuples(want["outer"]) == shape(jax.eval_shape(
        lambda k: V.init_detector(dc, k), key))
    assert as_tuples(want["inner"]) == shape(jax.eval_shape(
        lambda k: V.init_pose(pc, k), key))
    assert REF.anchors(CFG["detector"], 300).shape == (1917, 4)


def test_the_configuration_is_the_published_pair():
    from repro.configs.eda_vision import (movenet_lightning_config,
                                          ssd_mobilenet_v1_config)
    dc, pc = _models(CFG)
    published = {"outer": ssd_mobilenet_v1_config(),
                 "inner": movenet_lightning_config()}
    for kind, vc in (("outer", dc), ("inner", pc)):
        want = published[kind]
        for f in dataclasses.fields(vc):
            if f.name != "name":
                assert getattr(vc, f.name) == getattr(want, f.name), f.name
    assert (spec.model_res(CFG, "outer"), spec.model_res(CFG, "inner")) == (
        300, 192)
    bench = spec.load_benchmark()
    entry = {c["name"]: c for c in bench["configs"]}[CFG["name"]]
    assert entry["reduced"] == list(CFG["reduced"]) == ["frame_res"]
    cell = spec.resolve(CELL)
    assert cell.chips == 1 and cell.reference == (
        BENCH / "references" / "ssd300_movenet.py")
    assert sorted(m["name"] for m in cell.per_layer) == sorted(
        f"{n}.pair" for n in ("host_ms_per_tick", "dispatch_ms",
                              "ingest_frame_roofline", "mfu",
                              "device_idle_share", "model_device_ms"))
    assert [m["name"] for m in cell.end_to_end] == ["frames_per_s",
                                                    "setup_s"]


def test_the_engine_shapes_each_cameras_pool_to_its_section():
    """``fleet.engines`` builds each engine at the file's resolution;
    installing the sections' models gives each camera its own plane, and
    the harness's shape check holds as it is."""
    cfg = dict(_small(), **tiny.SHRINK)
    eng = fleet.engines(dict(cfg, replicas=1))[0]
    fleet.set_models(cfg, eng)
    params = jax.tree.map(lambda s: np.zeros(s, np.float32), REF.shapes(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    fleet.check_shapes(cfg, eng, params)
    assert eng.batches["outer"].shape[1:3] == (48, 48)
    assert eng.batches["inner"].shape[1:3] == (32, 32)


def test_model_device_ms_reads_the_device_time_besides_the_ingest():
    reader = spec.load_reader(spec.find_reader("model_device_ms.pair"))
    rec = drive.Record(ticks=[(0.03, 0.02), (0.03, 0.0), (0.03, 0.02)])
    red = SimpleNamespace(devices=2, op_s={
        "fusion.3": 0.010, "convolution.7": 0.030,
        "jit__ingest_frame_jit_/pallas_call": 0.5, "copy.1": 0.002})
    view = SimpleNamespace(rec=rec, red=red)
    # 42 ms of other device time over two dispatching ticks and two chips
    assert reader(view) == pytest.approx(1e3 * 0.042 / (2 * 2))
    assert reader(SimpleNamespace(rec=rec, red=None)) is None
    idle = drive.Record(ticks=[(0.03, 0.0)])
    assert reader(SimpleNamespace(rec=idle, red=red)) is None


@pytest.fixture(scope="module")
def tiny_run():
    c = tiny.cell(CELL)
    for section, sizes in SMALL.items():
        c.config[section].update(sizes)
    return tiny.run(c, control=True)


def test_a_tiny_pair_run_is_correct(tiny_run):
    assert tiny_run["correct"] is True, tiny_run["checks"]
    assert tiny_run["checks"]["ingest_max_abs_err"]["value"] == 0.0
    assert tiny_run["checks"]["flags_checked"]["value"] > 0
    assert tiny_run["metrics"]["frames_per_s"]["value"] > 0


def test_the_tiny_pair_runs_control_is_not_correct(tiny_run):
    assert tiny_run["control"]["correct"] is False


def test_the_pair_reads_its_golden_work_counts():
    """Operations of an SSD and of a MoveNet frame, and bytes of one
    frame's ingest at each camera's plane, as the cell reads them."""
    from chipbench import work
    cfg = spec.resolve(CELL).config
    assert REF.model_flops(cfg, "outer") == 2_474_258_816
    assert REF.model_flops(cfg, "inner") == 459_242_496
    planes = [work.ingest_bytes(1, cfg["frame_res"], spec.model_res(cfg, k),
                                cfg["gate_res"]) for k in drive.KINDS]
    assert planes == [2_874_052, 2_236_420]
