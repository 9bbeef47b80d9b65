"""The benchmark's frame generator and its plain reference, against the
program's own outputs at small sizes (Pallas kernels interpreted)."""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parents[1] / "src"), str(BENCH)]

from chipbench import frames, spec  # noqa: E402

REF = spec.load_module(BENCH / "references" / "eda_vision.py")
CFG = json.loads((BENCH / "configs" / "eda-fleet-192.json").read_text())


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def test_pools_repeat_by_seed_and_hold_quantised_frames():
    cam = _mix("motion-sat")["cameras"]["outer"]
    big = 2 ** 33 + 5                     # seeds wider than 32 bits
    a = frames.make_pool(cam, frames=4, res=32, seed=frames.seed_words(big, 1)[0])
    b = frames.make_pool(cam, frames=4, res=32, seed=frames.seed_words(big, 1)[0])
    c = frames.make_pool(cam, frames=4, res=32, seed=frames.seed_words(big + 1, 1)[0])
    assert a.shape == (4, 32, 32, 3) and a.dtype == np.float32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() <= 1
    assert np.allclose(a * 255, np.round(a * 255), atol=1e-4)


@pytest.mark.parametrize("camera", ["outer", "inner"])
def test_driving_scenes_move_more_than_parked_ones(camera):
    def step(mix):
        p = frames.make_pool(_mix(mix)["cameras"][camera], frames=8, res=48,
                             seed=3)
        return np.abs(np.diff(p, axis=0)).mean()
    assert step("motion-sat") > 1.5 * step("parked-30fps")


def test_reference_ingest_matches_the_program_kernel():
    from repro.kernels import vision_ops
    cam = _mix("motion-sat")["cameras"]["outer"]
    pool = frames.make_pool(cam, frames=5, res=64, seed=11)
    x = jnp.asarray(pool[1:])
    refs = REF.downscale(jnp.asarray(pool[:-1]), 16)
    got = vision_ops.ingest_frame(x, refs, model_res=32, gate_res=16,
                                  block=4, interpret=True)
    want = REF.ingest(x, refs, model_res=32, gate_res=16, block=4)
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                               atol=1e-6)
    # the control's resample differs from both, by bfloat16 rounding
    ctl = REF.ingest(x, refs, model_res=32, gate_res=16, block=4,
                     control=True)
    err = np.abs(np.asarray(ctl[0]) - np.asarray(want[0])).max()
    assert 1e-7 < err < 1e-4


def _program_flags(kind, head, classes=10, anchors=4):
    """The program's flag rules applied to given head outputs: the steps
    of ``detector_apply`` / ``pose_apply`` after their head convolution,
    then ``decode_detections`` + ``flag_hazards`` / ``flag_distraction``."""
    from repro.configs.eda_vision import detector_config
    from repro.models import vision as V
    B, g = head.shape[0], head.shape[1]
    if kind == "outer":
        cfg = dataclasses.replace(detector_config(16 * g),
                                  num_classes=classes, num_anchors=anchors)
        C = cfg.num_classes + 1
        raw = head.reshape(B, g * g * cfg.num_anchors, C + 4)
        preds = {"scores": jax.nn.softmax(raw[..., :C], axis=-1),
                 "boxes": raw[..., C:], "grid": g}
        return V.flag_hazards(V.decode_detections(cfg, preds)).any(axis=1)
    flat = head.reshape(B, g * g, head.shape[-1])
    idx = jnp.argmax(flat, axis=1)
    kp = {"y": (idx // g + 0.5) / g, "x": (idx % g + 0.5) / g,
          "score": jax.nn.sigmoid(jnp.max(flat, axis=1))}
    return V.flag_distraction(kp)


# (camera, object classes, anchors a cell): the program's default head,
# and the configuration's COCO head
HEADS = {"outer": ("outer", 10, 4), "inner": ("inner", 0, 0),
         "outer-coco": ("outer", CFG["detector"]["num_classes"],
                        CFG["detector"]["num_anchors"])}


@pytest.mark.parametrize("head_kind", list(HEADS))
def test_flag_margins_carry_the_program_flag_rules(head_kind):
    """On the same head outputs, a margin's sign is the program's flag."""
    kind, classes, anchors = HEADS[head_kind]
    # a small detector grid, so that not every frame holds a hazard
    g, ch = (2, anchors * (classes + 5)) if kind == "outer" else (12, 17)
    head = 2.0 * jax.random.normal(jax.random.key(4), (256, g, g, ch))
    if kind == "outer":
        # the person bias of make_params, so that detections are kept
        # among many classes too
        bias = np.zeros(ch, np.float32)
        bias[0::classes + 5] = -1.0
        bias[1::classes + 5] = 1.0
        head = head + (2.0 if classes > 10 else 0.0) * bias
        margin = REF._outer_from_head(head, classes, anchors)
    else:
        margin = REF._inner_from_head(head)
    margin = np.asarray(margin)
    flags = np.asarray(_program_flags(kind, head, classes, anchors))
    assert 0.1 < flags.mean() < 0.9
    clear = np.abs(margin) > 1e-4
    assert clear.mean() > 0.9
    assert np.array_equal(flags[clear], margin[clear] > 0)


def test_head_biases_put_the_calibration_frames_at_the_flags_boundary():
    """The detector's median margin goes to zero.  The pose model's bias
    moves only the keypoints' confidence: where too few frames hold a
    wrist up or a glance down even with every keypoint confident, it
    ends at the top of its range, and no higher bias flags more."""
    res = 128
    rows = {k: REF.downscale(jnp.asarray(frames.make_pool(
        _mix("motion-sat")["cameras"][k], frames=8, res=256, seed=2)), res)
        for k in ("outer", "inner")}
    params = REF.make_params(CFG, 5, rows)
    margin = {k: np.asarray(REF.margins(params, rows[k], CFG, k))
              for k in ("outer", "inner")}
    assert abs(np.median(margin["outer"])) < 0.05
    bias = np.asarray(params["inner"]["head"]["b"])
    if abs(np.median(margin["inner"])) >= 0.05:
        assert np.all(bias == 32.0) and np.median(margin["inner"]) < 0
        higher = jax.tree.map(lambda x: x, params)
        higher["inner"]["head"]["b"] = bias + 8.0
        more = np.asarray(REF.margins(higher, rows["inner"], CFG, "inner"))
        assert np.sum(more > 0) == np.sum(margin["inner"] > 0)


def test_weights_fit_the_program_tree_and_repeat_by_seed():
    from repro.configs.eda_vision import detector_config, pose_config
    from repro.models import vision as V
    calib = {k: jnp.asarray(frames.make_pool(
        _mix("motion-sat")["cameras"][k], frames=2, res=48, seed=1))
        for k in ("outer", "inner")}
    a, b = REF.make_params(CFG, 9, calib), REF.make_params(CFG, 9, calib)
    d, p = CFG["detector"], CFG["pose"]
    dc = dataclasses.replace(detector_config(192),
                             channels=tuple(d["channels"]),
                             num_classes=d["num_classes"],
                             num_anchors=d["num_anchors"])
    pc = dataclasses.replace(pose_config(192), channels=tuple(p["channels"]))
    shape = lambda t: jax.tree.map(np.shape, t)  # noqa: E731
    key = jax.random.key(0)
    assert shape(a["outer"]) == shape(jax.eval_shape(
        lambda k: V.init_detector(dc, k), key))
    assert shape(a["inner"]) == shape(jax.eval_shape(
        lambda k: V.init_pose(pc, k), key))
    assert all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_conv_operand_precision_is_read_layer_then_kind_then_rest():
    prec = REF.operand_precision(CFG)
    assert REF._precisions(prec, "dw1") == ("bfloat16", "float32")
    assert REF._precisions(prec, "dw7") == ("float32", "float32")
    assert REF._precisions(prec, "pw7") == ("bfloat16", "bfloat16")
    assert REF._precisions(prec, "stem") == ("bfloat16", "bfloat16")
    x = jnp.asarray([1.0 + 2.0 ** -10, 1.0 + 2.0 ** -5], jnp.float32)
    # stated bfloat16 -> float8 for the control; float32 -> bfloat16
    assert np.asarray(REF.ROUND["float32"](x)).tolist() == x.tolist()
    assert np.asarray(REF.ROUND["bfloat16"](x)).tolist() == [1.0, 1.03125]
    assert np.asarray(REF.ROUND_CONTROL["float32"](x)).tolist() == [1.0, 1.03125]
    assert np.asarray(REF.ROUND_CONTROL["bfloat16"](x)).tolist() == [1.0, 1.0]
