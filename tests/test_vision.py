"""Paper workloads (detector/pose) + flag-logic unit tests."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.eda_vision import (detector_config,
                                      movenet_lightning_config, pose_config,
                                      ssd_mobilenet_v1_config)
from repro.models import vision as V


@pytest.fixture(scope="module")
def models():
    rng = jax.random.key(0)
    dc, pc = detector_config(96), pose_config(96)
    return dc, V.init_detector(dc, rng), pc, V.init_pose(pc, rng)


def test_outer_pipeline_shapes(models):
    dc, dp, _, _ = models
    frames = jax.random.uniform(jax.random.key(1), (3, 128, 128, 3))
    flags, det = V.analyse_outer(dc, dp, frames)
    n = (dc.input_res // 16) ** 2 * dc.num_anchors
    assert flags.shape == (3, n) and flags.dtype == jnp.bool_
    assert det["score"].shape == (3, n)
    assert bool(jnp.isfinite(det["score"]).all())
    assert bool((det["score"] >= 0).all() and (det["score"] <= 1).all())


def test_inner_pipeline_shapes(models):
    _, _, pc, pp = models
    frames = jax.random.uniform(jax.random.key(2), (2, 64, 64, 3))
    distracted, kp = V.analyse_inner(pc, pp, frames)
    assert distracted.shape == (2,)
    assert kp["y"].shape == (2, pc.num_keypoints)
    assert bool((kp["y"] >= 0).all() and (kp["y"] <= 1).all())


def test_hazard_flag_logic():
    det = {
        "cls": jnp.asarray([[5, 2, 5, 2]]),            # person-ish, car, ...
        "score": jnp.asarray([[0.9, 0.9, 0.9, 0.9]]),
        "keep": jnp.asarray([[True, True, True, False]]),
        "cy": jnp.asarray([[0.8, 0.3, 0.2, 0.8]]),
        "cx": jnp.asarray([[0.5, 0.5, 0.5, 0.5]]),
        "h": jnp.asarray([[0.1, 0.1, 0.1, 0.9]]),
        "w": jnp.asarray([[0.1, 0.1, 0.1, 0.9]]),
    }
    flags = V.flag_hazards(det)
    # [0]: non-vehicle on road -> hazard; [1]: small vehicle off road -> no;
    # [2]: non-vehicle off-road -> no; [3]: huge vehicle but keep=False -> no
    assert flags.tolist() == [[True, False, False, False]]


def test_tailgate_flag():
    det = {
        "cls": jnp.asarray([[2]]), "score": jnp.asarray([[0.9]]),
        "keep": jnp.asarray([[True]]),
        "cy": jnp.asarray([[0.7]]), "cx": jnp.asarray([[0.5]]),
        "h": jnp.asarray([[0.6]]), "w": jnp.asarray([[0.5]]),
    }
    assert V.flag_hazards(det).tolist() == [[True]]    # area 0.3 > 0.18


def test_distraction_flag_logic():
    K = 17
    base_y = jnp.full((1, K), 0.6)
    base_score = jnp.full((1, K), 0.9)
    kp = {"y": base_y, "x": jnp.full((1, K), 0.5), "score": base_score}
    assert not bool(V.flag_distraction(kp)[0])

    # hand raised to ear (above 3/4 frame height)
    kp_hand = dict(kp, y=base_y.at[0, V.KP_LEFT_WRIST].set(0.1))
    assert bool(V.flag_distraction(kp_hand)[0])

    # eyes below ears (glance down)
    y2 = base_y.at[0, V.KP_LEFT_EYE].set(0.55).at[0, V.KP_RIGHT_EYE].set(0.55)
    y2 = y2.at[0, V.KP_LEFT_EAR].set(0.45).at[0, V.KP_RIGHT_EAR].set(0.45)
    assert bool(V.flag_distraction(dict(kp, y=y2))[0])

    # same posture but low-confidence eyes -> not flagged
    sc = base_score.at[0, V.KP_LEFT_EYE].set(0.1)
    assert not bool(V.flag_distraction(dict(kp, y=y2, score=sc))[0])


def test_downscale_matches_paper_behaviour():
    frames = jnp.arange(2 * 64 * 64 * 3, dtype=jnp.float32).reshape(2, 64, 64, 3)
    small = V.downscale(frames, 16)
    assert small.shape == (2, 16, 16, 3)
    # nearest-neighbour: values are a subset of the original
    assert bool(jnp.isin(small[0, 0, 0, 0], frames).all())


def test_flops_counts_positive_and_scale_with_res():
    d1, d2 = detector_config(96), detector_config(192)
    assert V.model_flops(d2) > 3 * V.model_flops(d1)
    assert V.model_flops(pose_config(96)) > 0


# ---------------------------------------------------------------------------
# the architecture fields: defaults, the published pair, the FLOP count
# ---------------------------------------------------------------------------


def _legacy_raw(p, x, head_res):
    """Today's eda-detector / eda-pose written out: a 3x3 stride-2 stem,
    depthwise-separable blocks striding in their first three, ReLU6, one
    3x3 head on the last map."""
    def conv(q, y, s=1, g=1):
        return jax.lax.conv_general_dilated(
            y, q["w"], (s, s), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=g) + q["b"]
    bb = p["backbone"]
    y = jax.nn.relu6(conv(bb["stem"], x, 2))
    n = sum(1 for k in bb if k.startswith("pw"))
    for i in range(1, n + 1):
        y = jax.nn.relu6(conv(bb[f"dw{i}"], y, 2 if i <= 3 else 1,
                              bb[f"dw{i}"]["w"].shape[-1]))
        y = jax.nn.relu6(conv(bb[f"pw{i}"], y))
    out = conv(p["head"], y)
    assert out.shape[1] == head_res
    return out


def test_default_configs_build_todays_models():
    dc, pc = detector_config(64), pose_config(64)
    key = jax.random.key(5)
    dp, pp = V.init_detector(dc, key), V.init_pose(pc, key)
    chans = (16, 32, 64, 128, 256)
    want = {"backbone/stem/w": (3, 3, 3, 16)}
    for i in range(1, 5):
        want[f"backbone/dw{i}/w"] = (3, 3, 1, chans[i - 1])
        want[f"backbone/pw{i}/w"] = (1, 1, chans[i - 1], chans[i])
    for tree, head in ((dp, 4 * (10 + 1 + 4)), (pp, 17)):
        got = {"/".join(k.key for k in path): leaf.shape for path, leaf
               in jax.tree_util.tree_flatten_with_path(tree)[0]}
        assert {k: v for k, v in got.items() if k.endswith("/w")} == dict(
            want, **{"head/w": (3, 3, 256, head)})
        assert len(got) == 2 * len(want) + 2            # a bias each
    x = jax.random.uniform(jax.random.key(6), (2, 64, 64, 3))
    raw = _legacy_raw(dp, x, 4).reshape(2, 4 * 4 * 4, 15)
    got = V.detector_apply(dc, dp, x)
    np.testing.assert_array_equal(got["boxes"], raw[..., 11:])
    np.testing.assert_array_equal(got["scores"],
                                  jax.nn.softmax(raw[..., :11], axis=-1))
    heat = _legacy_raw(pp, x, 4).reshape(2, 16, 17)
    kp = V.pose_apply(pc, pp, x)
    idx = jnp.argmax(heat, axis=1)
    np.testing.assert_array_equal(kp["y"], (idx // 4 + 0.5) / 4)
    np.testing.assert_array_equal(kp["score"],
                                  jax.nn.sigmoid(jnp.max(heat, axis=1)))


def test_published_pair_shapes():
    """SSD-MobileNetV1 at 300: six maps of 19, 10, 5, 3, 2 and 1 cells,
    1,917 anchors of 91 class scores and 4 box encodings; MoveNet at 192:
    its four heads on a 48x48 map, 1 + 34 + 17 + 34 channels."""
    key = jax.random.key(0)
    ssd, mn = ssd_mobilenet_v1_config(), movenet_lightning_config()
    assert V.ssd_map_sizes(ssd) == [19, 10, 5, 3, 2, 1]
    assert V.ssd_anchors(ssd).shape == (1917, 4)
    dp = jax.eval_shape(lambda k: V.init_detector(ssd, k), key)
    out = jax.eval_shape(lambda p, x: V.detector_apply(ssd, p, x), dp,
                         jax.ShapeDtypeStruct((2, 300, 300, 3), jnp.float32))
    assert out["scores"].shape == (2, 1917, 91)
    assert out["boxes"].shape == (2, 1917, 4)
    pp = jax.eval_shape(lambda k: V.init_pose(mn, k), key)
    frames = jax.ShapeDtypeStruct((2, 192, 192, 3), jnp.float32)
    heads = jax.eval_shape(lambda p, x: V.centernet_heads(mn, p, x), pp,
                           frames)
    assert {k: v.shape for k, v in heads.items()} == {
        "centre": (2, 48, 48, 1), "regress": (2, 48, 48, 34),
        "heat": (2, 48, 48, 17), "offset": (2, 48, 48, 34)}
    flags, kp = jax.eval_shape(lambda p, x: V.analyse_inner(mn, p, x), pp,
                               frames)
    assert flags.shape == (2,) and kp["y"].shape == (2, 17)


def test_model_flops_meet_the_papers_tables():
    # arXiv:1704.04861 Table 13: SSD 300 on MobileNet, 1.2 billion
    # multiply-adds
    assert abs(V.model_flops(ssd_mobilenet_v1_config()) / 2.4e9 - 1) < 0.05
    # arXiv:1801.04381 Table 4: MobileNetV2 1.0 at 224, 300 million, with
    # the 1x1 convolution to 1280 and the classifier that MoveNet's trunk
    # does without
    tail = 2 * 7 * 7 * 320 * 1280 + 2 * 1280 * 1000
    trunk = V.backbone_flops(movenet_lightning_config(224))
    assert abs((trunk + tail) / 600e6 - 1) < 0.05


def test_model_flops_count_every_part_of_the_pair():
    ssd = ssd_mobilenet_v1_config()
    fewer = dataclasses.replace(ssd, ssd_extras=ssd.ssd_extras[:3],
                                ssd_boxes=ssd.ssd_boxes[:5])
    # the last extra layer (1x1 to 64 at 2x2, 3x3 to 128 at 1x1) and its
    # predictor (1x1, 6 boxes of 95 outputs, at 1x1)
    last = (2 * 256 * 64 * 4 + 2 * 9 * 64 * 128 * 1
            + 2 * 128 * 6 * 95 * 1)
    assert V.model_flops(ssd) - V.model_flops(fewer) == last
    mn = movenet_lightning_config()
    wider = dataclasses.replace(mn, fpn_width=mn.fpn_width + 1)
    # one more channel on every lateral (at 48, 24, 12, 6 from 24, 32, 96
    # and 320 channels), every head's depthwise layer and its 1x1 input
    more = (2 * (24 * 48 * 48 + 32 * 24 * 24 + 96 * 12 * 12 + 320 * 6 * 6)
            + 4 * 2 * 9 * 48 * 48 + 2 * 86 * 48 * 48)
    assert V.model_flops(wider) - V.model_flops(mn) == more
    assert V.model_flops(mn) > V.backbone_flops(mn)


@pytest.mark.parametrize("bad", [dict(head="yolo"), dict(trunk="resnet"),
                                 dict(trunk="mobilenet_v2", width_mult=0.5,
                                      bottlenecks=((1, 16, 1, 1),))],
                         ids=["head", "trunk", "v2_width_mult"])
def test_model_flops_refuses_what_it_cannot_count(bad):
    with pytest.raises(ValueError):
        V.model_flops(dataclasses.replace(detector_config(64), **bad))
