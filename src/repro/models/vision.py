"""The paper's two analytics workloads as JAX models (§3.2.3).

  OuterAnalysis  — MobileNetV1-SSD-style detector: depthwise-separable conv
                   backbone + per-cell anchor head (class logits + boxes);
                   hazard flagging = non-vehicle object on the road region,
                   or a vehicle box large enough to indicate tailgating.
  InnerAnalysis  — MoveNet-Lightning-style pose model: conv backbone +
                   keypoint heatmap head; distraction flagging = a hand above
                   three-quarters of the frame height, or eyes positioned
                   below the ears (phone-glance posture).

The paper treats these as black-box TFLite models; here they are functional
JAX (same ``P`` descriptor system as the LMs) so the EDA runtime can drive
*real* inference end-to-end (``examples/eda_dashcam_serve.py``) and the
energy model can count their true FLOPs.  Frames are downscaled to the model
input resolution before inference — the paper's accuracy/latency trade-off,
kept configurable via ``VisionConfig.input_res``.

``VisionConfig``'s architecture fields also build the pair the paper
publishes: SSD-MobileNetV1 (``trunk="mobilenet_v1"``, MobileNetV1's own
stride schedule, ``head="ssd"``: extra layers, 1x1 box predictors over
six maps and anchor-prior box decoding) and MoveNet SinglePose Lightning
(``trunk="mobilenet_v2"`` inverted residuals, ``head="centernet"``: a
feature pyramid, four CenterNet heads and MoveNet's weighted-argmax
decode).  Each model part runs under a ``jax.named_scope`` (``trunk``,
``extra_layers``, ``box_predictors``, ``fpn``, ``heads``, ``head``,
``decode``), so a profiler's op metadata says which part a device op
belongs to.  The defaults build the program's ``eda-detector`` and
``eda-pose``.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.eda_vision import VisionConfig
from repro.models.param import P, init_tree

# COCO-ish class ids used by the detector head
VEHICLE_CLASSES = (2, 3, 4)        # car, truck, bus
PERSON_CLASS = 0
# keypoint ids (COCO-17 subset used by the flag logic)
KP_LEFT_EYE, KP_RIGHT_EYE = 1, 2
KP_LEFT_EAR, KP_RIGHT_EAR = 3, 4
KP_LEFT_WRIST, KP_RIGHT_WRIST = 9, 10


# ---------------------------------------------------------------------------
# Trunks: MobileNetV1 depthwise-separable blocks, MobileNetV2 inverted
# residuals
# ---------------------------------------------------------------------------


def _conv_p(kh, kw, cin, cout):
    return {"w": P((kh, kw, cin, cout), (None, None, None, None), scale=1.0),
            "b": P((cout,), (None,), init="zeros")}


def _dw_p(kh, kw, c):
    return {"w": P((kh, kw, 1, c), (None, None, None, None), scale=1.0),
            "b": P((c,), (None,), init="zeros")}


def _v2_blocks(cfg: VisionConfig):
    """(row, repeat, expansion, in, out, stride) of every inverted
    residual, rows and repeats 1-based."""
    if cfg.width_mult != 1:
        raise ValueError("mobilenet_v2 trunks take no width_mult")
    cin, out = cfg.channels[0], []
    for r, (t, c, n, s) in enumerate(cfg.bottlenecks, 1):
        for j in range(1, n + 1):
            out.append((r, j, t, cin, c, s if j == 1 else 1))
            cin = c
    return out


def stage_channels(cfg: VisionConfig) -> list:
    """Channels of each trunk stage: the stem's, then each block's
    (``mobilenet_v1``) or each row's (``mobilenet_v2``)."""
    if cfg.trunk == "mobilenet_v1":
        return [int(c * cfg.width_mult) for c in cfg.channels]
    if cfg.trunk == "mobilenet_v2":
        return [cfg.channels[0]] + [c for _, c, _, _ in cfg.bottlenecks]
    raise ValueError(f"unknown trunk {cfg.trunk!r}")


def stage_strides(cfg: VisionConfig) -> list:
    """Stride of each trunk stage relative to its input (the stem's is 2)."""
    if cfg.trunk == "mobilenet_v1":
        return [2] + [2 if i in cfg.stride_blocks else 1
                      for i in range(1, len(cfg.channels))]
    stage_channels(cfg)                      # refuses an unknown trunk
    return [2] + [s for _, _, _, s in cfg.bottlenecks]


def _down(n: int, stride: int) -> int:
    """Size of a ``SAME`` convolution's output."""
    return -(-n // stride)


def stage_sizes(cfg: VisionConfig) -> list:
    """Spatial size of each trunk stage at the model's input."""
    out, n = [], cfg.input_res
    for s in stage_strides(cfg):
        n = _down(n, s)
        out.append(n)
    return out


def backbone_params(cfg: VisionConfig) -> dict:
    chans = stage_channels(cfg)
    p = {"stem": _conv_p(3, 3, 3, chans[0])}
    if cfg.trunk == "mobilenet_v2":
        for r, j, t, cin, cout, _ in _v2_blocks(cfg):
            blk = {} if t == 1 else {"expand": _conv_p(1, 1, cin, cin * t)}
            blk["dw"] = _dw_p(3, 3, cin * t)
            blk["project"] = _conv_p(1, 1, cin * t, cout)
            p[f"ir{r}_{j}"] = blk
        return p
    for i in range(1, len(chans)):
        p[f"dw{i}"] = _dw_p(3, 3, chans[i - 1])
        p[f"pw{i}"] = _conv_p(1, 1, chans[i - 1], chans[i])
    return p


def _conv(p, x, stride=1, groups=1):
    w = p["w"].astype(x.dtype)
    if groups > 1:                       # depthwise
        y = jax.lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups)
    else:
        y = jax.lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"].astype(x.dtype)


def _v2_stages(cfg: VisionConfig, p: dict, x: jax.Array) -> list:
    """MobileNetV2: 1x1 expansion, ReLU6, 3x3 depthwise, ReLU6, linear
    1x1 projection, and the block's input added back where the stride
    is 1 and the widths match."""
    x = jax.nn.relu6(_conv(p["stem"], x, stride=2))
    stages = [x]
    for r, j, t, cin, cout, s in _v2_blocks(cfg):
        blk, h = p[f"ir{r}_{j}"], x
        if t != 1:
            h = jax.nn.relu6(_conv(blk["expand"], h))
        h = jax.nn.relu6(_conv(blk["dw"], h, stride=s, groups=cin * t))
        h = _conv(blk["project"], h)
        x = h + x if s == 1 and cin == cout else h
        if j == cfg.bottlenecks[r - 1][2]:
            stages.append(x)
    return stages


def trunk_stages(cfg: VisionConfig, p: dict, x: jax.Array) -> list:
    """x: (B, H, W, 3) in [0,1] -> every stage's output, stem first."""
    if cfg.trunk == "mobilenet_v2":
        return _v2_stages(cfg, p, x)
    chans = stage_channels(cfg)
    x = jax.nn.relu6(_conv(p["stem"], x, stride=2))
    stages = [x]
    for i in range(1, len(chans)):
        stride = 2 if i in cfg.stride_blocks else 1
        x = jax.nn.relu6(_conv(p[f"dw{i}"], x, stride=stride,
                               groups=chans[i - 1]))
        x = jax.nn.relu6(_conv(p[f"pw{i}"], x))
        stages.append(x)
    return stages


def backbone_apply(cfg: VisionConfig, p: dict, x: jax.Array) -> jax.Array:
    """x: (B, H, W, 3) in [0,1] -> the last stage (B, H/16, W/16, C_top)
    at the default strides."""
    with jax.named_scope("trunk"):
        return trunk_stages(cfg, p, x)[-1]


# ---------------------------------------------------------------------------
# Detector (outer)
# ---------------------------------------------------------------------------

# SSD's anchor generator and box coder as the TF Object Detection API's
# ssd_mobilenet_v1_coco sets them: scales spread evenly over the maps, the
# lowest map's three reduced boxes, five aspect ratios plus one box at the
# scale between a map's and the next's
SSD_MIN_SCALE, SSD_MAX_SCALE = 0.2, 0.95
SSD_RATIOS = (1.0, 2.0, 0.5, 3.0, 1.0 / 3.0)
SSD_LOWEST_SCALE = 0.1
SSD_BOX_SCALE = (10.0, 10.0, 5.0, 5.0)       # (y, x, h, w) coder scales


def ssd_map_channels(cfg: VisionConfig) -> list:
    ch = stage_channels(cfg)
    return [ch[i] for i in cfg.ssd_taps] + list(cfg.ssd_extras)


def ssd_map_sizes(cfg: VisionConfig) -> list:
    sizes = stage_sizes(cfg)
    out = [sizes[i] for i in cfg.ssd_taps]
    for _ in cfg.ssd_extras:
        out.append(_down(out[-1], 2))
    return out


def ssd_anchors(cfg: VisionConfig) -> np.ndarray:
    """(N, 4) anchors ``(cy, cx, h, w)`` in frame units, map by map, cell
    by cell (row-major), box by box: the order the predictors' outputs
    take."""
    sizes = ssd_map_sizes(cfg)
    L = len(sizes)
    scales = [SSD_MIN_SCALE + (SSD_MAX_SCALE - SSD_MIN_SCALE) * i
              / max(L - 1, 1) for i in range(L)] + [1.0]
    out = []
    for i, (g, boxes) in enumerate(zip(sizes, cfg.ssd_boxes)):
        s = scales[i]
        if boxes == 3:
            specs = [(SSD_LOWEST_SCALE, 1.0), (s, 2.0), (s, 0.5)]
        elif boxes == len(SSD_RATIOS) + 1:
            specs = ([(s, r) for r in SSD_RATIOS]
                     + [(np.sqrt(s * scales[i + 1]), 1.0)])
        else:
            raise ValueError(f"the SSD generator makes 3 or 6 boxes a "
                             f"cell, not {boxes}")
        hw = np.asarray([(sc / np.sqrt(r), sc * np.sqrt(r))
                         for sc, r in specs])
        c = (np.arange(g) + 0.5) / g
        cy, cx = np.meshgrid(c, c, indexing="ij")
        ctr = np.stack([cy.ravel(), cx.ravel()], -1)
        a = np.concatenate([np.repeat(ctr, len(specs), 0),
                            np.tile(hw, (g * g, 1))], -1)
        out.append(a)
    return np.concatenate(out).astype(np.float32)


def detector_params(cfg: VisionConfig) -> dict:
    out = cfg.num_classes + 1 + 4                       # +1 background
    if cfg.head == "ssd":
        p = {"backbone": backbone_params(cfg), "extras": {}, "predict": {}}
        cin = stage_channels(cfg)[cfg.ssd_taps[-1]]
        for i, d in enumerate(cfg.ssd_extras, 1):
            p["extras"][f"x{i}"] = {"reduce": _conv_p(1, 1, cin, d // 2),
                                    "conv": _conv_p(3, 3, d // 2, d)}
            cin = d
        for i, (c, boxes) in enumerate(zip(ssd_map_channels(cfg),
                                           cfg.ssd_boxes)):
            p["predict"][f"p{i}"] = _conv_p(1, 1, c, boxes * out)
        return p
    _grid_head(cfg)
    c_top = int(cfg.channels[-1] * cfg.width_mult)
    return {"backbone": backbone_params(cfg),
            "head": _conv_p(3, 3, c_top, cfg.num_anchors * out)}


def _grid_head(cfg: VisionConfig) -> None:
    if cfg.head != "grid":
        raise ValueError(f"no {cfg.head!r} head for a {cfg.task} model")


def init_detector(cfg: VisionConfig, rng: jax.Array) -> dict:
    return init_tree(detector_params(cfg), rng, "float32")


def _ssd_apply(cfg: VisionConfig, p: dict, frames: jax.Array):
    with jax.named_scope("trunk"):
        stages = trunk_stages(cfg, p["backbone"], frames)
    maps = [stages[i] for i in cfg.ssd_taps]
    with jax.named_scope("extra_layers"):
        x = maps[-1]
        for i in range(1, len(cfg.ssd_extras) + 1):
            layer = p["extras"][f"x{i}"]
            x = jax.nn.relu6(_conv(layer["reduce"], x))
            x = jax.nn.relu6(_conv(layer["conv"], x, stride=2))
            maps.append(x)
    C = cfg.num_classes + 1
    with jax.named_scope("box_predictors"):
        raws = []
        for i, m in enumerate(maps):
            r = _conv(p["predict"][f"p{i}"], m)
            B, h, w, _ = r.shape
            raws.append(r.reshape(B, h * w * cfg.ssd_boxes[i], C + 4))
        raw = jnp.concatenate(raws, axis=1)
    return {"scores": jax.nn.softmax(raw[..., :C], axis=-1),
            "boxes": raw[..., C:]}


def detector_apply(cfg: VisionConfig, p: dict, frames: jax.Array):
    """frames: (B, res, res, 3) -> dict of per-anchor predictions.

    Returns {"scores": (B, N, classes+1), "boxes": (B, N, 4)}.  The grid
    head has N = (res/16)^2 * anchors and boxes as (cy, cx, h, w) offsets
    from cell centres; SSD has one row per anchor of ``ssd_anchors`` and
    boxes encoded against them (``SSD_BOX_SCALE``).
    """
    if cfg.head == "ssd":
        return _ssd_apply(cfg, p, frames)
    _grid_head(cfg)
    feats = backbone_apply(cfg, p["backbone"], frames)
    with jax.named_scope("head"):
        raw = _conv(p["head"], feats)                    # (B, g, g, A*(C+5))
    B, g, _, _ = raw.shape
    A, C = cfg.num_anchors, cfg.num_classes + 1
    raw = raw.reshape(B, g * g * A, C + 4)
    return {"scores": jax.nn.softmax(raw[..., :C], axis=-1),
            "boxes": raw[..., C:],
            "grid": g}


def _ssd_boxes(cfg: VisionConfig, t: jax.Array):
    """Anchor-prior decoding: (cy, cx, h, w) of encodings ``t``."""
    a = ssd_anchors(cfg)
    sy, sx, sh, sw = SSD_BOX_SCALE
    cy = t[..., 0] / sy * a[:, 2] + a[:, 0]
    cx = t[..., 1] / sx * a[:, 3] + a[:, 1]
    h = jnp.exp(t[..., 2] / sh) * a[:, 2]
    w = jnp.exp(t[..., 3] / sw) * a[:, 3]
    return cy, cx, h, w


def decode_detections(cfg: VisionConfig, preds: dict,
                      score_thresh: float = 0.5):
    """Per-frame top detections: (class, score, cy, cx, h, w) arrays.
    No non-max suppression: every anchor is a detection."""
    scores = preds["scores"][..., 1:]                    # drop background
    best_c = jnp.argmax(scores, axis=-1)                 # (B, N)
    best_s = jnp.max(scores, axis=-1)
    keep = best_s >= score_thresh
    if cfg.head == "ssd":
        out_cy, out_cx, h, w = _ssd_boxes(cfg, preds["boxes"])
        return {"cls": best_c, "score": best_s, "keep": keep,
                "cy": out_cy, "cx": out_cx, "h": h, "w": w}
    g = preds["grid"]
    A = cfg.num_anchors
    n = g * g * A
    cell = jnp.arange(n) // A
    cy = (cell // g + 0.5) / g
    cx = (cell % g + 0.5) / g
    boxes = jax.nn.sigmoid(preds["boxes"])               # offsets in [0,1]
    out_cy = cy[None, :] + (boxes[..., 0] - 0.5) / g
    out_cx = cx[None, :] + (boxes[..., 1] - 0.5) / g
    h = boxes[..., 2]
    w = boxes[..., 3]
    return {"cls": best_c, "score": best_s, "keep": keep,
            "cy": out_cy, "cx": out_cx, "h": h, "w": w}


def flag_hazards(det: dict, road_y: float = 0.55,
                 road_x: Tuple[float, float] = (0.25, 0.75),
                 tailgate_area: float = 0.18) -> jax.Array:
    """Paper §3.2.3 OuterAnalysis flag logic, vectorised over anchors.

    hazard  := non-vehicle detection whose box centre lies in the
               lower-middle "road" region of the frame
    tailgate:= vehicle detection large enough to imply dangerous proximity
    Returns (B, N) bool per-detection danger flags.
    """
    is_vehicle = jnp.isin(det["cls"], jnp.asarray(VEHICLE_CLASSES))
    on_road = ((det["cy"] > road_y)
               & (det["cx"] > road_x[0]) & (det["cx"] < road_x[1]))
    hazard = (~is_vehicle) & on_road
    tailgate = is_vehicle & (det["h"] * det["w"] > tailgate_area)
    return det["keep"] & (hazard | tailgate)


# ---------------------------------------------------------------------------
# Pose (inner)
# ---------------------------------------------------------------------------

# MoveNet's decode weights the person-centre heat map by the inverse of
# each cell's distance to the frame's centre, and each keypoint heat map by
# the inverse of its distance to the keypoint's regressed position, both in
# map cells plus this constant
MOVENET_DIST_OFFSET = 1.8
CENTERNET_HEADS = ("centre", "regress", "heat", "offset")


def centernet_outputs(cfg: VisionConfig) -> dict:
    """Channels of each CenterNet head: the person centre, the keypoints'
    (dy, dx) from it, their heat maps and their (dy, dx) offsets."""
    K = cfg.num_keypoints
    return {"centre": 1, "regress": 2 * K, "heat": K, "offset": 2 * K}


def pose_params(cfg: VisionConfig) -> dict:
    if cfg.head == "centernet":
        ch, W = stage_channels(cfg), cfg.fpn_width
        return {"backbone": backbone_params(cfg),
                "fpn": {f"lat{i}": _conv_p(1, 1, ch[s], W)
                        for i, s in enumerate(cfg.fpn_taps)},
                "heads": {n: {"dw": _dw_p(3, 3, W), "out": _conv_p(1, 1, W, c)}
                          for n, c in centernet_outputs(cfg).items()}}
    _grid_head(cfg)
    c_top = int(cfg.channels[-1] * cfg.width_mult)
    return {"backbone": backbone_params(cfg),
            "head": _conv_p(3, 3, c_top, cfg.num_keypoints)}


def init_pose(cfg: VisionConfig, rng: jax.Array) -> dict:
    return init_tree(pose_params(cfg), rng, "float32")


def _up2(x: jax.Array, like: jax.Array) -> jax.Array:
    """2x nearest-neighbour upsampling, cropped to ``like``'s size."""
    x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
    return x[:, :like.shape[1], :like.shape[2]]


def _at(x: jax.Array, idx: jax.Array) -> jax.Array:
    """(B, G*G, K, ...) values at the flat cells ``idx`` (B, K)."""
    idx = idx.reshape(idx.shape + (1,) * (x.ndim - 3))
    return jnp.take_along_axis(x, idx[:, None], axis=1)[:, 0]


def movenet_decode(cfg: VisionConfig, out: dict) -> dict:
    """MoveNet's decode of the four heads (B, G, G, ·) -> keypoints
    {"y","x","score"}: (B, K) in frame units."""
    B, G = out["centre"].shape[:2]
    K = cfg.num_keypoints
    iy = jnp.arange(G, dtype=jnp.float32)[:, None]
    ix = jnp.arange(G, dtype=jnp.float32)[None, :]
    mid = (G - 1) / 2
    centre = (jax.nn.sigmoid(out["centre"][..., 0])
              / (jnp.sqrt((iy - mid) ** 2 + (ix - mid) ** 2)
                 + MOVENET_DIST_OFFSET))
    ci = jnp.argmax(centre.reshape(B, G * G), axis=1)          # (B,)
    reg = out["regress"].reshape(B, G * G, K, 2)
    reg = _at(reg, jnp.broadcast_to(ci[:, None], (B, K)))      # (B, K, 2)
    ry = (ci // G).astype(jnp.float32)[:, None] + reg[..., 0]
    rx = (ci % G).astype(jnp.float32)[:, None] + reg[..., 1]
    heat = jax.nn.sigmoid(out["heat"])                         # (B,G,G,K)
    dist = jnp.sqrt((iy[None, :, :, None] - ry[:, None, None, :]) ** 2
                    + (ix[None, :, :, None] - rx[:, None, None, :]) ** 2)
    ki = jnp.argmax((heat / (dist + MOVENET_DIST_OFFSET))
                    .reshape(B, G * G, K), axis=1)             # (B, K)
    off = _at(out["offset"].reshape(B, G * G, K, 2), ki)       # (B, K, 2)
    score = _at(heat.reshape(B, G * G, K), ki)
    ky = (ki // G).astype(jnp.float32) + off[..., 0]
    kx = (ki % G).astype(jnp.float32) + off[..., 1]
    return {"y": ky / G, "x": kx / G, "score": score}


def centernet_heads(cfg: VisionConfig, p: dict, frames: jax.Array) -> dict:
    """The four CenterNet heads' raw outputs, (B, G, G, ·) at the
    pyramid's finest stage."""
    with jax.named_scope("trunk"):
        stages = trunk_stages(cfg, p["backbone"], frames)
    with jax.named_scope("fpn"):
        taps = [stages[s] for s in cfg.fpn_taps]
        lat = p["fpn"]
        x = _conv(lat[f"lat{len(taps) - 1}"], taps[-1])
        for i in range(len(taps) - 2, -1, -1):
            x = _conv(lat[f"lat{i}"], taps[i]) + _up2(x, taps[i])
    with jax.named_scope("heads"):
        out = {}
        for n in CENTERNET_HEADS:
            hp = p["heads"][n]
            h = jax.nn.relu(_conv(hp["dw"], x, groups=cfg.fpn_width))
            out[n] = _conv(hp["out"], h)
        return out


def pose_apply(cfg: VisionConfig, p: dict, frames: jax.Array):
    """frames: (B, res, res, 3) -> keypoints {"y","x","score"}: (B, K)."""
    if cfg.head == "centernet":
        out = centernet_heads(cfg, p, frames)
        with jax.named_scope("decode"):
            return movenet_decode(cfg, out)
    _grid_head(cfg)
    feats = backbone_apply(cfg, p["backbone"], frames)
    with jax.named_scope("head"):
        heat = _conv(p["head"], feats)                   # (B, g, g, K)
    with jax.named_scope("decode"):
        B, g, _, K = heat.shape
        flat = heat.reshape(B, g * g, K)
        idx = jnp.argmax(flat, axis=1)                   # (B, K)
        score = jax.nn.sigmoid(jnp.max(flat, axis=1))
        ky = (idx // g + 0.5) / g
        kx = (idx % g + 0.5) / g
        return {"y": ky, "x": kx, "score": score}


def flag_distraction(kp: dict, hand_line: float = 0.25,
                     eye_margin: float = 0.02,
                     min_score: float = 0.3) -> jax.Array:
    """Paper §3.2.3 InnerAnalysis flag logic.

    distracted := a wrist above three-quarters of the frame height (phone to
    the ear), or eyes positioned below the ears (glancing down at a phone).
    y runs top(0) -> bottom(1); "above 3/4 height" = y < ``hand_line``.
    Returns (B,) bool.
    """
    def ok(i):
        return kp["score"][:, i] >= min_score

    hand_up = ((ok(KP_LEFT_WRIST) & (kp["y"][:, KP_LEFT_WRIST] < hand_line))
               | (ok(KP_RIGHT_WRIST) & (kp["y"][:, KP_RIGHT_WRIST] < hand_line)))
    eyes = (kp["y"][:, KP_LEFT_EYE] + kp["y"][:, KP_RIGHT_EYE]) / 2
    ears = (kp["y"][:, KP_LEFT_EAR] + kp["y"][:, KP_RIGHT_EAR]) / 2
    eyes_ok = (ok(KP_LEFT_EYE) & ok(KP_RIGHT_EYE)
               & ok(KP_LEFT_EAR) & ok(KP_RIGHT_EAR))
    glance_down = eyes_ok & (eyes > ears + eye_margin)
    return hand_up | glance_down


# ---------------------------------------------------------------------------
# FLOPs accounting (energy model / roofline)
# ---------------------------------------------------------------------------


def _conv_flops(hw: int, k: int, cin: int, cout: int) -> int:
    """Multiply-adds times two of a dense k x k convolution whose output
    is hw x hw."""
    return 2 * k * k * cin * cout * hw * hw


def backbone_flops(cfg: VisionConfig) -> float:
    """MACs*2 of one frame through the trunk (every stage)."""
    ch, sizes = stage_channels(cfg), stage_sizes(cfg)
    total = _conv_flops(sizes[0], 3, 3, ch[0])             # stem
    if cfg.trunk == "mobilenet_v2":
        hw = sizes[0]
        for _, _, t, cin, cout, s in _v2_blocks(cfg):
            h_in, hw = hw, _down(hw, s)
            if t != 1:
                total += _conv_flops(h_in, 1, cin, cin * t)   # expansion
            total += 2 * 9 * cin * t * hw * hw                # depthwise
            total += _conv_flops(hw, 1, cin * t, cout)        # projection
        return float(total)
    for i in range(1, len(ch)):
        hw = sizes[i]
        total += 2 * 9 * ch[i - 1] * hw * hw                  # depthwise
        total += _conv_flops(hw, 1, ch[i - 1], ch[i])         # pointwise
    return float(total)


def model_flops(cfg: VisionConfig) -> float:
    """MACs*2 of one frame through the whole model: trunk, then the grid
    head, SSD's extra layers and predictors, or MoveNet's pyramid and
    heads.  Decodes, softmaxes and residual adds are not counted."""
    total = backbone_flops(cfg)
    ch, sizes = stage_channels(cfg), stage_sizes(cfg)
    if cfg.head == "ssd":
        out = cfg.num_classes + 1 + 4
        maps, n = ssd_map_sizes(cfg), len(cfg.ssd_taps)
        cin = ch[cfg.ssd_taps[-1]]
        for k, d in enumerate(cfg.ssd_extras):
            total += (_conv_flops(maps[n - 1 + k], 1, cin, d // 2)
                      + _conv_flops(maps[n + k], 3, d // 2, d))
            cin = d
        for c, hw, boxes in zip(ssd_map_channels(cfg), maps, cfg.ssd_boxes):
            total += _conv_flops(hw, 1, c, boxes * out)
        return float(total)
    if cfg.head == "centernet":
        W = cfg.fpn_width
        for s in cfg.fpn_taps:
            total += _conv_flops(sizes[s], 1, ch[s], W)      # laterals
        hw = sizes[cfg.fpn_taps[0]]
        for c in centernet_outputs(cfg).values():
            total += 2 * 9 * W * hw * hw + _conv_flops(hw, 1, W, c)
        return float(total)
    _grid_head(cfg)
    if cfg.task == "detect":
        out = cfg.num_anchors * (cfg.num_classes + 1 + 4)
    else:
        out = cfg.num_keypoints
    return float(total + _conv_flops(sizes[-1], 3, ch[-1], out))


# ---------------------------------------------------------------------------
# Frame downscaling (the paper's pre-inference resize)
# ---------------------------------------------------------------------------


def downscale(frames: jax.Array, res: int, *, use_pallas: bool = False,
              method: str = "nearest", interpret=None) -> jax.Array:
    """(B, H, W, 3) -> (B, res, res, 3) nearest-neighbour (cheap, like the
    paper's Bitmap scaling).

    ``use_pallas`` dispatches to the ``kernels.vision_ops`` resample kernel
    (normalized fp32 out; bit-identical to the gather for fp32 inputs and
    ``method="nearest"``, box filtering also available); the default jnp
    gather keeps the model jits self-contained.
    """
    if use_pallas:
        from repro.kernels import vision_ops
        return vision_ops.downscale(frames, res, method=method,
                                    interpret=interpret)
    # the jnp gather is nearest-only: refuse rather than silently aliasing
    # when a caller asked for box filtering without the kernel path
    assert method == "nearest", \
        f"method={method!r} requires use_pallas=True (kernels.vision_ops)"
    B, H, W, _ = frames.shape
    ys = (jnp.arange(res) * H // res)
    xs = (jnp.arange(res) * W // res)
    return frames[:, ys][:, :, xs]


@partial(jax.jit, static_argnames=("cfg",))
def analyse_outer(cfg: VisionConfig, params: dict, frames: jax.Array):
    """Full outer pipeline: downscale -> detect -> flag.  Returns
    (danger_flags (B,N) bool, detections dict)."""
    x = downscale(frames.astype(jnp.float32), cfg.input_res)
    preds = detector_apply(cfg, params, x)
    with jax.named_scope("decode"):
        det = decode_detections(cfg, preds)
        return flag_hazards(det), det


@partial(jax.jit, static_argnames=("cfg",))
def analyse_inner(cfg: VisionConfig, params: dict, frames: jax.Array):
    """Full inner pipeline: downscale -> pose -> flag.  Returns
    (distracted (B,) bool, keypoints dict)."""
    x = downscale(frames.astype(jnp.float32), cfg.input_res)
    kp = pose_apply(cfg, params, x)
    with jax.named_scope("decode"):
        return flag_distraction(kp), kp
