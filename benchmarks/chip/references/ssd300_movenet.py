"""Plain float32 reference of the served path with the paper's published
pair, and its control.

The outer camera runs SSD-MobileNetV1 at 300 (arXiv:1512.02325 on
arXiv:1704.04861's MobileNetV1 1.0, laid out as the TF Object Detection
API's ``ssd_mobilenet_v1_coco``), the inner camera MoveNet SinglePose
Lightning at 192 (a MobileNetV2 trunk, arXiv:1801.04381, under a feature
pyramid and four CenterNet heads).  It imports nothing of the program:

* ingest and resample: the ``eda_vision`` reference's, loaded by path
  (each camera at its own model resolution);
* SSD: the trunk's stages ``ssd_taps`` and one extra layer per depth of
  ``ssd_extras`` (a 1x1 convolution to half the depth, then a 3x3 one at
  stride 2, ReLU6 after each) are the feature maps; a 1x1 predictor on
  each gives, for ``ssd_boxes`` boxes a cell, 91 class logits and four
  box encodings; anchors come from SSD's generator (scales 0.2 to 0.95,
  the lowest map's three boxes at 0.1, ratios 1, 2, 1/2, 3, 1/3 and one
  interpolated scale) and encodings decode against them with scale
  factors 10, 10, 5, 5;
* MoveNet: MobileNetV2's inverted residuals (1x1 expansion, ReLU6, 3x3
  depthwise, ReLU6, linear 1x1 projection, the input added back where the
  stride is 1 and the widths match), 1x1 laterals of width ``fpn_width``
  on the stages ``fpn_taps`` merged top-down by 2x nearest upsampling and
  addition, and four heads (3x3 depthwise, ReLU, 1x1): the person centre,
  each keypoint's (dy, dx) from it, keypoint heat maps and (dy, dx)
  offsets.  The decode takes the centre cell with the largest
  ``sigmoid(centre) / (d + 1.8)``, ``d`` the cell's distance to the map's
  middle, reads the keypoints' regressed positions there, and takes each
  keypoint's cell with the largest ``sigmoid(heat) / (d + 1.8)``, ``d``
  the distance to its regressed position; the keypoint lies at that
  cell plus its offset, with the heat map's value there as its score;
* the paper's flag rules (section 3.2.3) on top, with no non-max
  suppression: a frame is flagged when any anchor is;
* work: the operations of one frame through each camera's model
  (``model_flops``).

Flags are returned as margins, as the ``eda_vision`` reference returns
them: the sign is the flag, the size how far the evidence lies from the
rule's boundary (AND the minimum, OR the maximum, NOT the negation).  A
rule on a box's position or size is a bound on its raw encoding (the
decode is monotone in each).  A keypoint's rule rests on two argmax
choices, the centre's and its own: its margin is capped by the smaller
gap, in log weight, between the chosen cell and the next best, since no
smaller change of the heads moves either choice.

Precision: float32 weights and activations; each convolution's operands
rounded as ``precision.conv_operands`` states for its layer (``stem``,
``dwI``, ``pwI``, ``irR_J.expand``, ``irR_J.dw``, ``irR_J.project``,
``xI.reduce``, ``xI.conv``, ``pI``, ``latI``, ``<head>.dw``,
``<head>.out``), else for its kind (``depthwise``, ``pointwise``,
``expansion``, ``projection``, ``extra``, ``predictor``, ``fpn``,
``head_depthwise``, ``head``), else the rest's (``default``); products and
sums exact (``Precision.HIGHEST``).  The control is every operand one
step lower, and the ingest at three bfloat16 passes.
"""
from __future__ import annotations

import json
import math
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.spec import SECTION, load_module, model_res

# the eda_vision reference's ingest, resample, rounding and calibration
_base = load_module(Path(__file__).with_name("eda_vision.py"),
                    "chipbench_reference_eda_vision")
downscale, ingest = _base.downscale, _base.ingest
ROUND, ROUND_CONTROL = _base.ROUND, _base.ROUND_CONTROL
operand_precision = _base.operand_precision

HIGHEST = jax.lax.Precision.HIGHEST

# rule constants of the paper's flag logic (section 3.2.3)
VEHICLE = (2, 3, 4)            # the program's car, truck and bus ids
ROAD_Y, ROAD_X = 0.55, (0.25, 0.75)
TAILGATE_AREA = 0.18
KP_MIN_SCORE = 0.3
HAND_LINE = 0.25               # a wrist above 3/4 of the frame height
EYE_MARGIN = 0.02              # eyes below the ears: glancing down
L_EYE, R_EYE, L_EAR, R_EAR, L_WRIST, R_WRIST = 1, 2, 3, 4, 9, 10

# SSD's anchor generator and box coder (ssd_mobilenet_v1_coco)
MIN_SCALE, MAX_SCALE, LOWEST_SCALE = 0.2, 0.95, 0.1
RATIOS = (1.0, 2.0, 0.5, 3.0, 1.0 / 3.0)
BOX_SCALE = (10.0, 10.0, 5.0, 5.0)
# MoveNet's inverse-distance weighting: 1 / (distance in cells + 1.8)
DIST_OFFSET = 1.8
HEADS = ("centre", "regress", "heat", "offset")


# ---------------------------------------------------------------------------
# architecture: layers, sizes and the tree of weight shapes
# ---------------------------------------------------------------------------


def _out(n: int, stride: int) -> int:
    return -(-n // stride)               # a SAME convolution's output


def v2_blocks(model: dict):
    """(name, expansion, in, out, stride, last of its row) of every
    inverted residual."""
    cin, out = model["channels"][0], []
    for r, (t, c, n, s) in enumerate(model["bottlenecks"], 1):
        for j in range(1, n + 1):
            out.append((f"ir{r}_{j}", t, cin, c, s if j == 1 else 1, j == n))
            cin = c
    return out


def stages(model: dict, res: int):
    """(channels, size) of every trunk stage, the stem's first."""
    ch = model["channels"]
    n = _out(res, 2)
    out = [(ch[0], n)]
    if model["trunk"] == "mobilenet_v2":
        for _, _, _, c, s, last in v2_blocks(model):
            n = _out(n, s)
            if last:
                out.append((c, n))
        return out
    for i in range(1, len(ch)):
        n = _out(n, 2 if i in model["stride_blocks"] else 1)
        out.append((ch[i], n))
    return out


def ssd_maps(model: dict, res: int):
    """(channels, size) of each SSD feature map."""
    st = stages(model, res)
    maps = [st[i] for i in model["ssd_taps"]]
    for d in model["ssd_extras"]:
        maps.append((d, _out(maps[-1][1], 2)))
    return maps


def anchors(model: dict, res: int) -> np.ndarray:
    """(N, 4) anchors (cy, cx, h, w), map by map, cell by cell, box by
    box."""
    sizes = [g for _, g in ssd_maps(model, res)]
    L = len(sizes)
    scales = [MIN_SCALE + (MAX_SCALE - MIN_SCALE) * i / (L - 1)
              for i in range(L)] + [1.0]
    rows = []
    for i, (g, boxes) in enumerate(zip(sizes, model["ssd_boxes"])):
        s = scales[i]
        specs = ([(LOWEST_SCALE, 1.0), (s, 2.0), (s, 0.5)] if boxes == 3 else
                 [(s, r) for r in RATIOS]
                 + [(math.sqrt(s * scales[i + 1]), 1.0)])
        assert len(specs) == boxes, (boxes, specs)
        for y in range(g):
            for x in range(g):
                for sc, r in specs:
                    rows.append(((y + 0.5) / g, (x + 0.5) / g,
                                 sc / math.sqrt(r), sc * math.sqrt(r)))
    return np.asarray(rows, np.float32)


def _conv_shape(k, cin, cout):
    return {"w": (k, k, cin, cout), "b": (cout,)}


def _dw_shape(c):
    return {"w": (3, 3, 1, c), "b": (c,)}


def trunk_shapes(model: dict) -> dict:
    ch = model["channels"]
    bb = {"stem": _conv_shape(3, 3, ch[0])}
    if model["trunk"] == "mobilenet_v2":
        for name, t, cin, cout, _, _ in v2_blocks(model):
            blk = {} if t == 1 else {"expand": _conv_shape(1, cin, cin * t)}
            blk["dw"] = _dw_shape(cin * t)
            blk["project"] = _conv_shape(1, cin * t, cout)
            bb[name] = blk
        return bb
    for i in range(1, len(ch)):
        bb[f"dw{i}"] = _dw_shape(ch[i - 1])
        bb[f"pw{i}"] = _conv_shape(1, ch[i - 1], ch[i])
    return bb


def head_outputs(model: dict) -> dict:
    K = model["num_keypoints"]
    return {"centre": 1, "regress": 2 * K, "heat": K, "offset": 2 * K}


def shapes(cfg: dict) -> dict:
    """The trees of weight shapes, as the program names them."""
    d, p = cfg["detector"], cfg["pose"]
    C = d["num_classes"] + 1 + 4
    res = model_res(cfg, "outer")
    outer = {"backbone": trunk_shapes(d), "extras": {}, "predict": {}}
    cin = d["channels"][d["ssd_taps"][-1]]
    for i, dep in enumerate(d["ssd_extras"], 1):
        outer["extras"][f"x{i}"] = {"reduce": _conv_shape(1, cin, dep // 2),
                                    "conv": _conv_shape(3, dep // 2, dep)}
        cin = dep
    for i, ((c, _), boxes) in enumerate(zip(ssd_maps(d, res),
                                            d["ssd_boxes"])):
        outer["predict"][f"p{i}"] = _conv_shape(1, c, boxes * C)
    st, W = stages(p, model_res(cfg, "inner")), p["fpn_width"]
    inner = {"backbone": trunk_shapes(p),
             "fpn": {f"lat{i}": _conv_shape(1, st[s][0], W)
                     for i, s in enumerate(p["fpn_taps"])},
             "heads": {n: {"dw": _dw_shape(W), "out": _conv_shape(1, W, c)}
                       for n, c in head_outputs(p).items()}}
    return {"outer": outer, "inner": inner}


# ---------------------------------------------------------------------------
# work: multiply-adds times two of one frame through a model
# ---------------------------------------------------------------------------


def _mac2(hw, k, cin, cout):
    return 2 * k * k * cin * cout * hw * hw


def trunk_flops(model: dict, res: int) -> int:
    st = stages(model, res)
    total = _mac2(st[0][1], 3, 3, st[0][0])                    # stem
    if model["trunk"] == "mobilenet_v2":
        n = st[0][1]
        for _, t, cin, cout, s, _ in v2_blocks(model):
            n_in, n = n, _out(n, s)
            if t != 1:
                total += _mac2(n_in, 1, cin, cin * t)          # expansion
            total += 2 * 9 * cin * t * n * n                   # depthwise
            total += _mac2(n, 1, cin * t, cout)                # projection
        return total
    for i in range(1, len(st)):
        cin, (cout, n) = st[i - 1][0], st[i]
        total += 2 * 9 * cin * n * n + _mac2(n, 1, cin, cout)
    return total


def model_flops(cfg: dict, kind: str) -> int:
    """Operations of one frame through camera ``kind``'s model (``outer``
    SSD-MobileNetV1, ``inner`` MoveNet) at its model resolution; decodes,
    softmaxes and additions are not counted."""
    model, res = cfg[SECTION[kind]], model_res(cfg, kind)
    total = trunk_flops(model, res)
    if kind == "outer":
        maps, taps = ssd_maps(model, res), len(model["ssd_taps"])
        cin = maps[taps - 1][0]
        for k, d in enumerate(model["ssd_extras"]):
            total += (_mac2(maps[taps - 1 + k][1], 1, cin, d // 2)
                      + _mac2(maps[taps + k][1], 3, d // 2, d))
            cin = d
        out = model["num_classes"] + 1 + 4
        for (c, n), boxes in zip(maps, model["ssd_boxes"]):
            total += _mac2(n, 1, c, boxes * out)
        return total
    st, W = stages(model, res), model["fpn_width"]
    for s in model["fpn_taps"]:
        total += _mac2(st[s][1], 1, st[s][0], W)
    n = st[model["fpn_taps"][0]][1]
    for c in head_outputs(model).values():
        total += 2 * 9 * W * n * n + _mac2(n, 1, W, c)
    return total


# ---------------------------------------------------------------------------
# the networks, each convolution's operands rounded as the configuration
# states
# ---------------------------------------------------------------------------


KINDS = {"stem": "stem", "dw": "depthwise", "pw": "pointwise",
         "expand": "expansion", "project": "projection", "reduce": "extra",
         "conv": "extra", "lat": "fpn", "out": "head"}


def layer_kind(layer: str) -> str:
    """``dw3`` -> depthwise, ``ir2_1.expand`` -> expansion, ``heat.dw`` ->
    head_depthwise, ``p0`` -> predictor ..."""
    part = layer.rsplit(".", 1)[-1]
    if part == "dw" and layer.split(".")[0] in HEADS:
        return "head_depthwise"
    if layer[0] == "p" and layer[1:].isdigit():
        return "predictor"
    return KINDS.get(part.rstrip("0123456789"), part)


def precisions(prec, layer: str):
    """(activations, weights) precision of ``layer``: its own entry, else
    its kind's, else the rest's."""
    table = dict(prec)
    for key in (layer, layer_kind(layer), "default"):
        if key in table:
            return table[key]
    raise KeyError(f"no conv_operands precision for {layer!r}")


def _conv(p, x, stride, groups, layer, prec, control):
    rnd = ROUND_CONTROL if control else ROUND
    px, pw = precisions(prec, layer)
    y = jax.lax.conv_general_dilated(
        rnd[px](x), rnd[pw](p["w"]), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=HIGHEST)
    return y + p["b"]


def _relu6(x):
    return jnp.clip(x, 0.0, 6.0)


def trunk(p, x, model: dict, prec, control):
    """Every stage's output, the stem's first."""
    x = _relu6(_conv(p["stem"], x, 2, 1, "stem", prec, control))
    out = [x]
    if model["trunk"] == "mobilenet_v2":
        for name, t, cin, cout, s, last in v2_blocks(model):
            blk, h = p[name], x
            if t != 1:
                h = _relu6(_conv(blk["expand"], h, 1, 1, f"{name}.expand",
                                 prec, control))
            h = _relu6(_conv(blk["dw"], h, s, cin * t, f"{name}.dw", prec,
                             control))
            h = _conv(blk["project"], h, 1, 1, f"{name}.project", prec,
                      control)
            x = h + x if s == 1 and cin == cout else h
            if last:
                out.append(x)
        return out
    ch = model["channels"]
    for i in range(1, len(ch)):
        s = 2 if i in model["stride_blocks"] else 1
        x = _relu6(_conv(p[f"dw{i}"], x, s, ch[i - 1], f"dw{i}", prec,
                         control))
        x = _relu6(_conv(p[f"pw{i}"], x, 1, 1, f"pw{i}", prec, control))
        out.append(x)
    return out


def ssd_raw(p, x, model: dict, prec, control):
    """(B, anchors, 91 + 4) class logits and box encodings."""
    st = trunk(p["backbone"], x, model, prec, control)
    maps = [st[i] for i in model["ssd_taps"]]
    y = maps[-1]
    for i in range(1, len(model["ssd_extras"]) + 1):
        e = p["extras"][f"x{i}"]
        y = _relu6(_conv(e["reduce"], y, 1, 1, f"x{i}.reduce", prec, control))
        y = _relu6(_conv(e["conv"], y, 2, 1, f"x{i}.conv", prec, control))
        maps.append(y)
    C = model["num_classes"] + 1 + 4
    raws = []
    for i, m in enumerate(maps):
        r = _conv(p["predict"][f"p{i}"], m, 1, 1, f"p{i}", prec, control)
        raws.append(r.reshape(r.shape[0], -1, C))
    return jnp.concatenate(raws, axis=1)


def movenet_heads(p, x, model: dict, prec, control) -> dict:
    """The four heads' raw outputs, (B, G, G, ·)."""
    st = trunk(p["backbone"], x, model, prec, control)
    taps = [st[s] for s in model["fpn_taps"]]
    n = len(taps)
    y = _conv(p["fpn"][f"lat{n - 1}"], taps[-1], 1, 1, f"lat{n - 1}", prec,
              control)
    for i in range(n - 2, -1, -1):
        up = jnp.repeat(jnp.repeat(y, 2, axis=1), 2, axis=2)
        lat = _conv(p["fpn"][f"lat{i}"], taps[i], 1, 1, f"lat{i}", prec,
                    control)
        y = lat + up[:, :lat.shape[1], :lat.shape[2]]
    W = model["fpn_width"]
    out = {}
    for name in HEADS:
        hp = p["heads"][name]
        h = jnp.maximum(_conv(hp["dw"], y, 1, W, f"{name}.dw", prec,
                              control), 0.0)
        out[name] = _conv(hp["out"], h, 1, 1, f"{name}.out", prec, control)
    return out


# ---------------------------------------------------------------------------
# flag margins
# ---------------------------------------------------------------------------


def _logit(q):
    return math.log(q / (1.0 - q))


def _outer_from_raw(raw, anc, num_classes: int):
    """Hazard-flag margins (B,) from class logits and box encodings."""
    C = num_classes + 1
    z, t = raw[..., :C], raw[..., C:]
    obj = z[..., 1:]
    # softmax_c >= 0.5 <=> z_c >= logsumexp of every other logit
    top = jnp.argmax(obj, axis=-1) + 1
    is_top = jnp.arange(C) == top[..., None]
    keep = jnp.max(obj, axis=-1) - jax.nn.logsumexp(
        jnp.where(is_top, -jnp.inf, z), axis=-1)
    veh = np.zeros(num_classes, bool)
    veh[list(VEHICLE)] = True
    vehicle = (jnp.max(jnp.where(veh, obj, -jnp.inf), axis=-1)
               - jnp.max(jnp.where(veh, -jnp.inf, obj), axis=-1))
    ya, xa, ha, wa = (anc[:, i] for i in range(4))
    sy, sx, sh, sw = BOX_SCALE
    # cy = ty / sy * ha + ya > ROAD_Y, and so on: bounds on the encodings
    road = jnp.minimum(
        t[..., 0] - sy * (ROAD_Y - ya) / ha,
        jnp.minimum(t[..., 1] - sx * (ROAD_X[0] - xa) / wa,
                    sx * (ROAD_X[1] - xa) / wa - t[..., 1]))
    # exp(th / sh) * ha * exp(tw / sw) * wa > area, in log space
    tail = (t[..., 2] / sh + t[..., 3] / sw + np.log(ha * wa)
            - math.log(TAILGATE_AREA))
    hazard = jnp.maximum(jnp.minimum(-vehicle, road),
                         jnp.minimum(vehicle, tail))
    return jnp.max(jnp.minimum(keep, hazard), axis=-1)


def _top2_gap(logw, axis):
    """Gap between the largest and the second largest entry."""
    top = jax.lax.top_k(jnp.moveaxis(logw, axis, -1), 2)[0]
    return top[..., 0] - top[..., 1]


def _capped(m, cap):
    return jnp.sign(m) * jnp.minimum(jnp.abs(m), cap)


def _inner_from_heads(out: dict):
    """Distraction-flag margins (B,) from MoveNet's four heads."""
    B, G = out["centre"].shape[:2]
    K = out["heat"].shape[-1]
    iy = np.arange(G, dtype=np.float32)[:, None]
    ix = np.arange(G, dtype=np.float32)[None, :]
    mid = (G - 1) / 2
    wc = (jax.nn.sigmoid(out["centre"][..., 0])
          / (np.sqrt((iy - mid) ** 2 + (ix - mid) ** 2) + DIST_OFFSET))
    wc = wc.reshape(B, G * G)
    ci = jnp.argmax(wc, axis=1)
    gap_c = _top2_gap(jnp.log(wc), 1)                           # (B,)
    reg = out["regress"].reshape(B, G * G, K, 2)[jnp.arange(B), ci]
    ry = (ci // G).astype(jnp.float32)[:, None] + reg[..., 0]   # (B, K)
    rx = (ci % G).astype(jnp.float32)[:, None] + reg[..., 1]
    heat = jax.nn.sigmoid(out["heat"])
    d = jnp.sqrt((iy[None, :, :, None] - ry[:, None, None, :]) ** 2
                 + (ix[None, :, :, None] - rx[:, None, None, :]) ** 2)
    wk = (heat / (d + DIST_OFFSET)).reshape(B, G * G, K)
    ki = jnp.argmax(wk, axis=1)                                 # (B, K)
    gap_k = _top2_gap(jnp.log(wk), 1)
    at = jnp.arange(B)[:, None], ki, jnp.arange(K)[None, :]
    logit = out["heat"].reshape(B, G * G, K)[at]
    off_y = out["offset"].reshape(B, G * G, K, 2)[at + (0,)]
    ky = (ki // G).astype(jnp.float32) + off_y                  # in cells
    cap = jnp.minimum(gap_c[:, None], gap_k)
    ok = _capped(logit - _logit(KP_MIN_SCORE), cap)             # score >= 0.3
    high = _capped(HAND_LINE * G - ky, cap)                     # y < 1/4
    hand = jnp.maximum(jnp.minimum(ok[:, L_WRIST], high[:, L_WRIST]),
                       jnp.minimum(ok[:, R_WRIST], high[:, R_WRIST]))
    four = [L_EYE, R_EYE, L_EAR, R_EAR]
    below = ((ky[:, L_EYE] + ky[:, R_EYE]) / 2
             - (ky[:, L_EAR] + ky[:, R_EAR]) / 2 - EYE_MARGIN * G)
    glance = jnp.minimum(_capped(below, jnp.min(cap[:, four], axis=1)),
                         jnp.min(ok[:, four], axis=1))
    return jnp.maximum(hand, glance)


@partial(jax.jit, static_argnames=("model", "res", "prec", "control"))
def outer_margin(params, x, *, model: str, res: int, prec,
                 control: bool = False):
    """Hazard-flag margin of each frame (B,): > 0 where flagged."""
    m = json.loads(model)
    raw = ssd_raw(params, x, m, prec, control)
    return _outer_from_raw(raw, anchors(m, res), m["num_classes"])


@partial(jax.jit, static_argnames=("model", "prec", "control"))
def inner_margin(params, x, *, model: str, prec, control: bool = False):
    """Distraction-flag margin of each frame (B,): > 0 where flagged."""
    return _inner_from_heads(movenet_heads(params, x, json.loads(model),
                                           prec, control))


def margins(params, rows, cfg: dict, kind: str, control: bool = False):
    """Flag margins of model-resolution ``rows`` for one camera kind."""
    model = json.dumps(cfg[SECTION[kind]], sort_keys=True)
    prec = operand_precision(cfg)
    if kind == "outer":
        return outer_margin(params["outer"], rows, model=model,
                            res=model_res(cfg, kind), prec=prec,
                            control=control)
    return inner_margin(params["inner"], rows, model=model, prec=prec,
                        control=control)


# ---------------------------------------------------------------------------
# weights: the benchmark's own, from the seed, in one jitted call
# ---------------------------------------------------------------------------


def _is_shape(x) -> bool:
    return isinstance(x, list) and all(isinstance(i, int) for i in x)


HEAD_LAYERS = ("predict", "out")      # the convolutions at head_gain


def _kernels(key, tree: str, gain: float, head_gain: float):
    """Normal kernels at ``gain / sqrt(fan in)`` (``head_gain`` for the
    predictors and the heads' last convolutions) and zero biases, one key
    per leaf in a fixed order."""
    leaves, treedef = jax.tree.flatten_with_path(json.loads(tree),
                                                 is_leaf=_is_shape)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (path, shp) in zip(keys, leaves):
        if len(shp) == 1:
            out.append(jnp.zeros(shp, jnp.float32))
            continue
        names = {getattr(e, "key", None) for e in path}
        g = head_gain if names & set(HEAD_LAYERS) else gain
        out.append(jax.random.normal(k, tuple(shp), jnp.float32)
                   * (g / math.sqrt(math.prod(shp[:-1]))))
    return jax.tree.unflatten(treedef, out)


@partial(jax.jit, static_argnames=("cfg",))
def _make(key, outer_rows, inner_rows, cfg: str):
    c = json.loads(cfg)
    init, tree = c["init"], shapes(c)
    k_outer, k_inner = jax.random.split(key)
    p = {"outer": _kernels(k_outer, json.dumps(tree["outer"], sort_keys=True),
                           float(init["gain"]), float(init["head_gain"])),
         "inner": _kernels(k_inner, json.dumps(tree["inner"], sort_keys=True),
                           float(init["pose_gain"]),
                           float(init["head_gain"]))}
    d, prec = c["detector"], operand_precision(c)
    res = model_res(c, "outer")
    C = d["num_classes"] + 1 + 4
    # the detector: a bias on every predictor that lowers each box's
    # background logit and raises its first object class (a person) by as
    # much, which keeps more detections and, on the road, more hazards
    person = np.zeros(C, np.float32)
    person[0], person[1] = -1.0, 1.0
    anc = anchors(d, res)
    raw = ssd_raw(p["outer"], outer_rows, d, prec, False)
    shift = _base._centre(raw, person,
                          lambda r: _outer_from_raw(r, anc,
                                                    d["num_classes"]))
    for i, boxes in enumerate(d["ssd_boxes"]):
        p["outer"]["predict"][f"p{i}"]["b"] = jnp.tile(shift, boxes)
    # the pose model: one bias on every keypoint's heat map
    heads = movenet_heads(p["inner"], inner_rows, c["pose"], prec, False)
    every = np.ones(c["pose"]["num_keypoints"], np.float32)
    p["inner"]["heads"]["heat"]["out"]["b"] = _base._centre(
        heads["heat"], every,
        lambda h: _inner_from_heads(dict(heads, heat=h)))
    return p


def make_params(cfg: dict, seed: int, calib: dict) -> dict:
    """``{"outer": SSD weights, "inner": MoveNet weights}`` (float32, on
    the default device) from one seed, each camera's calibration rows at
    its model resolution.  As in the ``eda_vision`` reference, a gain
    keeps the frames' differences alive through the network without
    amplifying a rounding to the output's scale: ``gain`` for SSD's
    MobileNetV1 (its 14 trunk layers and 8 extra ones, as the
    ``eda_vision`` networks) and ``pose_gain`` for MoveNet, whose 51
    trunk convolutions and 10 residual additions grow a perturbation
    faster.
    Each head's bias is then set so that half of the calibration frames
    are flagged."""
    sizes = {k: cfg[k] for k in ("detector", "pose", "init", "precision",
                                 "input_res")}
    return _make(jax.random.key(seed), calib["outer"], calib["inner"],
                 json.dumps(sizes, sort_keys=True))
