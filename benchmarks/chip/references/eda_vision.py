"""Plain float32 reference of the served vision path, and its control.

It follows the paper's description of the two analytics (arXiv:2206.14414
section 3.2.3) at the widths of the configuration file, and imports
nothing of the program:

* ingest: frames in [0, 1] are resampled to the model and gate
  resolutions by nearest neighbour (row ``i`` takes source row
  ``i * H // res``), and the motion score is the largest 8x8-block mean of
  the per-pixel channel-mean absolute difference against the lane's
  reference frame;
* admit: a lane is admitted when its score exceeds the threshold the host
  shipped, or when it holds no reference yet;
* models: a MobileNetV1-style depthwise-separable backbone (stem 3x3
  stride 2, then depthwise 3x3 + pointwise 1x1 blocks, stride 2 in the
  first three, ReLU6) with a 3x3 head: SSD-style anchors for the outer
  camera's hazard detector, keypoint heat maps for the inner camera's
  distraction detector, and the paper's flag rules on top.

Flags are booleans, so the reference returns each flag's *margin*: a
number whose sign is the flag and whose size is how far the evidence
lies from the rule's boundary (in logits, or in heat-map units for a
keypoint's position).  AND is the minimum, OR the maximum and NOT the
negation of its parts' margins.  A served flag that disagrees with the
reference is then judged by how clear the reference's margin was.

Precision follows the configuration.  The ingest is exact float32 (its
resample matmuls run at ``Precision.HIGHEST``).  The models keep float32
weights and activations and run their convolutions at the TPU's default
precision, which the configuration states operand by operand
(``precision.conv_operands``: bfloat16 or float32 for the activations
and the weights of a layer, a kind of layer or the rest): the reference
rounds each operand so (``lax.reduce_precision``) and then multiplies and
sums exactly (``Precision.HIGHEST``).  The control puts the next
precision down in the program's place: the ingest resample at three
bfloat16 passes (``high``), and every model operand one step lower
(bfloat16 to float8 e4m3, float32 to bfloat16).
"""
from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

# rule constants of the paper's flag logic (section 3.2.3)
VEHICLE = (2, 3, 4)            # the program's car, truck and bus ids
SCORE_KEEP = 0.5               # a detection counts at class score >= 0.5
ROAD_Y, ROAD_X = 0.55, (0.25, 0.75)
TAILGATE_AREA = 0.18
KP_MIN_SCORE = 0.3
HAND_LINE = 0.25               # a wrist above 3/4 of the frame height
EYE_MARGIN = 0.02              # eyes below the ears: glancing down
L_EYE, R_EYE, L_EAR, R_EAR, L_WRIST, R_WRIST = 1, 2, 3, 4, 9, 10


# ---------------------------------------------------------------------------
# weights: the benchmark's own, from the seed, in one jitted call
# ---------------------------------------------------------------------------


def param_shapes(model: dict, head_out: int) -> dict:
    """The tree of weight shapes: ``backbone/{stem,dwI,pwI}/{w,b}`` and
    ``head/{w,b}``, HWIO convolution kernels."""
    ch = list(model["channels"])
    bb = {"stem": {"w": (3, 3, 3, ch[0]), "b": (ch[0],)}}
    for i in range(1, len(ch)):
        bb[f"dw{i}"] = {"w": (3, 3, 1, ch[i - 1]), "b": (ch[i - 1],)}
        bb[f"pw{i}"] = {"w": (1, 1, ch[i - 1], ch[i]), "b": (ch[i],)}
    return {"backbone": bb,
            "head": {"w": (3, 3, ch[-1], head_out), "b": (head_out,)}}


def detector_out(model: dict) -> int:
    return model["num_anchors"] * (model["num_classes"] + 1 + 4)


def shapes(cfg: dict) -> dict:
    return {"outer": param_shapes(cfg["detector"], detector_out(cfg["detector"])),
            "inner": param_shapes(cfg["pose"], cfg["pose"]["num_keypoints"])}


def _is_shape(x) -> bool:
    return isinstance(x, list) and all(isinstance(i, int) for i in x)


def _kernels(key, tree: str, gain: float, head_gain: float):
    """Normal kernels at ``gain / sqrt(fan in)`` (``head_gain`` for the
    heads) and zero biases, one key per leaf in a fixed order."""
    leaves, treedef = jax.tree.flatten_with_path(json.loads(tree),
                                                 is_leaf=_is_shape)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (path, shp) in zip(keys, leaves):
        if len(shp) == 1:
            out.append(jnp.zeros(shp, jnp.float32))
            continue
        g = head_gain if path[1].key == "head" else gain
        out.append(jax.random.normal(k, tuple(shp), jnp.float32)
                   * (g / math.sqrt(math.prod(shp[:-1]))))
    return jax.tree.unflatten(treedef, out)


def _centre(raw, where, margin_of, steps: int = 40):
    """The multiple of ``where`` (a bias on some head channels) that puts
    the median flag margin of a calibration batch at zero; the margin
    grows with it, so bisect on [-32, 32].  Where no multiple reaches
    zero, the bisection ends at the bound nearest to it."""
    def med(b):
        return jnp.median(margin_of(raw + b * where))

    def step(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) / 2
        up = med(mid) < 0
        return jnp.where(up, mid, lo), jnp.where(up, hi, mid)

    lo, hi = jax.lax.fori_loop(0, steps, step, (jnp.float32(-32.0),
                                                jnp.float32(32.0)))
    return (lo + hi) / 2 * where


@partial(jax.jit, static_argnames=("cfg",))
def _make(key, outer_rows, inner_rows, cfg: str):
    c = json.loads(cfg)
    p = _kernels(key, json.dumps(shapes(c), sort_keys=True),
                 float(c["init"]["gain"]), float(c["init"]["head_gain"]))
    d = c["detector"]
    C = d["num_classes"] + 1
    # the detector: a bias that lowers the background logit and raises
    # the first object class (a person) by as much, which keeps more
    # detections and, on the road, more hazards
    person = np.zeros(detector_out(d), np.float32)
    person[0::C + 4] = -1.0
    person[1::C + 4] = 1.0
    prec = operand_precision(c)
    raw = _head(p["outer"], outer_rows, prec, False)
    p["outer"]["head"]["b"] = _centre(
        raw, person, lambda r: _outer_from_head(r, d["num_classes"],
                                                d["num_anchors"]))
    # the pose model: one bias on every keypoint's heat map
    heat = _head(p["inner"], inner_rows, prec, False)
    every = np.ones(c["pose"]["num_keypoints"], np.float32)
    p["inner"]["head"]["b"] = _centre(heat, every, _inner_from_head)
    return p


def make_params(cfg: dict, seed: int, calib: dict) -> dict:
    """``{"outer": detector weights, "inner": pose weights}`` (float32,
    on the default device) from one seed.  The gain keeps the frames'
    differences alive through the ReLU6 layers without amplifying a
    rounding anywhere in them to the scale of the output.  Each head's bias is then set so that half
    of a calibration batch of each camera's frames (``calib``, at the
    model resolution) is flagged: with random weights the flags would
    otherwise be all one way, and a comparison of flags would then see
    no decision that a lower precision could turn.  The pose model's
    bias moves only its keypoints' confidence, not where they lie: where
    fewer than half of the frames hold a wrist up or a glance down, it
    makes every keypoint confident, which flags the most frames it can."""
    sizes = {k: cfg[k] for k in ("detector", "pose", "init", "precision")}
    return _make(jax.random.key(seed), calib["outer"], calib["inner"],
                 json.dumps(sizes, sort_keys=True))


# ---------------------------------------------------------------------------
# ingest and admit
# ---------------------------------------------------------------------------


def _bf16(x):
    # reduce_precision rounds as a cast would, and unlike a pair of
    # casts the compiler may not drop it for excess precision
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def split3(x):
    """``x`` as the three-pass bfloat16 product sees it: a high and a low
    bfloat16 part (``Precision.HIGH``), the rest dropped."""
    hi = _bf16(x)
    return hi + _bf16(x - hi)


def downscale(frames, res: int):
    """(S, H, W, C) -> (S, res, res, C) nearest neighbour."""
    _, H, W, _ = frames.shape
    ys = np.arange(res) * H // res
    xs = np.arange(res) * W // res
    return frames[:, ys][:, :, xs]


def block_sad(refs, gate, block: int):
    """Largest block mean of the channel-mean absolute difference, (S,)."""
    S, H, W, _ = gate.shape
    d = jnp.abs(gate - refs).mean(axis=-1)
    nh, nw = -(-H // block), -(-W // block)
    d = jnp.pad(d, ((0, 0), (0, nh * block - H), (0, nw * block - W)))
    sums = d.reshape(S, nh, block, nw, block).sum(axis=(2, 4))
    cnt = np.outer(np.minimum(block, H - np.arange(nh) * block),
                   np.minimum(block, W - np.arange(nw) * block))
    return (sums / jnp.asarray(cnt, jnp.float32)).reshape(S, -1).max(axis=-1)


@partial(jax.jit, static_argnames=("model_res", "gate_res", "block", "control"))
def ingest(frames, refs, *, model_res: int, gate_res: int, block: int,
           control: bool = False):
    """(model rows, gate rows, scores) of ``frames`` against ``refs``."""
    x = split3(frames) if control else frames
    model = downscale(x, model_res)
    gate = downscale(x, gate_res)
    return model, gate, block_sad(refs, gate, block)


# ---------------------------------------------------------------------------
# models, as flag margins
# ---------------------------------------------------------------------------


def _round_fp8(x):
    """Round to float8 e4m3 (3 mantissa bits; below 2**-6 the subnormal
    step of 2**-9), as a cast would round finite values of this range."""
    sub = jnp.round(x * 512.0) / 512.0
    return jnp.where(jnp.abs(x) < 2.0 ** -6, sub,
                     jax.lax.reduce_precision(x, exponent_bits=4,
                                              mantissa_bits=3))


ROUND = {"bfloat16": _bf16, "float32": lambda x: x}
ROUND_CONTROL = {"bfloat16": _round_fp8, "float32": _bf16}
KIND = {"dw": "depthwise", "pw": "pointwise"}


def operand_precision(cfg: dict):
    """The configuration's ``precision.conv_operands`` as a hashable
    key for the jitted functions."""
    ops = cfg["precision"]["conv_operands"]
    return tuple(sorted((k, tuple(v)) for k, v in ops.items()))


def _precisions(prec, layer: str):
    """(activations, weights) precision of ``layer`` (``stem``, ``dwI``,
    ``pwI``, ``head``): its own entry, else its kind's, else the rest's."""
    table = dict(prec)
    for key in (layer, KIND.get(layer[:2], layer), "default"):
        if key in table:
            return table[key]
    raise KeyError(f"no conv_operands precision for {layer!r}")


def _conv(p, x, stride: int, groups: int, layer: str, prec, control: bool):
    """A convolution whose operands are rounded as the configuration
    states, or one step lower for the control, and whose products and
    sums are exact float32."""
    rnd = ROUND_CONTROL if control else ROUND
    px, pw = _precisions(prec, layer)
    y = jax.lax.conv_general_dilated(
        rnd[px](x), rnd[pw](p["w"]), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=HIGHEST)
    return y + p["b"]


def backbone(p, x, prec, control: bool):
    x = jnp.clip(_conv(p["stem"], x, 2, 1, "stem", prec, control), 0.0, 6.0)
    n = sum(1 for k in p if k.startswith("pw"))
    for i in range(1, n + 1):
        c = p[f"dw{i}"]["w"].shape[-1]
        x = jnp.clip(_conv(p[f"dw{i}"], x, 2 if i <= 3 else 1, c, f"dw{i}",
                           prec, control), 0.0, 6.0)
        x = jnp.clip(_conv(p[f"pw{i}"], x, 1, 1, f"pw{i}", prec, control),
                     0.0, 6.0)
    return x


def _logit(p):
    return math.log(p / (1.0 - p))


def _above(v, t):
    """Margin of ``sigmoid(v) > t`` for a constant array ``t``: v - logit(t),
    +inf where t <= 0 (always true) and -inf where t >= 1 (never)."""
    tc = jnp.clip(t, 1e-12, 1 - 1e-12)
    m = v - jnp.log(tc / (1 - tc))
    return jnp.where(t <= 0, jnp.inf, jnp.where(t >= 1, -jnp.inf, m))


def _outer_from_head(raw, num_classes: int, num_anchors: int):
    """Hazard-flag margins (B,) from the detector head's output."""
    B, g = raw.shape[0], raw.shape[1]
    C = num_classes + 1
    raw = raw.reshape(B, g * g * num_anchors, C + 4)
    z, box = raw[..., :C], raw[..., C:]
    obj = z[..., 1:]                                    # object classes
    # softmax_c >= 0.5  <=>  z_c >= logsumexp of every other logit; the
    # best object class has the largest such margin
    top = jnp.argmax(obj, axis=-1) + 1
    is_top = jnp.arange(C) == top[..., None]
    others = jax.nn.logsumexp(jnp.where(is_top, -jnp.inf, z), axis=-1)
    keep = jnp.max(obj, axis=-1) - others
    veh = np.zeros(num_classes, bool)
    veh[list(VEHICLE)] = True
    vehicle = (jnp.max(jnp.where(veh, obj, -jnp.inf), axis=-1)
               - jnp.max(jnp.where(veh, -jnp.inf, obj), axis=-1))
    cell = np.arange(g * g * num_anchors) // num_anchors
    cy = (cell // g + 0.5) / g
    cx = (cell % g + 0.5) / g
    # box centre = cell centre + (sigmoid(offset) - 0.5) / g
    road = jnp.minimum(
        _above(box[..., 0], 0.5 + (ROAD_Y - cy) * g),
        jnp.minimum(_above(box[..., 1], 0.5 + (ROAD_X[0] - cx) * g),
                    -_above(box[..., 1], 0.5 + (ROAD_X[1] - cx) * g)))
    # sigmoid(h) * sigmoid(w) > area, in log space
    tail = (-jax.nn.softplus(-box[..., 2]) - jax.nn.softplus(-box[..., 3])
            - math.log(TAILGATE_AREA))
    hazard = jnp.maximum(jnp.minimum(-vehicle, road),
                         jnp.minimum(vehicle, tail))
    return jnp.max(jnp.minimum(keep, hazard), axis=-1)


def _inner_from_head(heat):
    """Distraction-flag margins (B,) from the pose head's heat maps."""
    B, g, _, K = heat.shape
    top = heat.max(axis=(1, 2))                         # (B, K)
    ok = top - _logit(KP_MIN_SCORE)                     # sigmoid(top) >= 0.3
    rows = heat.max(axis=2)                             # (B, g, K) per row
    # keypoint y = (row + 0.5) / g < HAND_LINE  <=>  row < HAND_LINE*g - 0.5
    up = np.arange(g) < HAND_LINE * g - 0.5
    high = (jnp.max(jnp.where(up[None, :, None], rows, -jnp.inf), axis=1)
            - jnp.max(jnp.where(up[None, :, None], -jnp.inf, rows), axis=1))
    hand = jnp.maximum(jnp.minimum(ok[:, L_WRIST], high[:, L_WRIST]),
                       jnp.minimum(ok[:, R_WRIST], high[:, R_WRIST]))
    # eyes below the ears: a rule on four argmax rows.  Its margin is
    # bounded below by the smallest gap between a keypoint's best row and
    # its best other row: no smaller change of the heat maps moves a row.
    row = jnp.argmax(rows, axis=1).astype(jnp.float32)  # (B, K)
    srt = jnp.sort(rows, axis=1)
    gap = srt[:, -1] - srt[:, -2]
    yy = (row + 0.5) / g
    below = ((yy[:, L_EYE] + yy[:, R_EYE]) / 2
             > (yy[:, L_EAR] + yy[:, R_EAR]) / 2 + EYE_MARGIN)
    four = [L_EYE, R_EYE, L_EAR, R_EAR]
    size = jnp.min(gap[:, four], axis=1)
    glance = jnp.minimum(jnp.where(below, size, -size),
                         jnp.min(ok[:, four], axis=1))
    return jnp.maximum(hand, glance)


def _head(params, x, prec, control: bool):
    feats = backbone(params["backbone"], x, prec, control)
    return _conv(params["head"], feats, 1, 1, "head", prec, control)


@partial(jax.jit, static_argnames=("num_classes", "num_anchors", "prec",
                                   "control"))
def outer_margin(params, x, *, num_classes: int, num_anchors: int, prec,
                 control: bool = False):
    """Hazard-flag margin of each frame (B,): > 0 where the frame is
    flagged.  A frame is flagged when any anchor keeps a detection (best
    object-class score >= 0.5) that is either a non-vehicle whose box
    centre lies on the road region, or a vehicle whose box area implies
    tailgating."""
    return _outer_from_head(_head(params, x, prec, control), num_classes,
                            num_anchors)


@partial(jax.jit, static_argnames=("num_keypoints", "prec", "control"))
def inner_margin(params, x, *, num_keypoints: int, prec,
                 control: bool = False):
    """Distraction-flag margin of each frame (B,): > 0 where flagged.  A
    frame is flagged when a confident wrist lies above three quarters of
    the frame height, or when all four of eyes and ears are confident and
    the eyes sit below the ears (a glance down)."""
    return _inner_from_head(_head(params, x, prec, control))


def margins(params, rows, cfg: dict, kind: str, control: bool = False):
    """Flag margins of model-resolution ``rows`` for one camera kind."""
    if kind == "outer":
        d = cfg["detector"]
        return outer_margin(params["outer"], rows,
                            num_classes=d["num_classes"],
                            num_anchors=d["num_anchors"],
                            prec=operand_precision(cfg), control=control)
    return inner_margin(params["inner"], rows,
                        num_keypoints=cfg["pose"]["num_keypoints"],
                        prec=operand_precision(cfg), control=control)
