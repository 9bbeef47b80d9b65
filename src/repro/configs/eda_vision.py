"""The paper's own two workloads, as JAX models.

- ``eda-detector``: MobileNetV1-SSD-style object detector (outer videos,
  road-hazard detection).  Depthwise-separable conv backbone + SSD-ish head
  over a coarse anchor grid [arXiv:1704.04861; paper §3.2.3 OuterAnalysis].
- ``eda-pose``: MoveNet-Lightning-style pose/heatmap model (inner videos,
  driver-distractedness) — conv backbone + keypoint heatmap head
  [paper §3.2.3 InnerAnalysis].

These are small CNNs (the paper runs them on phones); they are described by
``VisionConfig`` rather than ``ModelConfig`` and are consumed by
``repro.models.vision`` and the EDA runtime (``repro.core``).

The architecture fields name the parts of the published pair the paper
runs, and their defaults build the two models above:

- ``trunk``: ``mobilenet_v1`` (a stem, then depthwise-separable blocks to
  ``channels[1:]``, stride 2 in the blocks ``stride_blocks`` lists,
  1-based) or ``mobilenet_v2`` (a stem to ``channels[0]``, then the
  inverted-residual rows ``bottlenecks``, each ``(t, c, n, s)`` as in
  arXiv:1801.04381 Table 2).  A trunk's stage ``i`` is the output of its
  block (or row) ``i``; stage 0 is the stem's.
- ``head``: ``grid`` (one 3x3 head on the last stage, ``num_anchors``
  boxes a cell), ``ssd`` (SSD, arXiv:1512.02325: the stages
  ``ssd_taps``, then one extra layer per depth of ``ssd_extras``, each
  map predicting ``ssd_boxes`` boxes a cell through a 1x1 conv) or
  ``centernet`` (MoveNet: a feature pyramid of width ``fpn_width`` over
  the stages ``fpn_taps``, finest first, and four CenterNet heads).
"""
from dataclasses import dataclass


@dataclass(frozen=True)
class VisionConfig:
    name: str
    task: str                 # detect | pose
    input_res: int = 192      # paper downscales frames to the model input res
    channels: tuple = (16, 32, 64, 128, 256)
    num_classes: int = 10     # detector: COCO-ish subset (vehicle/person/...)
    num_anchors: int = 4      # detector: anchors per cell
    num_keypoints: int = 17   # pose: COCO keypoints
    width_mult: float = 1.0
    trunk: str = "mobilenet_v1"          # mobilenet_v1 | mobilenet_v2
    stride_blocks: tuple = (1, 2, 3)     # mobilenet_v1: blocks at stride 2
    bottlenecks: tuple = ()              # mobilenet_v2: (t, c, n, s) rows
    head: str = "grid"                   # grid | ssd | centernet
    ssd_taps: tuple = ()                 # ssd: trunk stages used as maps
    ssd_extras: tuple = ()               # ssd: depth of each extra layer
    ssd_boxes: tuple = ()                # ssd: boxes a cell on each map
    fpn_width: int = 0                   # centernet: pyramid channels
    fpn_taps: tuple = ()                 # centernet: stages, finest first


def detector_config(input_res: int = 192) -> VisionConfig:
    return VisionConfig(name="eda-detector", task="detect", input_res=input_res)


def pose_config(input_res: int = 192) -> VisionConfig:
    return VisionConfig(name="eda-pose", task="pose", input_res=input_res)


# MobileNetV1 1.0 (arXiv:1704.04861 Table 1): the stem's width, then each
# depthwise-separable block's
MOBILENET_V1 = (32, 64, 128, 128, 256, 256, 512, 512, 512, 512, 512, 512,
                1024, 1024)
# MobileNetV2 1.0 (arXiv:1801.04381 Table 2): (t, c, n, s) after a stem of 32
MOBILENET_V2 = ((1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
                (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1))


def ssd_mobilenet_v1_config(input_res: int = 300) -> VisionConfig:
    """SSD-MobileNetV1 as the TF Object Detection API's
    ``ssd_mobilenet_v1_coco`` lays it out: maps at ``Conv2d_11_pointwise``
    and ``Conv2d_13_pointwise`` and four extra layers (19, 10, 5, 3, 2 and
    1 cells at 300), 3 boxes a cell on the first map and 6 on the others,
    COCO's 90 classes."""
    return VisionConfig(
        name="ssd-mobilenet-v1", task="detect", input_res=input_res,
        channels=MOBILENET_V1, num_classes=90, stride_blocks=(2, 4, 6, 12),
        head="ssd", ssd_taps=(11, 13), ssd_extras=(512, 256, 256, 128),
        ssd_boxes=(3, 6, 6, 6, 6, 6))


def movenet_lightning_config(input_res: int = 192) -> VisionConfig:
    """MoveNet SinglePose Lightning: a MobileNetV2 1.0 trunk, a feature
    pyramid over its stride 4, 8, 16 and 32 stages (24, 32, 96 and 320
    channels) and CenterNet heads at stride 4 (48x48 at 192).  The
    pyramid's width is not published: 64 is assumed."""
    return VisionConfig(
        name="movenet-lightning", task="pose", input_res=input_res,
        channels=(32,), trunk="mobilenet_v2", bottlenecks=MOBILENET_V2,
        head="centernet", fpn_width=64, fpn_taps=(2, 3, 5, 7))


# Paper's device classes (Table 4.1) with relative processing capacity used by
# the CPU evaluation harness.  Capacities are calibrated from the paper's
# one-node processing times (Table 4.2: FindX2Pro fastest).
DEVICE_CLASSES = {
    # name: (relative_speed, joules_per_gflop, idle_w, battery_mah)
    "pixel3": (0.55, 0.55, 0.35, 2915),
    "pixel6": (0.75, 0.60, 0.40, 4614),
    "oneplus8": (1.00, 0.95, 0.55, 4300),
    "findx2pro": (1.10, 1.20, 0.60, 4260),
}
