"""The work an algorithm needs, in bytes and operations, from its shapes.

These count what the job needs, not what today's code executes, so a
later change to how the work is done reads against the same yardstick:

* ingest: read each new frame once and its lane's gate reference, write
  its model-resolution and gate-resolution planes and its score.
  Resampling by nearest neighbour is a gather: no operations.  The
  motion score is a difference, an absolute value and a sum per gate
  value, and a mean per gate pixel.
* models: multiply-adds times two of one frame through the backbone and
  the head, from the configuration's widths (the same count as
  ``repro.models.vision.model_flops``, copied here so that the yardstick
  stays with the benchmark).
"""
from __future__ import annotations

F32 = 4


def ingest_bytes(frames: int, frame_res: int, model_res: int,
                 gate_res: int, channels: int = 3) -> int:
    per = (frame_res ** 2 + model_res ** 2 + 2 * gate_res ** 2) * channels
    return frames * (per * F32 + F32)


def ingest_flops(frames: int, gate_res: int, channels: int = 3) -> int:
    return frames * (3 * channels + 1) * gate_res ** 2


def backbone_flops(channels, input_res: int) -> int:
    hw = input_res // 2
    total = 2 * 9 * 3 * channels[0] * hw * hw                 # stem
    for i in range(1, len(channels)):
        if i <= 3:
            hw //= 2
        total += 2 * 9 * channels[i - 1] * hw * hw            # depthwise
        total += 2 * channels[i - 1] * channels[i] * hw * hw  # pointwise
    return total


def model_flops(model: dict, input_res: int, head_out: int) -> int:
    """One frame through a backbone and its 3x3 head."""
    hw = input_res // 16
    head = 2 * 9 * model["channels"][-1] * head_out * hw * hw
    return backbone_flops(model["channels"], input_res) + head


def least_time_s(nbytes: float, flops: float, peak: dict):
    """The least time the chip needs, and which bound sets it."""
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_ops = flops / peak["flops"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
