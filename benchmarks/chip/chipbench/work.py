"""The work an algorithm needs, in bytes and operations, from its shapes.

These count what the job needs, not what today's code executes, so a
later change to how the work is done reads against the same yardstick:

* ingest: read each new frame once and its lane's gate reference, write
  its model-resolution and gate-resolution planes and its score.
  Resampling by nearest neighbour is a gather: no operations.  The
  motion score is a difference, an absolute value and a sum per gate
  value, and a mean per gate pixel.

A model's operations a frame are its configuration's: each plain
reference counts them (``model_flops(cfg, kind)`` in
``references/<reference>.py``), so that a configuration that brings
another model brings its count with it.
"""
from __future__ import annotations

F32 = 4


def ingest_bytes(frames: int, frame_res: int, model_res: int,
                 gate_res: int, channels: int = 3) -> int:
    per = (frame_res ** 2 + model_res ** 2 + 2 * gate_res ** 2) * channels
    return frames * (per * F32 + F32)


def ingest_flops(frames: int, gate_res: int, channels: int = 3) -> int:
    return frames * (3 * channels + 1) * gate_res ** 2


def least_time_s(nbytes: float, flops: float, peak: dict):
    """The least time the chip needs, and which bound sets it."""
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_ops = flops / peak["flops"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
