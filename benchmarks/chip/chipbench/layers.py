"""Per-layer readings that the metric readers (``metrics/<name>.py``) take.

Each returns ``None`` where the run holds nothing to read (no trace, no
such kernel in it, no dispatch), and the harness then leaves the metric
out of the result line: a share of a roofline or of a peak is never 0 for
want of data.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import numpy as np

from chipbench import work
from chipbench.drive import KINDS, Record
from chipbench.spec import model_res
from chipbench.trace import Reduced


@dataclass
class RunView:
    """What a reader may read of one run."""
    cfg: dict
    rec: Record                   # the measured window
    red: Optional[Reduced]        # its trace, reduced (--trace 1)
    peak: dict                    # peaks.peaks(device_kind)
    chips: int
    model_flops: Dict[str, int]   # a frame of each camera, its reference's


def turnaround_ms(run: RunView, q: float) -> Optional[float]:
    """The ``q``-th percentile of the window frames' turnaround, in ms."""
    t = run.rec.turnaround_s
    return 1e3 * float(np.percentile(np.asarray(t), q)) if t else None


def host_ms_per_tick(run: RunView) -> Optional[float]:
    """Mean wall time of ``gw.tick()`` less its fused dispatch, in ms."""
    t = run.rec.ticks
    if not t:
        return None
    return 1e3 * sum(w - d for w, d in t) / len(t)


def dispatch_ms(run: RunView) -> Optional[float]:
    """Mean ``FleetStep.last_dispatch_s`` over the window's dispatches."""
    d = [d for _, d in run.rec.ticks if d > 0]
    return 1e3 * sum(d) / len(d) if d else None


def ingest_roofline(run: RunView, kernel: str) -> Optional[float]:
    """Percent: the least time the window's ingest work (every frame
    staged, once) needs on the chip over the device time of the
    operations whose trace name holds ``kernel``."""
    if run.red is None:
        return None
    t = run.red.kernel_s(kernel)
    if t <= 0:
        return None
    c, active = run.cfg, run.rec.active
    n = sum(active.values())
    nbytes = sum(work.ingest_bytes(active[k], c["frame_res"],
                                   model_res(c, k), c["gate_res"])
                 for k in KINDS)
    least, _ = work.least_time_s(nbytes, work.ingest_flops(n, c["gate_res"]),
                                 run.peak)
    return 100.0 * least / t


def useful_model_flops(run: RunView) -> int:
    """Admitted frames of each camera through its model."""
    return sum(run.rec.admitted[k] * run.model_flops[k] for k in KINDS)


def mfu(run: RunView) -> Optional[float]:
    """Percent: useful model operations (admitted frames through their
    model) over the summed fused-dispatch time x chips x peak."""
    busy = sum(d for _, d in run.rec.ticks)
    if busy <= 0:
        return None
    return (100.0 * useful_model_flops(run)
            / (busy * run.chips * run.peak["flops"]))


def device_idle_share(run: RunView) -> Optional[float]:
    """Percent of the traced window in which no operation ran on the
    device (averaged over the cell's chips)."""
    if run.red is None:
        return None
    return 100.0 * run.red.idle_share
