"""End-to-end driver: EDA analysing synthetic dash-cam video with REAL
JAX inference (the paper's case study, §3.2.3).

Master downloads (outer, inner) clip pairs from the synthetic dash cam,
the capacity scheduler places them across three simulated phones,
segmentation splits inner clips, early stopping enforces the per-video
deadline, and the detector/pose models produce hazard/distraction flags
frame by frame.

    PYTHONPATH=src python examples/eda_dashcam_serve.py [--pairs 8]
"""
import argparse
import time

import numpy as np
import jax

from repro.compile_cache import enable_compile_cache
from repro.config import EDAConfig
from repro.configs.eda_vision import detector_config, pose_config
from repro.core.runtime import EDARuntime, PAPER_DEVICES
from repro.core.segmentation import Segment
from repro.data import DashCamSource
from repro.models import vision as V


class RealExecutor:
    """Actual model inference with per-device speed emulation."""

    SPEED = {"pixel3": 0.45, "pixel6": 0.75, "oneplus8": 1.0,
             "findx2pro": 1.1}

    def __init__(self, source: DashCamSource, res: int = 96):
        rng = jax.random.key(0)
        self.dc, self.pc = detector_config(res), pose_config(res)
        self.dp, self.pp = V.init_detector(self.dc, rng), V.init_pose(self.pc, rng)
        self.source = source

    def frame_cost_ms(self, device, stream, frames=30):
        return 6.0 / self.SPEED[device]

    def run(self, device, seg: Segment, budget: int):
        n = min(budget, seg.frame_count)
        if n == 0:
            return 0, 0.0, {}
        pair = self.source.pair(int(seg.video_id.split("_")[0][1:]))
        clip = (pair.outer if seg.stream == "outer" else
                pair.inner)[seg.frame_start: seg.frame_start + n]
        t0 = time.perf_counter()
        if seg.stream == "outer":
            flags, det = V.analyse_outer(self.dc, self.dp, clip)
            per_frame = np.asarray(flags).any(axis=1)
        else:
            per_frame, _ = V.analyse_inner(self.pc, self.pp, clip)
            per_frame = np.asarray(per_frame)
        wall = (time.perf_counter() - t0) * 1000 / self.SPEED[device]
        return n, wall, {i: {"danger": bool(per_frame[i])} for i in range(n)}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--pairs", type=int, default=8)
    ap.add_argument("--fps", type=int, default=6)
    args = ap.parse_args()

    src = DashCamSource(granularity_s=1.0, fps=args.fps, res=96, seed=7)
    rt = EDARuntime(
        eda=EDAConfig(granularity_s=1.0, fps=args.fps,
                      simulate_download_s=0.35, segmentation=True,
                      dynamic_esd=True),
        master=PAPER_DEVICES["findx2pro"],
        workers=[PAPER_DEVICES["pixel6"], PAPER_DEVICES["oneplus8"]],
        executor=RealExecutor(src))
    ledger = rt.run(args.pairs)

    print(ledger.table())
    print()
    for vid in sorted(rt.results):
        frames = rt.results[vid]
        danger = [i for i, r in sorted(frames.items()) if r["danger"]]
        kind = "hazard" if vid.endswith("out_000") or "_out" in vid else "distraction"
        status = f"{kind} frames {danger}" if danger else "clear"
        print(f"{vid:16s} {len(frames):3d} frames analysed  -> {status}")
    print(f"\nnear-real-time fraction: {ledger.real_time_fraction():.0%}")


if __name__ == "__main__":
    main()
