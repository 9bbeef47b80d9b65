"""A cell of the benchmark shrunk so that a CPU test can run it whole:
the same harness, driver, fleet and comparison, with the Pallas kernels
interpreted."""
from __future__ import annotations

import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parents[1] / "src"), str(BENCH)]

from chipbench import harness, spec  # noqa: E402

# the sizes, not the fleet's geometry: a cell keeps its replicas and mode
SHRINK = dict(slots=4, frame_res=128, input_res=64, gate_res=16,
              gate_block=4)


def cell(name: str = "eda192-motion-sat", **traffic) -> spec.Cell:
    c = spec.resolve(name)
    c.config.update(SHRINK)
    c.traffic.update(dict(pool_frames=12, warm_s=0.2), **traffic)
    if c.traffic["loop"] == "open":
        c.traffic.setdefault("vehicles", 3)
        c.traffic["vehicles"] = min(c.traffic["vehicles"], 3)
    return c


def run(c: spec.Cell, seed: int = 7, seconds: float = 0.4, **kw) -> dict:
    """One run of ``c`` on the CPU; no persistent compile cache."""
    from repro.streams import fleet_step
    fleet_step._build_fused.cache_clear()
    try:
        return harness.run(c, seed, seconds, False,
                           t_start=time.perf_counter(), require_tpu=False,
                           samples=6, **kw)
    finally:
        fleet_step._build_fused.cache_clear()
