"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

The harness traces the device only: the host tracer would record every
chunk of the runtime's host-side transposes (hundreds of thousands of
events a tick) and slow the host several times over.  What the host did
comes instead from the driver's own spans (``drive.py``), on its clock.

* Device planes are those named ``/device:TPU:<n>``.  Their ``XLA Ops``
  line holds one event per operation run on the device, named by its
  whole HLO instruction; their ``XLA Modules`` line one event per program
  run, the fused tick among them (``jit_fused``).
* The device clock is put on the host's by one offset: the k-th fused
  program of device 0 must run inside the k-th tick that dispatched, so
  the offset lies between the latest ``program end - tick end`` and the
  earliest ``program start - tick start``; the middle of that range is
  taken.
* Everything is clipped to the measured window.

From these: each device's busy time (the union of its operations'
intervals), the device time of each operation, and the device's idle
time split over the host spans that overlap it.
"""
from __future__ import annotations

import json
from bisect import bisect_right
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
FUSED = "jit_fused"
HOST_NAMES = ("push", "gateway.tick", "bookkeeping", "wait")


@dataclass
class Reduced:
    window_s: float
    devices: int
    busy_s: List[float]                       # per device
    op_s: Dict[str, float]                    # summed over devices
    idle_by_host: Dict[str, float]            # summed over devices
    offset_slack_s: float                     # width of the offset range

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s / self.window_s

    def kernel_s(self, part: str) -> float:
        """Device seconds of the operations whose name holds ``part``
        (a Pallas call is named after the jitted wrapper that makes it,
        such as ``_ingest_frame_jit``), or 0."""
        return sum(t for n, t in self.op_s.items() if part in n)


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(name: str) -> str:
    """An operation's instruction name: the device line names each event
    by its whole HLO instruction (``%name = f32[...] op(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _overlap(spans, starts, s, e) -> float:
    """Length of the disjoint sorted ``spans`` inside [s, e)."""
    i = max(bisect_right(starts, s) - 1, 0)
    total = 0.0
    while i < len(spans) and spans[i][0] < e:
        a, b = spans[i]
        if b > s:
            total += min(b, e) - max(a, s)
        i += 1
    return total


def _device_planes(planes, devices: int):
    out = {}
    for p in planes:
        if p.name.startswith(DEVICE_PREFIX):
            n = p.name[len(DEVICE_PREFIX):]
            if n.isdigit() and int(n) < devices:
                out[int(n)] = p
    return [out[k] for k in sorted(out)]


def _offset(plane, dispatch_ticks):
    """(offset in s, slack in s) putting ``plane``'s clock on the host's."""
    runs = sorted((ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                  for line in plane.lines if line.name == MODULES_LINE
                  for ev in line.events if ev.name.startswith(FUSED))
    pairs = list(zip(runs, dispatch_ticks))
    if not pairs:
        raise ValueError("the trace holds no fused tick to align the "
                         "device clock with")
    lo = max(e - te for (_, e), (_, te) in pairs)
    hi = min(s - ts for (s, _), (ts, _) in pairs)
    return (lo + hi) / 2, hi - lo


def reduce_planes(planes, devices: int, window, spans,
                  dispatch_ticks) -> Reduced:
    """Reduce already-read planes (objects with ``name`` and ``lines``,
    each line with ``name`` and ``events`` that have ``name``,
    ``start_ns`` and ``duration_ns``, as ``jax.profiler.ProfileData``
    gives them) over the host window ``(lo, hi)`` in seconds, with the
    driver's host ``spans`` and ``dispatch_ticks``.  Only the first
    ``devices`` TPU devices (the chips the cell uses) count."""
    dev = _device_planes(planes, devices)
    if not dev:
        raise ValueError("the trace holds no TPU device plane")
    shift, slack = _offset(dev[0], dispatch_ticks)
    lo, hi = window
    host = {}
    for name in HOST_NAMES:
        got = _union([(max(s, lo), min(e, hi)) for n, s, e in spans
                      if n == name and e > lo and s < hi])
        host[name] = (got, [a for a, _ in got])
    busy, op_s, idle_by = [], defaultdict(float), defaultdict(float)
    for plane in dev:
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = ev.start_ns * 1e-9 - shift
                e = s + ev.duration_ns * 1e-9
                if e > lo and s < hi:
                    ops.append((op_name(ev.name), max(s, lo), min(e, hi)))
        for n, s, e in ops:
            op_s[n] += e - s
        merged = _union([(s, e) for _, s, e in ops])
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            covered = 0.0
            for name, (got, starts) in host.items():
                ov = _overlap(got, starts, s, e)
                idle_by[name] += ov
                covered += ov
            # the driver's spans are disjoint: the rest of a gap is host
            # time outside them (its own bookkeeping between spans, the GC)
            idle_by["other"] += max(e - s - covered, 0.0)
    return Reduced(window_s=hi - lo, devices=len(dev), busy_s=busy,
                   op_s=dict(op_s),
                   idle_by_host={k: v for k, v in idle_by.items() if v > 0},
                   offset_slack_s=slack)


def reduce(path: Path, devices: int, window, spans,
           dispatch_ticks) -> Reduced:
    from jax.profiler import ProfileData
    planes = ProfileData.from_file(str(path)).planes
    return reduce_planes(planes, devices, window, spans, dispatch_ticks)


def save_host(path: Path, window, spans, dispatch_ticks) -> None:
    """What the reduction needs of the host, beside a kept trace."""
    path.write_text(json.dumps({"window": list(window), "spans": spans,
                                "dispatch_ticks": dispatch_ticks}))


def load_host(path: Path):
    d = json.loads(Path(path).read_text())
    return (tuple(d["window"]), [tuple(s) for s in d["spans"]],
            [tuple(t) for t in d["dispatch_ticks"]])


def breakdown(red: Reduced, n: int = 10) -> dict:
    ops = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:n]
    idle = sorted(red.idle_by_host.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
