"""Put the program's own spans on the device trace's clock, and read them.

The program records its spans (``repro.obs.tracing.SpanTracer``, attached
with ``FleetGateway.attach_obs``) on ``time.perf_counter``, the clock the
driver's spans (``drive.py``) use too.  Its export carries clock anchors,
pairs of ``(perf_counter ns, unix ns)`` read back to back at attach and
at export (``otherData.clock_anchors``).  The profiler dates its session
in unix ns (``profile_start_time``, ``Task Environment`` plane) and each
device event from that start.  So a device event lies at

    unix ns = event ns + profile_start_time
    host s  = perf ns of that unix ns, interpolated between the anchors

with no fit.  On that clock:

* each device's fused program (``jit_fused``) is paired with the
  ``fused_dispatch`` span that holds its start: the host's time before
  the device starts it (argument upload, host transposes, launch) and
  after it ends (the host's wake-up) are read per dispatch;
* the device's idle time inside the driver's ``gateway.tick`` spans is
  split by the innermost program span over it, and what no program span
  covers is the remainder;
* a tick over four times the window's median is put down to the span
  that holds its excess.

A run with no program spans or no anchors gives nothing (``None``).
"""
from __future__ import annotations

import statistics
from bisect import bisect_right
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import trace

TASK_ENV = "Task Environment"
START_STAT = "profile_start_time"
DISPATCH = "fused_dispatch"
TICK = "gateway.tick"               # the driver's span around gw.tick()
STALL_RATIO = 4.0                   # a stall: a tick over 4x the median


@dataclass
class Span:
    name: str
    lane: str
    start: float                    # perf_counter s
    end: float


@dataclass
class SpanReduction:
    window_s: float
    ticks: int                      # the program's window fleet ticks
    stage_s: float                  # summed window ``stage`` spans
    commit_s: float                 # summed window ``fleet.commit`` spans
    runs: int                       # window fused programs, all devices
    paired: int                     # of those: started in a dispatch span
    inside: int                     # of those: ended inside it too
    upload_lag_s: List[float]       # per paired run: run start - dispatch
    device_s: List[float]           # per paired run: its length
    readback_lag_s: List[float]     # per paired run: dispatch end - run
    dispatch_s: List[float]         # per paired run: the dispatch span
    idle_split: Dict[str, float]    # ``gateway.tick:<span>`` + remainder
    anchor_gaps_ns: List[int]
    drift_ppm: float
    anchored_offset_s: float        # host = device - offset, mid-window
    fitted_offset_s: Optional[float] = None   # trace.py's, and its slack
    fitted_slack_s: Optional[float] = None
    stalls: List[dict] = field(default_factory=list)

    @property
    def inside_share(self) -> float:
        return self.inside / self.runs if self.runs else 0.0

    @property
    def tick_idle_s(self) -> float:
        return sum(self.idle_split.values())

    @property
    def remainder_share(self) -> float:
        total = self.tick_idle_s
        return self.idle_split.get(TICK, 0.0) / total if total else 0.0

    def per_tick_ms(self, total_s: float) -> Optional[float]:
        return 1e3 * total_s / self.ticks if self.ticks else None

    def mean_ms(self, xs: Sequence[float]) -> Optional[float]:
        return 1e3 * sum(xs) / len(xs) if xs else None


# ---------------------------------------------------------------------------
# the shared clock
# ---------------------------------------------------------------------------
def profile_start_ns(planes) -> Optional[int]:
    """The profiling session's start in unix ns, or None."""
    for p in planes:
        if p.name == TASK_ENV:
            for key, value in p.stats:
                if key == START_STAT:
                    return int(value)
    return None


class SharedClock:
    """Device event ns (from the session's start) to host perf seconds,
    through the first and the last anchor."""

    def __init__(self, anchors: Sequence[dict], start_ns: int) -> None:
        a, b = anchors[0], anchors[-1]
        span = b["perf_ns"] - a["perf_ns"]
        self.rate = (b["unix_ns"] - a["unix_ns"]) / span if span > 0 else 1.0
        self.perf0 = a["perf_ns"]
        # integers first: unix ns lie beyond a double's exact range
        self.base = start_ns - a["unix_ns"]

    def host_s(self, device_ns: float) -> float:
        return (self.perf0 + (device_ns + self.base) / self.rate) * 1e-9

    def device_ns(self, host_s: float) -> float:
        return (host_s * 1e9 - self.perf0) * self.rate - self.base


# ---------------------------------------------------------------------------
# program spans
# ---------------------------------------------------------------------------
def program_spans(export: dict) -> List[Span]:
    """The complete spans of a ``SpanTracer.to_chrome()`` export."""
    events = export.get("traceEvents", [])
    lanes = {e["tid"]: e["args"]["name"] for e in events if e["ph"] == "M"}
    return [Span(e["name"], lanes.get(e["tid"], str(e["tid"])),
                 e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6)
            for e in events if e["ph"] == "X"]


def leaves(spans: Sequence[Span]) -> List[Tuple[float, float, str]]:
    """The host's time cut into disjoint stretches, each named by the
    innermost span over it.  Spans of one thread nest; a child that
    outlasts its parent by a rounding is cut at the parent's end."""
    out: List[Tuple[float, float, str]] = []
    stack: List[list] = []                   # [end, name]
    t = float("-inf")
    for sp in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1][0] <= sp.start:
            end, name = stack.pop()
            if t < end:
                out.append((t, end, name))
                t = end
        if stack and t < sp.start:
            out.append((t, sp.start, stack[-1][1]))
        t = max(t, sp.start)
        end = min(sp.end, stack[-1][0]) if stack else sp.end
        stack.append([end, sp.name])
    while stack:
        end, name = stack.pop()
        if t < end:
            out.append((t, end, name))
            t = end
    return out


def _intersect(a, b):
    """Pieces common to two sorted lists of disjoint intervals; each
    piece keeps the further fields (a name, an index) of its ``a`` and
    then its ``b`` interval."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e) + tuple(a[i][2:]) + tuple(b[j][2:]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _by_name(pieces) -> Dict[str, float]:
    out: Dict[str, float] = defaultdict(float)
    for s, e, name in pieces:
        out[name] += e - s
    return out


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------
def _device_runs(plane, clock: SharedClock, line: str, lo: float,
                 hi: float, fused_only: bool):
    out = []
    for ln in plane.lines:
        if ln.name != line:
            continue
        for ev in ln.events:
            if fused_only and not ev.name.startswith(trace.FUSED):
                continue
            s = clock.host_s(ev.start_ns)
            e = clock.host_s(ev.start_ns + ev.duration_ns)
            if e > lo and s < hi:
                out.append((s, e))
    return out


def _stalls(ticks, lv, lo: float, lags) -> List[dict]:
    """Ticks over ``STALL_RATIO`` times the median, each with the span
    whose time in it most exceeds that span's median time a tick, and
    the upload and read-back lags of device 0's fused program in it
    (``lags``: sorted ``(dispatch start, upload s, read-back s)``)."""
    if not ticks:
        return []
    per_tick = [defaultdict(float) for _ in ticks]
    indexed = [(s, e, k) for k, (s, e) in enumerate(ticks)]
    for s, e, name, k in _intersect(lv, indexed):
        per_tick[k][name] += e - s
    for t, names in zip(ticks, per_tick):
        names[TICK] = (t[1] - t[0]) - sum(names.values())
    med = statistics.median(e - s for s, e in ticks)
    names = {n for d in per_tick for n in d}
    typical = {n: statistics.median(d.get(n, 0.0) for d in per_tick)
               for n in names}
    out = []
    for (s, e), d in zip(ticks, per_tick):
        if e - s <= STALL_RATIO * med:
            continue
        excess = {n: v - typical[n] for n, v in d.items()}
        held = max(excess, key=excess.get)
        line = {"at_s": s - lo, "tick_ms": 1e3 * (e - s),
                "median_ms": 1e3 * med, "span": held,
                "excess_ms": 1e3 * excess[held]}
        k = bisect_right(lags, (s,))
        if k < len(lags) and lags[k][0] < e:
            line["upload_lag_ms"] = 1e3 * lags[k][1]
            line["readback_lag_ms"] = 1e3 * lags[k][2]
        out.append(line)
    return out


def reduce_planes(planes, devices: int, window, driver_spans,
                  dispatch_ticks, export: Optional[dict]
                  ) -> Optional[SpanReduction]:
    """Reduce the device ``planes`` (as ``trace.reduce_planes`` reads
    them), the driver's spans and the program's ``export`` over the host
    window ``(lo, hi)``; None where the program left no span or anchor,
    or the trace no session start."""
    if not export:
        return None
    planes = list(planes)           # ProfileData hands an iterator
    anchors = export.get("otherData", {}).get("clock_anchors") or []
    start = profile_start_ns(planes)
    prog = program_spans(export)
    if not anchors or start is None or not prog:
        return None
    clock = SharedClock(anchors, start)
    lo, hi = window
    dev = trace._device_planes(planes, devices)
    if not dev:
        raise ValueError("the trace holds no TPU device plane")

    def in_window(sp: Span) -> bool:
        return lo <= sp.start < hi

    dispatches = sorted((sp.start, sp.end) for sp in prog
                        if sp.name == DISPATCH)
    starts = [s for s, _ in dispatches]
    lv = leaves(prog)
    ticks = trace._union([(max(s, lo), min(e, hi))
                          for n, s, e in driver_spans
                          if n == TICK and e > lo and s < hi])
    runs = paired = inside = 0
    up, busy_dev, down, whole = [], [], [], []
    lags = []                       # device 0's, for the stall lines
    split: Dict[str, float] = defaultdict(float)
    for plane in dev:
        for s, e in _device_runs(plane, clock, trace.MODULES_LINE, lo, hi,
                                 True):
            if s < lo:
                continue
            runs += 1
            k = bisect_right(starts, s) - 1
            if k < 0 or dispatches[k][1] < s:
                continue
            ds, de = dispatches[k]
            paired += 1
            inside += e <= de
            up.append(s - ds)
            if plane is dev[0]:
                lags.append((ds, s - ds, de - e))
            busy_dev.append(e - s)
            down.append(de - e)
            whole.append(de - ds)
        ops = [(max(s, lo), min(e, hi)) for s, e in
               _device_runs(plane, clock, trace.OPS_LINE, lo, hi, False)]
        merged = trace._union(ops)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        in_tick = _intersect(idle, ticks)
        named = _by_name(_intersect(in_tick, lv))
        for name, v in named.items():
            split[f"{TICK}:{name}"] += v
        split[TICK] += max(sum(e - s for s, e in in_tick)
                           - sum(named.values()), 0.0)
    mid = (lo + hi) / 2
    fitted = (trace._offset(dev[0], dispatch_ticks) if dispatch_ticks
              else (None, None))
    fleet_ticks = [sp for sp in prog if sp.name == "fleet.tick"
                   and in_window(sp)]
    return SpanReduction(
        window_s=hi - lo, ticks=len(fleet_ticks),
        stage_s=sum(sp.end - sp.start for sp in prog
                    if sp.name == "stage" and in_window(sp)),
        commit_s=sum(sp.end - sp.start for sp in prog
                     if sp.name == "fleet.commit" and in_window(sp)),
        runs=runs, paired=paired, inside=inside, upload_lag_s=up,
        device_s=busy_dev, readback_lag_s=down, dispatch_s=whole,
        idle_split=dict(split),
        anchor_gaps_ns=[a["gap_ns"] for a in anchors],
        drift_ppm=(clock.rate - 1.0) * 1e6,
        anchored_offset_s=clock.device_ns(mid) * 1e-9 - mid,
        fitted_offset_s=fitted[0], fitted_slack_s=fitted[1],
        stalls=_stalls(ticks, lv, lo, sorted(lags)))


def reduce(path, devices: int, window, driver_spans, dispatch_ticks,
           export: Optional[dict]) -> Optional[SpanReduction]:
    from jax.profiler import ProfileData
    planes = ProfileData.from_file(str(path)).planes
    return reduce_planes(planes, devices, window, driver_spans,
                         dispatch_ticks, export)


def summary(red: SpanReduction) -> dict:
    """The one line a traced run prints: the idle split, the remainder,
    the dispatch pairing, the anchors, and how far the anchored offset
    lies from ``trace.py``'s fitted one, beside the fit's slack."""
    out = {
        "idle_split_s": dict(sorted(red.idle_split.items(),
                                    key=lambda kv: -kv[1])),
        "tick_idle_s": red.tick_idle_s,
        "remainder_share": red.remainder_share,
        "runs": red.runs, "paired": red.paired, "inside": red.inside,
        "inside_share": red.inside_share,
        "upload_lag_ms": red.mean_ms(red.upload_lag_s),
        "device_ms": red.mean_ms(red.device_s),
        "readback_lag_ms": red.mean_ms(red.readback_lag_s),
        "fused_dispatch_ms": red.mean_ms(red.dispatch_s),
        "stage_ms": red.per_tick_ms(red.stage_s),
        "commit_ms": red.per_tick_ms(red.commit_s),
        "ticks": red.ticks,
        "anchor_gaps_ns": red.anchor_gaps_ns,
        "drift_ppm": red.drift_ppm,
        "anchored_offset_s": red.anchored_offset_s,
        "fitted_offset_s": red.fitted_offset_s,
        "fitted_slack_s": red.fitted_slack_s,
        "stalls": red.stalls,
    }
    if red.fitted_offset_s is not None:
        out["anchored_minus_fitted_s"] = (red.anchored_offset_s
                                          - red.fitted_offset_s)
    return out


# ---------------------------------------------------------------------------
# what the metric readers (``metrics/<name>.py``) take
# ---------------------------------------------------------------------------
def _of(run) -> Optional[SpanReduction]:
    """A run's span reduction: ``RunView.spans`` where the harness fills
    it, else None (the metric is then left out of the result line)."""
    return getattr(run, "spans", None)


def stage_ms(run) -> Optional[float]:
    red = _of(run)
    return None if red is None else red.per_tick_ms(red.stage_s)


def commit_ms(run) -> Optional[float]:
    red = _of(run)
    return None if red is None else red.per_tick_ms(red.commit_s)


def upload_lag_ms(run) -> Optional[float]:
    red = _of(run)
    return None if red is None else red.mean_ms(red.upload_lag_s)


def readback_lag_ms(run) -> Optional[float]:
    red = _of(run)
    return None if red is None else red.mean_ms(red.readback_lag_s)
