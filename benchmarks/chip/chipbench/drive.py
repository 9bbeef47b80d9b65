"""The traffic driver: one general generator for every mix.

A mix (``traffic/<name>.json``) says how many vehicles join, the scene of
each camera (``frames.py``), and the loop:

* ``closed``: a vehicle's next frame pair is pushed as soon as its last
  one is resolved, so every lane is busy and nothing backs up.  A frame's
  capture time is its push.
* ``open``: every camera captures at ``fps`` on a wall-clock schedule,
  whatever the fleet does; vehicle ``v`` of ``V`` captures frame ``n`` at
  ``(n + v / V) / fps`` after the start.  Frames due are pushed before
  each tick, and a frame is timed from its scheduled capture, so a stall
  counts against every frame it delays.

Frames enter only through ``FleetGateway.join`` / ``push``, and the fleet
advances only through ``FleetGateway.tick``.  After each tick the driver
reads each stream's counters: frames trimmed for a deadline leave the
stream's backlog first (oldest first), then the processed or gated ones;
each resolved frame's turnaround runs from its capture to the end of the
tick that resolved it.  While ``spans`` is a list, the driver records
there each stretch of its host work (``push``, ``gateway.tick``,
``bookkeeping``, ``wait``) on its own clock, and in ``dispatch_ticks`` the
ticks that dispatched, so that device idle time in a trace can be put down
to what the host was doing (``trace.py``).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import numpy as np

OUTER, INNER = "outer", "inner"
KINDS = (OUTER, INNER)


def n_vehicles(traffic: dict, cfg: dict) -> int:
    """``"fill"``: one vehicle (two streams) per two lanes of the fleet."""
    v = traffic["vehicles"]
    return cfg["replicas"] * cfg["slots"] // 2 if v == "fill" else int(v)


class Stream:
    """The driver's view of one camera stream of one vehicle."""

    __slots__ = ("vehicle", "kind", "replica", "state", "log", "processed",
                 "gated", "trimmed")

    def __init__(self, vehicle: int, kind: str, replica: int, state) -> None:
        self.vehicle, self.kind, self.replica = vehicle, kind, replica
        self.state = state              # the engine's StreamState
        self.log = deque()              # (capture s, pool index, in window)
        self.processed = self.gated = self.trimmed = 0


@dataclass
class Record:
    """What one phase of the run measured."""
    ticks: List[tuple] = field(default_factory=list)   # (tick s, dispatch s)
    resolved: int = 0          # frames resolved by the phase's ticks
    # frames each camera staged in the phase's ticks (those they resolved)
    active: dict = field(default_factory=lambda: {OUTER: 0, INNER: 0})
    admitted: dict = field(default_factory=lambda: {OUTER: 0, INNER: 0})
    turnaround_s: List[float] = field(default_factory=list)
    captured_s: List[float] = field(default_factory=list)  # of the same
    lateness_s: List[float] = field(default_factory=list)
    attempted: int = 0         # frames captured in the window
    failed: int = 0            # of those: shed, trimmed or never resolved
    start: float = 0.0
    end: float = 0.0


class Driver:
    def __init__(self, gw, cfg: dict, traffic: dict, pools: dict, *,
                 sampler=None, clock=time.perf_counter,
                 sleep=time.sleep) -> None:
        self.gw, self.fleet = gw, gw._fleet
        self.traffic, self.pools = traffic, pools
        self.sampler = sampler
        self.clock, self.sleep = clock, sleep
        self.V = n_vehicles(traffic, cfg)
        self.fps = float(traffic.get("fps", cfg["fps"]))
        self.closed = traffic["loop"] == "closed"
        if traffic["loop"] not in ("closed", "open"):
            raise ValueError(f"unknown loop {traffic['loop']!r}")
        self.P = len(pools[OUTER])
        stride = int(traffic.get("offset_stride", 7))
        self.offset = [(v * stride) % self.P for v in range(self.V)]
        self.sent = [0] * self.V       # frame pairs pushed per vehicle
        self.next_j = 0                # open loop: next capture in order
        self.t0 = 0.0                  # open loop: schedule origin
        self.streams: List[Stream] = []
        self.by_vehicle: List[tuple] = []
        self.pushing = True
        self.spans: Optional[list] = None      # (name, start s, end s)
        self.dispatch_ticks: list = []        # (start s, end s)

    # ------------------------------------------------------------------
    def join(self) -> None:
        """Join every vehicle; each stream must hold a lane of its own."""
        gw = self.gw
        index = {r.name: i for i, r in enumerate(gw.replicas)}
        for v in range(self.V):
            pair = gw.join(f"v{v}")
            if pair is None:
                raise RuntimeError(f"vehicle v{v} was refused")
            streams = []
            for sess, kind in zip(pair, KINDS):
                eng = gw.replicas[index[sess.engine]]
                streams.append(Stream(v, kind, index[sess.engine],
                                      eng.streams[sess.key]))
            self.by_vehicle.append(tuple(streams))
            self.streams.extend(streams)
        waiting = [s for s in self.streams if not s.state.bound]
        if waiting:
            raise RuntimeError(f"{len(waiting)} streams found no lane: "
                               f"the mix oversubscribes the fleet")

    def start_schedule(self) -> None:
        """Capture from now on: the open loop's schedule starts here."""
        self.t0 = self.clock()
        self.next_j = 0
        self.pushing = True

    # ------------------------------------------------------------------
    def _push(self, v: int, t_cap: float, window: bool, rec: Record) -> None:
        idx = (self.offset[v] + self.sent[v]) % self.P
        self.sent[v] += 1
        ok = self.gw.push(f"v{v}", self.pools[OUTER][idx],
                          self.pools[INNER][idx])
        for s, accepted in zip(self.by_vehicle[v], ok):
            if window:
                rec.attempted += 1
            if accepted:
                s.log.append((t_cap, idx, window))
            elif window:
                rec.failed += 1

    def _due(self, j: int) -> float:
        return self.t0 + j / (self.V * self.fps)

    def _push_due(self, now: float, window_from: float, rec: Record) -> None:
        if self.closed:
            for v, pair in enumerate(self.by_vehicle):
                if not pair[0].log and not pair[1].log:
                    self._push(v, now, now >= window_from, rec)
            return
        while self._due(self.next_j) <= now:
            t = self._due(self.next_j)
            window = t >= window_from
            self._push(self.next_j % self.V, t, window, rec)
            if window:
                rec.lateness_s.append(now - t)
            self.next_j += 1

    def has_work(self) -> bool:
        return any(s.log for s in self.streams)

    # ------------------------------------------------------------------
    def _account(self, t_end: float, rec: Record, resolved: list) -> None:
        for s in self.streams:
            st = s.state
            d_trim = st.deadline_dropped - s.trimmed
            d_proc = st.processed - s.processed
            d_gate = st.gated - s.gated
            if not (d_trim or d_proc or d_gate):
                continue
            s.trimmed, s.processed, s.gated = (st.deadline_dropped,
                                               st.processed, st.gated)
            for _ in range(d_trim):
                if s.log.popleft()[2]:
                    rec.failed += 1
            for k in range(d_proc + d_gate):
                t_cap, idx, window = s.log.popleft()
                if window:
                    rec.turnaround_s.append(t_end - t_cap)
                    rec.captured_s.append(t_cap)
                resolved.append((s, idx, k < d_proc))
            rec.admitted[s.kind] += d_proc
            rec.active[s.kind] += d_proc + d_gate
        rec.resolved += len(resolved)

    def _span(self, name: str, t0: float) -> None:
        if self.spans is not None:
            self.spans.append((name, t0, self.clock()))

    def tick(self, rec: Record) -> None:
        cap = self.sampler.before() if self.sampler is not None else None
        d0 = self.fleet.dispatches
        t0 = self.clock()
        self.gw.tick()
        t1 = self.clock()
        dispatch = (self.fleet.last_dispatch_s
                    if self.fleet.dispatches != d0 else 0.0)
        rec.ticks.append((t1 - t0, dispatch))
        resolved: list = []
        self._account(t1, rec, resolved)
        if cap is not None:
            self.sampler.after(cap, resolved, self.fleet.last_masks)
        if self.spans is not None:
            self.spans.append(("gateway.tick", t0, t1))
            if dispatch:
                self.dispatch_ticks.append((t0, t1))
            self._span("bookkeeping", t1)

    def run(self, until: float, window_from: float, rec: Record) -> None:
        """Push and tick until the clock reaches ``until``; frames
        captured from ``window_from`` on are the window's."""
        while True:
            now = self.clock()
            if now >= until:
                return
            if self.pushing:
                self._push_due(now, window_from, rec)
                self._span("push", now)
            if not self.has_work():
                t = self.clock()
                nxt = until if self.closed else self._due(self.next_j)
                self.sleep(max(0.0, min(nxt, until) - t))
                self._span("wait", t)
                continue
            self.tick(rec)

    def drain(self, rec: Record, limit_s: float) -> None:
        """Stop capturing and tick until every window frame is resolved;
        what is left after ``limit_s`` never came, and failed."""
        self.pushing = False
        deadline = self.clock() + limit_s
        while self.has_work() and self.clock() < deadline:
            self.tick(rec)
        rec.failed += sum(1 for s in self.streams for e in s.log if e[2])


class Sampler:
    """Keeps what the comparison needs of ``k`` window ticks, spread over
    the window and drawn from the seed: the window is cut into ``k``
    equal stretches, a time is drawn uniformly within each (``arm``), and
    the first tick to start after it is kept.  Of a kept tick: each
    replica's batch pools, gate references and host thresholds before the
    tick, its pools and references after it, the tick's masks, and which
    pool frame each resolved lane staged.  A kept tick's device arrays
    start their copy to the host when it is kept and are swapped for the
    host copies when the next is kept, by when the copy has long ended:
    the chip holds at most two kept ticks, and the window pays the same
    small cost for each, spread evenly over it."""

    def __init__(self, gw, k: int, seed: int,
                 clock=time.perf_counter) -> None:
        self.gw, self.k, self.clock = gw, k, clock
        self.rng = np.random.default_rng(seed)
        self.due: deque = deque()       # times after which a tick is kept
        self.kept: List[dict] = []
        self.pending = False            # the last kept tick is on the device

    def arm(self, start: float, seconds: float) -> None:
        step = seconds / self.k
        self.due = deque(start + step * (np.arange(self.k)
                                         + self.rng.random(self.k)))

    def stop(self) -> None:
        self.due.clear()

    def _state(self, before: bool) -> list:
        out = []
        for r in self.gw.replicas:
            row = {}
            for kind in KINDS:
                g = r.gates[kind]
                row[kind] = {"batch": r.batches[kind], "refs": g.refs}
                if before:
                    row[kind]["thresh"] = g.thresh.copy()
                    row[kind]["has_ref"] = g.has_ref.copy()
            out.append(row)
        return out

    def _settle(self) -> None:
        if self.pending:
            self.kept[-1] = jax.tree.map(np.asarray, self.kept[-1])
            self.pending = False

    def before(self):
        if not self.due or self.clock() < self.due[0]:
            return None
        while self.due and self.clock() >= self.due[0]:
            self.due.popleft()
        self._settle()
        return self._state(before=True)

    def after(self, before: list, resolved: list, masks: np.ndarray) -> None:
        lanes = [(s.replica, s.kind, s.state.lane, idx)
                 for s, idx, _ in resolved]
        rec = {"before": before, "after": self._state(before=False),
               "masks": masks, "lanes": lanes}
        for leaf in jax.tree.leaves(rec):
            if isinstance(leaf, jax.Array):
                leaf.copy_to_host_async()
        self.kept.append(rec)
        self.pending = True

    def host(self) -> list:
        """The kept ticks, every array on the host."""
        self._settle()
        return self.kept
