#!/usr/bin/env python3
"""Smoke run of the served vision fleet on a TPU.

    python chip_smoke.py             # one chip: the fused fleet tick
    python chip_smoke.py --chips 4   # four chips: the replica mesh

One chip: two ``VisionServeEngine`` replicas at the paper's model
geometry (384 px frames, ``eda-detector`` / ``eda-pose`` at input 192,
channels 16..256, 8 slots, random weights from fixed seeds) serve eight
synthetic dash-cam vehicles through ``FleetGateway(parallel=True)`` for one
second of video at 10 fps, then drain.  It checks that the Pallas kernels
run compiled (not interpreted), that frames were processed, that the ledger
conserves every frame, that nothing compiles after the fleet's warm-up, and
that the on-chip ``ingest_frame`` matches ``ref.ingest_frame_ref`` within
the fp32 tolerance of ``tests/kernel_harness.py``.

Four chips (``--chips 4``): four replicas under ``mode="shard_map"``, one
per chip, serve sixteen vehicles; the same frames then go through the same
fleet under ``mode="vmap"`` on one chip, and the admit and flag masks of
every tick must be equal.

Runs in this one process and starts no other.  Exits non-zero, without a
result line, when JAX finds no TPU or any phase fails.  The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "tests")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from kernel_harness import TIGHT  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.data import DashCamSource  # noqa: E402
from repro.kernels import ref, vision_ops  # noqa: E402
from repro.streams import FleetGateway, VisionServeEngine  # noqa: E402
from repro.streams.fleet_step import resolve_mode  # noqa: E402

FRAME_RES = 384
INPUT_RES = 192        # configs/eda_vision.py: the paper's model input
SLOTS = 8
FPS = 10
SECONDS = 1.0
SEED = 0
OUTER = "outer"          # VisionServeEngine's outward-camera batch pool


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileCounter:
    """Counts XLA compilations (persistent-cache loads included)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.n += 1


def build_fleet(n_replicas: int, mode=None) -> FleetGateway:
    replicas = [VisionServeEngine(f"r{i}", slots=SLOTS, frame_res=FRAME_RES,
                                  input_res=INPUT_RES, fps=FPS,
                                  use_pallas=True, rng=jax.random.key(i))
                for i in range(n_replicas)]
    bad = [r.name for r in replicas if r._interpret is not False]
    check(not bad, f"Pallas kernels would run interpreted on {bad}")
    return FleetGateway(replicas, parallel=True, fleet_mode=mode)


def drive(gw: FleetGateway, clips) -> list:
    """Join every vehicle, feed one frame pair per vehicle per tick, drain.
    Returns the masks of every fused dispatch, in order."""
    for v in range(len(clips)):
        check(gw.join(f"v{v}") is not None, f"vehicle v{v} was refused")
    fleet = gw._fleet
    masks = []

    def tick():
        before = fleet.dispatches
        gw.tick()
        if fleet.dispatches != before:
            masks.append(fleet.last_masks.copy())

    for t in range(clips[0].frames):
        for v, pair in enumerate(clips):
            gw.push(f"v{v}", pair.outer[t], pair.inner[t])
        tick()
    while any(r.has_work() for r in gw.live_replicas()):
        tick()
    return masks


def close_all(gw: FleetGateway, n_vehicles: int) -> dict:
    for v in range(n_vehicles):
        gw.leave(f"v{v}")
    gw.ledger.check()
    recs = gw.ledger.records
    return {k: sum(getattr(r, f"frames_{k}") for r in recs)
            for k in ("total", "processed", "gated", "dropped")}


def serve(n_replicas: int, n_vehicles: int, mode, counter: CompileCounter):
    """Build, warm, drive and close one fleet; print its phase lines."""
    clips = [DashCamSource(granularity_s=SECONDS, fps=FPS, res=FRAME_RES,
                           seed=SEED).pair(v) for v in range(n_vehicles)]
    t0 = time.perf_counter()
    gw = build_fleet(n_replicas, mode)
    warm_s = time.perf_counter() - t0
    fleet = gw._fleet
    interpret = sorted({r._interpret for r in gw.replicas})
    print(f"fleet: replicas={n_replicas} vehicles={n_vehicles} "
          f"mode={fleet.mode} interpret={interpret} warm_s={warm_s}")
    compiles0 = counter.n
    t0 = time.perf_counter()
    masks = drive(gw, clips)
    serve_s = time.perf_counter() - t0
    compiles = counter.n - compiles0
    placement = [sorted(d.id for d in r.batches[OUTER].devices())
                 for r in gw.replicas]
    frames = close_all(gw, n_vehicles)
    print(f"frames: offered={frames['total']} "
          f"processed={frames['processed']} gated={frames['gated']} "
          f"dropped={frames['dropped']} dispatches={fleet.dispatches} "
          f"serve_s={serve_s}")
    print("ledger: check passed")
    print(f"compiles after warm-up: {compiles}")
    check(frames["processed"] > 0, "no frame was processed")
    check(compiles == 0, f"{compiles} compiles after the fleet's warm-up")
    return fleet.mode, masks, placement


def ingest_parity() -> None:
    """On-chip ``ingest_frame`` vs ``ref.ingest_frame_ref`` on one batch of
    dash-cam frames, scored against the previous frame's gate image."""
    src = DashCamSource(granularity_s=SECONDS, fps=FPS, res=FRAME_RES,
                        seed=SEED + 1)
    clips = [src.pair(v).outer for v in range(SLOTS)]
    prev = jnp.asarray(np.stack([c[0] for c in clips]))
    frames = jnp.asarray(np.stack([c[1] for c in clips]))
    gate_res, block = 32, 8                     # MotionGate's defaults
    refs = ref.downscale_ref(prev, gate_res)
    got = vision_ops.ingest_frame(frames, refs, model_res=INPUT_RES,
                                  gate_res=gate_res, block=block,
                                  interpret=False)
    want = ref.ingest_frame_ref(frames, refs, model_res=INPUT_RES,
                                gate_res=gate_res, block=block)
    errs = {}
    for name, g, w in zip(("model", "gate", "scores"), got, want):
        g, w = np.asarray(g), np.asarray(w)
        check(g.shape == w.shape and np.isfinite(g).all(),
              f"ingest {name}: shape {g.shape} vs {w.shape} or non-finite")
        errs[name] = float(np.abs(g - w).max())
        check(np.allclose(g, w, **TIGHT),
              f"ingest {name} differs from the reference beyond {TIGHT}: "
              f"max abs error {errs[name]}")
    err = max(errs.values())
    print(f"ingest_frame vs ref: max_abs_err={err} per output={errs} "
          f"tolerance={TIGHT}")


def one_chip(counter: CompileCounter) -> None:
    print(f"resolve_mode(2) on this host: {resolve_mode(2)}")
    serve(2, 8, None, counter)
    ingest_parity()


def four_chips(counter: CompileCounter) -> None:
    check(jax.device_count() >= 4,
          f"--chips 4 needs four devices, found {jax.device_count()}")
    mode, mesh_masks, placement = serve(4, 16, "shard_map", counter)
    check(mode == "shard_map", f"mesh fleet ran mode={mode}")
    print(f"batch pool devices per replica (shard_map): {placement}")
    check(len({tuple(p) for p in placement}) == 4
          and all(len(p) == 1 for p in placement),
          f"replica state is not one replica per chip: {placement}")
    mode, vmap_masks, placement = serve(4, 16, "vmap", counter)
    print(f"batch pool devices per replica (vmap): {placement}")
    check(len(mesh_masks) == len(vmap_masks),
          f"tick counts differ: shard_map {len(mesh_masks)} vs vmap "
          f"{len(vmap_masks)}")
    for t, (a, b) in enumerate(zip(mesh_masks, vmap_masks)):
        check((a == b).all(), f"tick {t}: shard_map masks != vmap masks")
    print(f"masks equal: shard_map vs vmap over {len(mesh_masks)} ticks")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip replica-mesh check")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    print(f"backend={jax.default_backend()} device_kind={dev.device_kind} "
          f"devices={jax.device_count()} "
          f"compile_cache={enable_compile_cache()}")
    counter = CompileCounter()
    (four_chips if args.chips == 4 else one_chip)(counter)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
