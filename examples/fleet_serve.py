"""Fleet-scale serving demo: N synthetic vehicles streaming (outer, inner)
dash-cam frames through the gateway into batched engine replicas.

Vehicles join staggered (churn), stream for a few seconds of video, and
leave; the gateway shards their sessions across replicas with the capacity
scheduler, the motion gate sheds near-duplicate frames, and the fleet
ledger prints the paper-style per-replica turnaround/skip table.

    PYTHONPATH=src python examples/fleet_serve.py [--vehicles 12]
"""
import argparse

import jax

from repro.compile_cache import enable_compile_cache
from repro.config import EDAConfig
from repro.data import DashCamSource
from repro.streams import FleetGateway, VisionServeEngine


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--vehicles", type=int, default=12)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--fps", type=int, default=10)
    ap.add_argument("--seconds", type=float, default=2.0,
                    help="video seconds each vehicle streams")
    ap.add_argument("--esd", type=float, default=2.0)
    ap.add_argument("--no-gate", action="store_true")
    args = ap.parse_args()

    src = DashCamSource(granularity_s=args.seconds, fps=args.fps,
                        res=64, seed=11)
    replicas = [
        VisionServeEngine(f"replica{i}", slots=args.slots, frame_res=64,
                          input_res=48, fps=args.fps,
                          eda=EDAConfig(esd=args.esd),
                          use_gate=not args.no_gate,
                          rng=jax.random.key(i))
        for i in range(args.replicas)]
    gw = FleetGateway(replicas, deadline_ms=1000.0 * args.seconds)

    frames = src.frames_per_video
    clips = {f"veh{v:02d}": src.pair(v) for v in range(args.vehicles)}
    joined, waiting = {}, list(clips)
    cursor = {}

    # interleaved join -> stream -> leave churn: a new vehicle joins every
    # other tick while earlier ones finish their clip and leave
    tick = 0
    while waiting or joined:
        if waiting and tick % 2 == 0:
            name = waiting[0]
            if gw.join(name, now_ms=float(tick)) is not None:
                waiting.pop(0)
                joined[name] = clips[name]
                cursor[name] = 0
        for name in list(joined):
            f = cursor[name]
            if f < frames:
                pair = joined[name]
                gw.push(name, pair.outer[f], pair.inner[f])
                cursor[name] = f + 1
            elif gw.backlog(name) == 0:
                gw.leave(name)
                del joined[name]
        gw.tick()
        tick += 1
    gw.drain()

    print(gw.ledger.table())
    total = sum(r.frames_processed for r in replicas)
    gated = sum(g.stats.gated for r in replicas
                for g in r.gates.values() if g is not None)
    print(f"\nvehicles={args.vehicles} replicas={args.replicas} "
          f"slots={args.slots} ticks={tick}")
    print(f"frames processed: {total}   motion-gated: {gated}   "
          f"joins refused (backpressure): {gw.refused}")
    for r in replicas:
        s = r.stats()
        print(f"  {r.name}: busy {s['busy_s'] * 1000:.0f} ms over "
              f"{s['ticks']} ticks, {s['frame_cost_ms']:.2f} ms/frame "
              f"amortised, {s['tick_cost_ms']:.2f} ms/tick latency")
    print(f"near-real-time fraction: {gw.ledger.real_time_fraction():.0%}")
    for rec in gw.ledger.records[:6]:
        print(f"  {rec.video_id:14s} {rec.frames_processed:3d}/"
              f"{rec.frames_total:3d} frames  skip {rec.skip_rate:5.1%}  "
              f"turnaround {rec.turnaround_ms:7.1f} ms")


if __name__ == "__main__":
    main()
