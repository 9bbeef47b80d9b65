"""One run of one cell: set-up, the measured window, the comparison.

Set-up (``setup_s``, from process start to the first timed tick): JAX
and the chips, the frame pools and the weights (each one jitted call
from the seed), the fleet with the fused tick's warm-up, every vehicle's
join, and a spell of warm traffic through the same loop the window uses,
so nothing compiles inside the window; what that traffic left queued is
resolved before the window's capture schedule starts.  Then the window
runs for
``seconds``; with ``trace`` the profiler records it, and the per-layer
metrics are read instead of the end-to-end ones.  After the window the
driver stops capturing and drains the window's frames, the chips' memory
peak is read, the fleet is freed, and the kept ticks are compared with
the configuration's plain reference.
"""
from __future__ import annotations

import gc
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import check, drive, fleet, frames, layers, peaks, spec, trace
from chipbench.drive import INNER, OUTER

DRAIN_LIMIT_S = 60.0
CALIB_FRAMES = 8           # frames of each camera that set the head biases


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileCounter:
    """Counts XLA compilations (persistent-cache loads included)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.n += 1


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def devices(chips: int, require_tpu: bool) -> list:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def _memory_peak(devs) -> int:
    """The peak bytes in use on the fullest of ``devs``."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)


def _percentile(xs, q) -> Optional[float]:
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else None


def end_to_end(name: str, rec: drive.Record, setup_s: float):
    if name == "frames_per_s":
        return rec.resolved / (rec.end - rec.start)
    if name == "turnaround_p95_ms":
        v = _percentile(rec.turnaround_s, 95)
        return None if v is None else 1e3 * v
    if name == "turnaround_p50_ms":
        v = _percentile(rec.turnaround_s, 50)
        return None if v is None else 1e3 * v
    if name == "setup_s":
        return setup_s
    raise KeyError(f"no end-to-end metric {name!r}")


def run_view(cfg: dict, ref, rec: drive.Record, red, peak: dict,
             chips: int) -> layers.RunView:
    """What the per-layer readers read of a run: the window, its trace,
    and each camera's operations a frame as the configuration's reference
    counts them."""
    return layers.RunView(cfg=cfg, rec=rec, red=red, peak=peak, chips=chips,
                          model_flops={k: ref.model_flops(cfg, k)
                                       for k in drive.KINDS})


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool, *,
        t_start: float, require_tpu: bool = True,
        keep_trace: Optional[Path] = None, samples: int = 32,
        control: bool = False) -> dict:
    """Run ``cell`` once and return its result line (a dict).  With
    ``control`` the result also holds the control's numbers under
    ``"control"``: the verdict and numbers of the reference at lower
    precision, judged by the same limits in the program's place on the
    same kept ticks."""
    cfg, traffic = cell.config, cell.traffic
    devs = devices(cell.chips, require_tpu)
    dev = devs[0]
    peak = peaks.peaks(dev.device_kind) if require_tpu else None
    cache = None
    if require_tpu:
        from repro.compile_cache import enable_compile_cache
        cache = enable_compile_cache()
        # cache every program, however quick to compile, so that only a
        # checkout's first run compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()
    t_jax = time.perf_counter()

    w_outer, w_inner, w_params, w_sample = frames.seed_words(seed, 4)
    pools = {kind: frames.make_pool(traffic["cameras"][kind],
                                    frames=traffic["pool_frames"],
                                    res=cfg["frame_res"], seed=w)
             for kind, w in ((OUTER, w_outer), (INNER, w_inner))}
    t_pool = time.perf_counter()
    ref = spec.load_module(cell.reference)
    calib = {k: ref.downscale(jnp.asarray(p[:CALIB_FRAMES]),
                              spec.model_res(cfg, k))
             for k, p in pools.items()}
    params = jax.block_until_ready(ref.make_params(cfg, w_params, calib))
    t_params = time.perf_counter()

    replicas = fleet.engines(cfg)
    if require_tpu and any(r._interpret is not False for r in replicas):
        raise RuntimeError("the Pallas kernels would run interpreted")
    t_engines = time.perf_counter()
    gw = fleet.build(cfg, params, replicas)
    t_fleet = time.perf_counter()

    sampler = drive.Sampler(gw, samples, w_sample)
    drv = drive.Driver(gw, cfg, traffic, pools, sampler=sampler)
    drv.join()
    drv.start_schedule()
    warm = drive.Record()
    drv.run(time.perf_counter() + float(traffic["warm_s"]),
            float("inf"), warm)
    # the window starts with no frame waiting: what the warm traffic left
    # queued is resolved, and the capture schedule starts anew
    drv.drain(warm, DRAIN_LIMIT_S)
    drv.start_schedule()
    t_warm = time.perf_counter()
    setup_s = t_warm - t_start

    trace_dir = None
    if traced:
        trace_dir = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
        # the device only, and no Python tracer: host tracing records
        # every chunk of the runtime's host-side transposes and slows the
        # host several times over; the driver keeps its own host spans
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir.name, profiler_options=opts)
        drv.spans = []
    rec = drive.Record()
    compiles0 = counter.n
    mem_warm = _memory_peak(devs)
    rec.start = time.perf_counter()
    sampler.arm(rec.start, seconds)
    drv.run(rec.start + seconds, rec.start, rec)
    rec.end = time.perf_counter()
    sampler.stop()
    compiles = counter.n - compiles0
    red = None
    # the window's last frames are resolved before the profiler stops: its
    # stop writes the trace, which can take seconds they would wait out
    drv.drain(rec, DRAIN_LIMIT_S)
    if traced:
        jax.profiler.stop_trace()
        spans, drv.spans = drv.spans, None
    mem = _memory_peak(devs)
    if traced:
        path = trace.find_xplane(Path(trace_dir.name))
        window = (rec.start, rec.end)
        if keep_trace is not None:
            keep_trace.mkdir(parents=True, exist_ok=True)
            (keep_trace / f"{cell.name}.xplane.pb").write_bytes(
                path.read_bytes())
            trace.save_host(keep_trace / f"{cell.name}.host.json", window,
                            spans, drv.dispatch_ticks)
        red = trace.reduce(path, len(devs), window, spans,
                           drv.dispatch_ticks)
        trace_dir.cleanup()
        log(f"trace: device clock offset known to {red.offset_slack_s} s")

    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
        f"compile cache {cache}")
    log(f"setup_s split: jax_init={t_jax - t_start} "
        f"frame_pools={t_pool - t_jax} weights={t_params - t_pool} "
        f"engines={t_engines - t_params} fleet_build_and_warm="
        f"{t_fleet - t_engines} join_and_warm_traffic={t_warm - t_fleet}")
    log(f"fleet: mode={gw._fleet.mode} replicas={cfg['replicas']} "
        f"slots={cfg['slots']} vehicles={drv.V}")
    log(f"window: seconds={rec.end - rec.start} ticks={len(rec.ticks)} "
        f"resolved={rec.resolved} admitted={rec.admitted} "
        f"attempted={rec.attempted} failed={rec.failed}")
    log(f"compiles in window: {compiles}")
    log(f"memory peak bytes: {mem} (before the window: {mem_warm}); "
        f"ticks kept for the comparison: {len(sampler.kept)}")
    if rec.turnaround_s:
        lat, cap = np.asarray(rec.turnaround_s), np.asarray(rec.captured_s)
        mid = rec.start + (rec.end - rec.start) / 2
        halves = [1e3 * float(np.percentile(lat[m], 95)) if m.any() else None
                  for m in (cap < mid, cap >= mid)]
        log(f"turnaround ms: p50={1e3 * np.percentile(lat, 50)} "
            f"p95={1e3 * np.percentile(lat, 95)} "
            f"p99={1e3 * np.percentile(lat, 99)} max={1e3 * lat.max()} "
            f"p95 by half of the window={halves}")
    if rec.lateness_s:
        log(f"generator lateness ms: p50={1e3 * _percentile(rec.lateness_s, 50)}"
            f" p95={1e3 * _percentile(rec.lateness_s, 95)} "
            f"max={1e3 * max(rec.lateness_s)}")

    metrics = {}
    if traced:
        view = run_view(cfg, ref, rec, red, peak, len(devs))
        for m in cell.per_layer:
            v = spec.load_reader(cell.readers[m["name"]])(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            v = end_to_end(m["name"], rec, setup_s)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the comparison runs after the window, on the kept ticks only
    kept = sampler.host()
    del gw, drv, sampler, replicas
    gc.collect()
    limits = cell.limits
    t_check = time.perf_counter()
    numbers = check.compare(kept, pools, cfg, ref, params)
    ok, rows = check.verdict(numbers, limits)
    log(f"comparison s: {time.perf_counter() - t_check}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": int(mem)}
    result = {"correct": bool(ok), "attempted": rec.attempted,
              "failed": rec.failed, "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = red.mean_busy_s
        device["window_s"] = red.window_s
        result["breakdown"] = trace.breakdown(red)
    if control:
        numbers = check.compare(kept, pools, cfg, ref, params, control=True)
        c_ok, c_rows = check.verdict(numbers, limits)
        result["control"] = {"correct": bool(c_ok),
                             "checks": {n: {"value": v, "limit": lim}
                                        for n, v, lim in c_rows}}
        for n, v, lim in c_rows:
            log(f"control check {n}: {v} limit {lim}")
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    for n, v, lim in rows:
        log(f"check {n}: {v} limit {lim}")
    return result
