"""Mesh-parallel fleet tick: every replica's device work in one dispatch.

The serial ``FleetGateway.tick`` steps replicas one after another, so each
tick pays (replicas x classes) separate gate + model dispatches plus a
per-frame admission scatter, and the accelerator only ever sees one
replica's tiny batch at a time — adding replicas adds wall-clock instead
of dividing it, the opposite of the paper's parallel-devices scaling story
(§3.2.5).  ``FleetStep`` stacks the per-replica engine state along a
leading ``replica`` axis —

    batch pools   (R, slots, res, res, 3)   per model class
    stage frames  (R, slots, H, W*3)        pinned host buffers, one upload
    gate refs     (R, slots, g, g, 3)       + thresh/has_ref (R, slots)
    lane masks    (R, slots) bool           liveness is masked, not reshaped
    model params  pytrees stacked to (R, ...)

— and runs ingest → gate-score → admit-threshold → model forward for *all*
replicas in one jit containing one mapped computation per **tier group**:

  * replicas are grouped by model geometry — ``(dc, pc, input_res,
    batch dtype)``, i.e. by :class:`~repro.streams.tiers.TierSpec` in a
    tiered fleet.  A uniform fleet is one group and compiles to exactly
    the pre-tier program; a mixed-tier fleet gets one vmapped body per
    group, all inside the *same* jit, so a whole heterogeneous fleet tick
    is still a single device dispatch (the 1-dispatch-per-tick contract
    ``tests/test_fleet_step`` pins);
  * ``mode="shard_map"``: ``shard_map`` over a ``mesh(("replica",))``
    (:func:`replica_mesh`): one replica per device, or ``R / D`` replicas
    per device when the fleet outnumbers the ``D`` devices, each device
    vmapping the same body over its block.  Each replica's batch pools and
    gate refs stay on its own device between ticks.  Requires a single
    tier group (a mesh axis cannot mix program shapes);
  * ``mode="vmap"``: the same stacked state through ``jax.vmap`` of the
    same body — the single-device / CPU / interpret fallback and the only
    mode for mixed-tier fleets.

Frames stage in the ingest kernels' ``(H, W*3)`` plane layout: row-major
host data that tiles the chip's ``(8, 128)`` layout as it stands (384 px
rows are 1152 = 9 x 128 lanes), so the upload needs no host transpose and
the kernels read the argument with no device relayout.  The mapped body
views each replica's planes as ``(slots, H, W, 3)`` frames, a row-major
reshape the compiler folds into the kernels' own plane view.

Inside the mapped body the existing kernels are reused unchanged:
``kernels.vision_ops.ingest_frame`` / ``scatter_admit`` on the Pallas
path, the ``streams.filter`` jnp gate ops + ``models.vision`` analysis
jits on the legacy path.  Replica-stacking and per-replica unstacking both
live *inside* the jit, and frames stage into pinned host buffers
(``VisionServeEngine.enable_host_staging``), so a whole fleet tick issues
exactly one device dispatch however many replicas/lanes/tiers are live.

Host/device split: everything the serial path does on the host stays on
the host, per replica, in the same order — lane rebalancing, deadline
trims, backlog pops (``VisionServeEngine.begin_tick``/``stage_class``),
the gate's AIMD controller and stats (``MotionGate.commit_decision``),
counter/EWMA/ledger bookkeeping (``commit_class``/``end_tick``).  Only the
O(pixels) work (normalize, resample, score, scatter, conv forward) and the
admit *threshold* (a compare against the host-owned per-lane thresholds,
shipped in as data) move into the fused dispatch.  Churn — join/leave/
fail/rebind/tier-migration — therefore works exactly as in serial mode; a
dead (or standby) replica's rows ride along with an all-False lane mask
and its host phases are skipped, so shapes never change and nothing
recompiles.

Under virtual clocks (``repro.simulate``) the parallel tick is
bit-identical to the serial tick: same admit decisions, same ledger
records, same golden-trace digests (pinned by ``tests/test_fleet_step``).

Spans (``obs.tracing``, on the ``fleet`` lane and on the lead live
replica's clock and sampled tick): ``fleet.tick`` around the whole tick;
``fused_dispatch`` over exactly the interval ``last_dispatch_s`` times,
with ``fleet.gather`` / ``fleet.call`` (the jit call returning, ``bytes``
staged) / ``fleet.wait`` (``block_until_ready``) inside it; then
``fleet.readback`` (the masks to the host), ``fleet.commit`` (state
unstacked, every ``commit_class``) and ``fleet.end`` (``end_tick`` and
the scheduler's feedback).  The replicas' own lanes hold ``rebalance``,
``stage`` and ``commit``.
"""
from __future__ import annotations

import functools
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.clock import VirtualClock
from repro.models import vision as V
from repro.obs.tracing import NULL_TRACER
from repro.sharding.compat import make_mesh
from repro.streams import filter as sfilter
from repro.streams.vision_engine import (INNER, OUTER, VisionServeEngine,
                                         _scatter_stage_impl)

MODES = ("shard_map", "vmap")


def resolve_mode(n_replicas: int, mode: Optional[str] = None) -> str:
    """``shard_map`` on an accelerator host with more than one device,
    ``vmap`` otherwise — same stacked state and mapped body either way.

    Forced host-platform CPU devices (``XLA_FLAGS=--xla_force_host_
    platform_device_count=N``) execute their programs *sequentially* on
    one shared thread pool, so a CPU shard_map only adds per-device
    coordination overhead (measured: an N-way mapped conv costs N x the
    single-device time plus 5-30 ms launch cost) — on CPU the fused
    tick's win is dispatch/sync amortisation, which ``vmap`` captures in
    full.  Pass ``mode="shard_map"`` explicitly to exercise the mesh path
    off-accelerator (the parity suite does, on a forced-device mesh)."""
    if mode is not None:
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
        return mode
    if (n_replicas > 1 and jax.device_count() > 1
            and jax.default_backend() != "cpu"):
        return "shard_map"
    return "vmap"


def replica_mesh(n_replicas: int):
    """The ``("replica",)`` mesh a shard_map fleet runs on: one replica
    per device up to the device count, and past it ``R / D`` replicas per
    device, which each device vmaps.  A fleet that cannot be spread evenly
    raises instead of piling onto one device."""
    n = min(n_replicas, jax.device_count())
    if n_replicas % n:
        raise ValueError(
            f"{n_replicas} replicas cannot be spread evenly over "
            f"{jax.device_count()} devices: use a multiple of the device "
            f"count, or mode='vmap' to run the fleet on one device")
    return make_mesh((n,), ("replica",))


def _stack_trees(trees: Sequence[dict]):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


@functools.lru_cache(maxsize=None)
def _build_fused(mode: str, mesh, members: Tuple[Tuple[int, ...], ...],
                 group_keys: tuple, use_pallas: bool, use_gate: bool,
                 gate_res: int, block: int, interpret: bool):
    """Build (and memoise) the fused fleet-tick jit for one fleet layout.

    Keyed on everything the closure captures — mode/mesh, the tier-group
    layout (``members`` = replica indices per group, ``group_keys`` =
    each group's (dc, pc, input_res, dtype)), gate geometry, kernel
    path — so repeated ``FleetStep`` construction (bench repeats, test
    sweeps, gateway rebuilds) reuses one compiled XLA program instead of
    recompiling per instance.  Model params are call arguments, never
    captured."""
    if use_pallas:
        from repro.kernels import vision_ops
    R = sum(len(m) for m in members)

    def make_single(dc, pc, input_res):
        """Per-group single-replica tick body (both classes, no replica
        axis) — mirrors the device half of
        ``VisionServeEngine._step_class`` exactly, at this group's model
        geometry."""

        def one_class(forward, batch, stage, refs, thr, href, act):
            S, H, WC = stage.shape                # staged (H, W*3) planes
            stage = stage.reshape(S, H, WC // 3, 3)
            if use_pallas:
                if use_gate:
                    model, small, scores = vision_ops.ingest_frame(
                        stage, refs, model_res=input_res, gate_res=gate_res,
                        block=block, interpret=interpret)
                    admit = act & ((scores > thr) | ~href)
                    batch, refs = vision_ops.scatter_admit(
                        batch, model, refs, small, admit,
                        interpret=interpret)
                else:
                    model = vision_ops.downscale(stage, input_res,
                                                 interpret=interpret)
                    admit = act
                    batch, _ = vision_ops.scatter_admit(
                        batch, model, refs, refs, admit,
                        interpret=interpret)
            else:
                # the one masked-scatter expression the bit-parity
                # contract rests on — shared with the engine's serial
                # host-staging path
                batch = _scatter_stage_impl(batch, stage, act)
                if use_gate:
                    small = V.downscale(sfilter._normalize(batch), gate_res)
                    scores = sfilter._block_sad_jnp(refs, small, block)
                    admit = act & ((scores > thr) | ~href)
                    refs = sfilter._gate_update(refs, small, admit)
                else:
                    admit = act
            return admit, forward(batch), batch, refs

        def single(ops: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
            dp, pp = ops["dp"], ops["pp"]

            def fwd_outer(batch):
                flags, _ = V.analyse_outer(dc, dp, batch)
                return flags.any(axis=1)                    # (slots,)

            def fwd_inner(batch):
                distracted, _ = V.analyse_inner(pc, pp, batch)
                return distracted

            out: Dict[str, jax.Array] = {}
            for kind, forward in ((OUTER, fwd_outer), (INNER, fwd_inner)):
                admit, flags, batch, refs = one_class(
                    forward, ops[f"batch_{kind}"], ops["stage"],
                    ops[f"refs_{kind}"], ops[f"thr_{kind}"],
                    ops[f"href_{kind}"], ops[f"act_{kind}"])
                out[f"admit_{kind}"] = admit
                out[f"flags_{kind}"] = flags
                out[f"batch_{kind}"] = batch
                if use_gate:
                    out[f"refs_{kind}"] = refs
            return out

        return single

    singles = [make_single(dc, pc, ires)
               for (dc, pc, ires, _dtype) in group_keys]

    if mode == "shard_map":
        assert len(singles) == 1, "shard_map requires one tier group"
        return _build_sharded(singles[0], mesh, R)
    mapped = [jax.vmap(s) for s in singles]

    # replica order of the group-concatenated rows, and its inverse: the
    # gather that restores replica order for the fleet-wide mask output
    concat = np.concatenate([np.asarray(m, int) for m in members])
    inv = np.argsort(concat)

    def fused(gops):
        """Stack per-group state, run each group's mapped tick, hand back
        the engine-owned arrays per replica — so the host round-trip
        costs zero eager dispatches either side of the one jit call."""
        outs = []
        for g, ops in enumerate(gops):
            stacked = {"dp": ops["dp"], "pp": ops["pp"],
                       "stage": jnp.asarray(ops["stage"])}
            for k in ("thr", "href", "act"):
                for kind in (OUTER, INNER):
                    stacked[f"{k}_{kind}"] = jnp.asarray(ops[f"{k}_{kind}"])
            for k in ("batch", "refs"):
                for kind in (OUTER, INNER):
                    stacked[f"{k}_{kind}"] = jnp.stack(ops[f"{k}_{kind}"])
            outs.append(mapped[g](stacked))
        # one (4, R, slots) bool mask output = one host transfer for
        # everything the commit loop reads, whatever the tier mix
        masks = jnp.concatenate(
            [jnp.stack([out[f"admit_{OUTER}"], out[f"admit_{INNER}"],
                        out[f"flags_{OUTER}"], out[f"flags_{INNER}"]])
             for out in outs], axis=1)[:, inv]
        res = {"masks": masks}
        per_rep: Dict[str, list] = {}
        for g, out in enumerate(outs):
            for key, v in out.items():
                if key.startswith(("admit", "flags")):
                    continue
                rows = per_rep.setdefault(key, [None] * R)
                for j, i in enumerate(members[g]):
                    rows[i] = v[j]
        for key, rows in per_rep.items():
            res[key] = tuple(rows)
        return res

    return jax.jit(fused)


def _build_sharded(single, mesh, n_replicas: int):
    """The fused tick over the ``("replica",)`` mesh: device ``d`` runs
    replicas ``d*k .. d*k+k-1`` (``k = R / mesh size``) by vmapping the
    per-replica body over its block.

    Per-replica state (batch pools, gate refs) crosses the jit boundary
    as ``k`` flat ``(mesh size * slots, ...)`` arrays whose device-``d``
    shard is exactly one replica's ``(slots, ...)`` array, so it is
    assembled from, and split back into, the engines' own single-device
    arrays without a copy: each replica's state stays on its device.
    Everything else (stage frames, masks, thresholds, stacked params) is
    one ``(R, ...)`` array sharded along the replica axis."""
    k = n_replicas // mesh.size

    def shard_body(ops):
        stacked = {key: jnp.stack(v) if isinstance(v, tuple) else v
                   for key, v in ops.items()}
        out = jax.vmap(single)(stacked)
        return {key: (v if key.startswith(("admit", "flags"))
                      else tuple(v[j] for j in range(k)))
                for key, v in out.items()}

    spec = PartitionSpec("replica")
    mapped = jax.shard_map(shard_body, mesh=mesh, in_specs=spec,
                           out_specs=spec, check_vma=False)

    def fused(ops):
        out = mapped(ops)
        res = {key: v for key, v in out.items()
               if not key.startswith(("admit", "flags"))}
        res["masks"] = jnp.stack([out[f"admit_{OUTER}"], out[f"admit_{INNER}"],
                                  out[f"flags_{OUTER}"], out[f"flags_{INNER}"]])
        return res

    return jax.jit(fused)


class FleetStep:
    """One-dispatch fleet tick over stacked ``VisionServeEngine`` state."""

    def __init__(self, replicas: Sequence[VisionServeEngine], *,
                 mode: Optional[str] = None, warm: bool = True) -> None:
        if not replicas:
            raise ValueError("need at least one engine replica")
        self.replicas: List[VisionServeEngine] = list(replicas)
        ref = self.replicas[0]
        for r in self.replicas:
            # fleet-wide uniform: slot width, source frame geometry, and
            # kernel path.  Model geometry (dc/pc/input_res/batch dtype)
            # may differ per replica — those split into tier groups below.
            for attr in ("slots", "frame_res", "use_pallas"):
                if getattr(r, attr) != getattr(ref, attr):
                    raise ValueError(
                        f"fleet-parallel tick needs uniform engine geometry: "
                        f"{r.name}.{attr}={getattr(r, attr)} != "
                        f"{ref.name}.{attr}={getattr(ref, attr)}")
            if (r.gates[OUTER] is None) != (ref.gates[OUTER] is None):
                raise ValueError("fleet-parallel tick needs a uniform "
                                 "use_gate setting across replicas")
        self.slots = ref.slots
        self.use_pallas = ref.use_pallas
        self.use_gate = ref.gates[OUTER] is not None
        if self.use_gate:
            g0 = ref.gates[OUTER]
            for r in self.replicas:
                for kind in (OUTER, INNER):
                    g = r.gates[kind]
                    if g.gate_res != g0.gate_res or g.block != g0.block:
                        raise ValueError(
                            "fleet-parallel tick needs uniform gate "
                            "geometry (gate_res, block) across replicas")
            self.gate_res, self.block = g0.gate_res, g0.block
        else:
            self.gate_res, self.block = 1, 8
        R = len(self.replicas)
        # tier groups: replicas sharing one model geometry map together.
        # Grouping is by first appearance, so a uniform fleet is exactly
        # one group in replica order (the pre-tier layout).
        sigs = [(r.dc, r.pc, r.input_res, str(r.batches[OUTER].dtype))
                for r in self.replicas]
        self._group_keys: List[tuple] = []
        self._members: List[List[int]] = []
        for i, sig in enumerate(sigs):
            if sig in self._group_keys:
                self._members[self._group_keys.index(sig)].append(i)
            else:
                self._group_keys.append(sig)
                self._members.append([i])
        self.mode = resolve_mode(R, mode)
        if len(self._members) > 1 and self.mode == "shard_map":
            if mode == "shard_map":
                raise ValueError(
                    "shard_map maps one program over the replica mesh and "
                    "cannot mix tier geometries; mixed-tier fleets run "
                    "mode='vmap'")
            warnings.warn(
                f"mixed-tier fleet of {R} replicas runs mode='vmap' on one "
                f"of {jax.device_count()} devices", stacklevel=3)
            self.mode = "vmap"
        self.mesh = replica_mesh(R) if self.mode == "shard_map" else None
        # one pinned staging buffer per tier group, in the kernels'
        # (H, W*3) planes; each engine's _stage is a (slots, H, W, 3) view
        # of its group row, so the host never copies frames again and the
        # fused call uploads each group's staging in one piece (frames
        # always arrive at the uniform frame_res, f32)
        res = ref.frame_res
        self._stage_groups: List[np.ndarray] = []
        for g, mem in enumerate(self._members):
            buf = np.zeros((len(mem), self.slots, res, res * 3), np.float32)
            self._stage_groups.append(buf)
            for j, i in enumerate(mem):
                r = self.replicas[i]
                r.enable_host_staging()
                r._stage = buf[j].reshape(self.slots, res, res, 3)
        # engines never retrain: stack the per-group model params once
        self._dp = [_stack_trees([self.replicas[i].dp for i in mem])
                    for mem in self._members]
        self._pp = [_stack_trees([self.replicas[i].pp for i in mem])
                    for mem in self._members]
        # gateless ref/scatter operands keep a fixed (tiny) shape
        self._null_refs = [
            tuple(jnp.zeros((self.slots, self.gate_res, self.gate_res, 3),
                            jnp.float32) for _ in mem)
            for mem in self._members]
        self._zeros_gs = [np.zeros((len(mem), self.slots), np.float32)
                          for mem in self._members]
        self._false_gs = [np.zeros((len(mem), self.slots), bool)
                          for mem in self._members]
        self._mem_idx = [np.asarray(mem, int) for mem in self._members]
        self._staged_bytes = sum(b.nbytes for b in self._stage_groups)
        self.tracer = NULL_TRACER      # the gateway's, set by attach_obs
        if self.mesh is not None:
            self._place_on_mesh()
        self._fused = self._build()
        self.dispatches = 0            # fused device dispatches issued
        self.last_dispatch_s = 0.0     # wall time of the last fused call
        # the last fused call's (admit_outer, admit_inner, flags_outer,
        # flags_inner) masks, (4, R, slots) — what every mode must agree on
        self.last_masks: Optional[np.ndarray] = None
        if warm:
            self._warm()

    # ------------------------------------------------------------------
    # fused computation
    # ------------------------------------------------------------------
    def _build(self):
        ref = self.replicas[0]
        return _build_fused(
            self.mode, self.mesh,
            tuple(tuple(m) for m in self._members),
            tuple(self._group_keys),
            self.use_pallas, self.use_gate, self.gate_res, self.block,
            ref._interpret if self.use_pallas else False)

    # ------------------------------------------------------------------
    # replica mesh placement (shard_map mode: one tier group)
    # ------------------------------------------------------------------
    def _place_on_mesh(self) -> None:
        """Commit every replica's device state to its mesh device and the
        stacked params to the replica sharding, once: from here on each
        tick's state only moves between a replica's engine and its own
        device's shard of the fused call (see :func:`_build_sharded`)."""
        self._sharding = NamedSharding(self.mesh, PartitionSpec("replica"))
        self._k = len(self.replicas) // self.mesh.size
        devices = list(self.mesh.devices.flat)
        for i, r in enumerate(self.replicas):
            dev = devices[i // self._k]
            for kind in (OUTER, INNER):
                r.batches[kind] = jax.device_put(r.batches[kind], dev)
                if self.use_gate:
                    g = r.gates[kind]
                    g.refs = jax.device_put(g.refs, dev)
        self._dp = [jax.device_put(self._dp[0], self._sharding)]
        self._pp = [jax.device_put(self._pp[0], self._sharding)]
        self._null_refs = [tuple(
            jax.device_put(x, devices[i // self._k])
            for i, x in enumerate(self._null_refs[0]))]

    def _to_mesh(self, ops: Dict[str, object]) -> Dict[str, object]:
        """One group's gathered operands as the sharded call's arguments:
        per-replica arrays become ``k`` flat arrays assembled from the
        replicas' own buffers, host arrays one sharded upload each."""
        n, k = self.mesh.size, self._k
        out: Dict[str, object] = {}
        host: Dict[str, np.ndarray] = {}
        for key, v in ops.items():
            if isinstance(v, tuple):
                shape = (n * v[0].shape[0],) + v[0].shape[1:]
                out[key] = tuple(
                    jax.make_array_from_single_device_arrays(
                        shape, self._sharding, [v[d * k + j] for d in range(n)])
                    for j in range(k))
            elif isinstance(v, np.ndarray):
                host[key] = v
            else:
                out[key] = v                      # params, placed at init
        out.update(jax.device_put(host, self._sharding))
        return out

    def _per_replica(self, v) -> Sequence[jax.Array]:
        """A fused-call state output as one array per replica, in replica
        order (shard_map: each replica's shard, still on its device)."""
        if self.mesh is None:
            return v
        rows: List[Optional[jax.Array]] = [None] * len(self.replicas)
        for j, a in enumerate(v):
            for sh in a.addressable_shards:
                d = (sh.index[0].start or 0) // self.slots
                rows[d * self._k + j] = sh.data
        return rows

    # ------------------------------------------------------------------
    # host orchestration
    # ------------------------------------------------------------------
    def _gather(self, act: Dict[str, np.ndarray]):
        """Collect per-group engine state for the fused call (tuples of
        device arrays + host numpy masks; stacking happens inside the jit).
        """
        gops: List[Dict[str, object]] = []
        for g, mem in enumerate(self._members):
            ops: Dict[str, object] = {"dp": self._dp[g], "pp": self._pp[g],
                                      "stage": self._stage_groups[g]}
            for kind in (OUTER, INNER):
                ops[f"batch_{kind}"] = tuple(
                    self.replicas[i].batches[kind] for i in mem)
                if self.use_gate:
                    ops[f"refs_{kind}"] = tuple(
                        self.replicas[i].gates[kind].refs for i in mem)
                    ops[f"thr_{kind}"] = np.stack(
                        [self.replicas[i].gates[kind].thresh for i in mem])
                    ops[f"href_{kind}"] = np.stack(
                        [self.replicas[i].gates[kind].has_ref for i in mem])
                else:
                    ops[f"refs_{kind}"] = self._null_refs[g]
                    ops[f"thr_{kind}"] = self._zeros_gs[g]
                    ops[f"href_{kind}"] = self._false_gs[g]
                ops[f"act_{kind}"] = act[kind][self._mem_idx[g]]
            gops.append(ops)
        if self.mesh is not None:
            return self._to_mesh(gops[0])
        return gops

    def _warm(self) -> None:
        """Compile the fused tick at construction (all-inactive masks, the
        exact shapes/dtypes every later tick uses) so churn mid-run never
        observes a compile — the same never-recompile contract the serial
        engines keep."""
        R = len(self.replicas)
        act = {OUTER: np.zeros((R, self.slots), bool),
               INNER: np.zeros((R, self.slots), bool)}
        jax.block_until_ready(self._fused(self._gather(act)))

    def tick(self, gw) -> int:
        """One fleet tick with serial semantics: identical host phases per
        live replica around a single fused device dispatch.  ``gw`` is the
        owning ``FleetGateway`` (scheduler feedback + dead-replica set)."""
        live = [r for r in self.replicas if r.name not in gw.dead]
        lead = live[0] if live else self.replicas[0]
        clk = lead.clock
        # the lead replica's begin_tick samples this same tick number
        tr = self.tracer.for_tick(lead.ticks)
        with tr.span(clk, "fleet.tick", tid="fleet", tick=lead.ticks):
            return self._tick(gw, live, tr, clk)

    def _tick(self, gw, live, tr, clk) -> int:
        R = len(self.replicas)
        t0s = {r.name: r.begin_tick() for r in live}
        act = {OUTER: np.zeros((R, self.slots), bool),
               INNER: np.zeros((R, self.slots), bool)}
        for i, r in enumerate(self.replicas):
            if r.name in gw.dead:
                continue
            for kind in (OUTER, INNER):
                act[kind][i] = r.stage_class(kind)

        per_done = {r.name: 0 for r in live}
        wall_share_s = {r.name: 0.0 for r in live}
        if act[OUTER].any() or act[INNER].any():
            on_wall = not isinstance(clk, VirtualClock)
            wall0 = time.perf_counter()
            # the span's start: the same read on a wall clock, the
            # clock's own time on a virtual one (read only when traced)
            t_span = wall0 if on_wall or not tr.enabled else clk.now_s()
            with tr.span(clk, "fleet.gather", tid="fleet"):
                ops = self._gather(act)
            with tr.span(clk, "fleet.call", tid="fleet",
                         bytes=self._staged_bytes):
                out = self._fused(ops)
            with tr.span(clk, "fleet.wait", tid="fleet"):
                out = jax.block_until_ready(out)
            wall = time.perf_counter() - wall0
            self.dispatches += 1
            self.last_dispatch_s = wall
            if tr.enabled:
                tr.complete("fused_dispatch", "fleet", t_span,
                            wall if on_wall else clk.now_s() - t_span,
                            dispatch=self.dispatches,
                            n_active=int(act[OUTER].sum()
                                         + act[INNER].sum()))
            with tr.span(clk, "fleet.readback", tid="fleet"):
                masks = np.asarray(out["masks"])          # (4, R, slots)
            self.last_masks = masks
            with tr.span(clk, "fleet.commit", tid="fleet"):
                self._commit(gw, out, masks, act, wall, per_done,
                             wall_share_s)

        done = 0
        with tr.span(clk, "fleet.end", tid="fleet"):
            for r in live:
                n = per_done[r.name]
                r.end_tick(t0s[r.name], n, span=False)
                if n:
                    if isinstance(r.clock, VirtualClock):
                        # same reads/charges as the serial path:
                        # bit-identical
                        dt_ms = (r.clock.now_s() - t0s[r.name]) * 1000.0
                    else:
                        # wall clocks: the elapsed time since t0 spans the
                        # WHOLE fleet's host+device work — feed the
                        # capacity EWMA this replica's share of the fused
                        # dispatch instead, matching serial observe
                        # semantics
                        dt_ms = wall_share_s[r.name] * 1000.0
                    gw.sched.by_name(r.name).observe(n, dt_ms)
                done += n
        if gw.token_replicas:
            # mixed fleets: the fused dispatch covers the vision replicas;
            # token decode runs its own shared jits, stepped with the
            # identical host phases (and order) the serial tick uses — so
            # mixed scenarios stay bit-identical across serial/parallel
            # modes.  A paged replica's block table / ring lengths are
            # host-side numpy owned by its ServeEngine and only converted
            # to device arrays at dispatch, so stepping order can never
            # reorder pool allocation between serial and parallel ticks.
            done += gw._tick_tokens()
        return done

    def _commit(self, gw, out, masks, act, wall, per_done,
                wall_share_s) -> None:
        """Hand each live replica its state back and run its host
        bookkeeping for both classes (``commit_class``)."""
        state = {key: self._per_replica(v) for key, v in out.items()
                 if key != "masks"}
        admit = {OUTER: masks[0], INNER: masks[1]}
        flags = {OUTER: masks[2], INNER: masks[3]}
        total = int(admit[OUTER].sum() + admit[INNER].sum())
        for i, r in enumerate(self.replicas):
            if r.name in gw.dead:
                continue
            on_wall = not isinstance(r.clock, VirtualClock)
            for kind in (OUTER, INNER):
                a_row, m_row = act[kind][i], admit[kind][i]
                if a_row.any():
                    # serial parity: state only refreshes where the
                    # serial path would have dispatched this class
                    r.batches[kind] = state[f"batch_{kind}"][i]
                    if self.use_gate:
                        r.gates[kind].refs = state[f"refs_{kind}"][i]
                dt = (wall * int(m_row.sum()) / total
                      if on_wall and total else None)
                if dt is not None:
                    wall_share_s[r.name] += dt
                per_done[r.name] += r.commit_class(
                    kind, a_row, m_row, flags[kind][i], dt_share_s=dt)
