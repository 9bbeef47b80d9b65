"""Motion-gated frame admission (redundant-frame filtering).

Dash-cam streams are massively redundant — a car waiting at a light sends
near-identical frames for seconds.  The Edge Video Analytics survey
(arXiv:2211.15751) names redundant-frame filtering as one of the two levers
that make fleet-scale serving economical; this module is that lever for the
``VisionServeEngine``: a vectorised block-SAD frame-difference gate, batched
across *all* streams of an engine, that rejects near-duplicate frames before
they ever occupy a batch slot.

Design:

  * :func:`block_sad` — the jit core.  Frames are compared against each
    stream's last-admitted reference at a small gate resolution; the score
    is the *maximum block* mean-absolute-difference, so a pedestrian
    entering one corner of an otherwise static scene still trips the gate
    (a full-frame mean would wash it out).  Edge blocks are pad-and-masked,
    so arbitrary gate resolutions work; ``use_pallas=True`` dispatches to
    the fused ``repro.kernels.vision_ops`` kernel (the engine's hot path
    fuses downscale+normalize+score via ``vision_ops.ingest_frame`` and
    feeds the scores straight into :meth:`MotionGate.decide`).
  * :class:`MotionGate` — per-engine state: one reference frame and one
    adaptive threshold per slot.  Everything device-side is fixed-shape
    (``(slots, gate_res, gate_res, 3)``) with boolean masks, mirroring the
    engine's never-recompile contract; reference updates use a masked
    scatter so gated rows keep their old reference.
  * Adaptive thresholds — per-stream AIMD on the observed skip fraction
    (same controller idiom as ``core.early_stop.DynamicESD``), steering
    every lane toward the ``target_skip`` band: a stream skipping above
    ``target_skip[1]`` has its threshold multiplicatively decayed so it
    admits more (bounded below by ``thresh_floor`` — a parked vehicle must
    not end up admitting sensor noise), and a stream admitting nothing but
    near-duplicates gets its threshold additively raised so it skips more.
    The controller is per-stream, not global: each lane converges to the
    sensitivity its own scene requires.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.early_stop import EWMA
from repro.models.vision import downscale


def _normalize(frames: jax.Array) -> jax.Array:
    """fp32 in [0,1]: uint8 frames scale by 1/255 (same rule as the fused
    kernel and the ref goldens, so use_pallas on/off score identically)."""
    x = frames.astype(jnp.float32)
    if frames.dtype == jnp.uint8:
        x = x * (1.0 / 255.0)
    return x


# NOTE: deliberately mirrors (not imports) ref.block_sad_ref — the goldens
# stay independent of every production path so a shared bug cannot hide;
# tests/test_vision_kernels.py pins this copy to the golden.
@partial(jax.jit, static_argnames=("block",))
def _block_sad_jnp(ref: jax.Array, frames: jax.Array, block: int) -> jax.Array:
    S, H, W, _ = frames.shape
    # cast before subtracting: uint8 difference would wrap modulo 256
    d = jnp.abs(frames.astype(jnp.float32)
                - ref.astype(jnp.float32)).mean(axis=-1)       # (S, H, W)
    nh, nw = -(-H // block), -(-W // block)
    # pad-and-mask: arbitrary gate resolutions work; partial edge blocks
    # average only their valid pixels (zero-padded sums / true counts)
    d = jnp.pad(d, ((0, 0), (0, nh * block - H), (0, nw * block - W)))
    sums = d.reshape(S, nh, block, nw, block).sum(axis=(2, 4))
    cnt_h = np.minimum(block, H - np.arange(nh) * block)
    cnt_w = np.minimum(block, W - np.arange(nw) * block)
    counts = jnp.asarray(np.outer(cnt_h, cnt_w), jnp.float32)
    return (sums / counts).reshape(S, -1).max(axis=-1)


def block_sad(ref: jax.Array, frames: jax.Array, block: int = 8, *,
              use_pallas: bool = False,
              interpret: Optional[bool] = None) -> jax.Array:
    """Per-stream motion score: max block mean-absolute-difference.

    ref/frames: (S, H, W, C); H, W need NOT divide ``block`` (edge blocks
    average their valid pixels only).  Returns (S,) float32 in [0, 1] for
    [0, 1]-ranged inputs.  ``use_pallas`` dispatches to the fused kernel in
    ``repro.kernels.vision_ops`` (interpret-mode fallback off-TPU).
    """
    if use_pallas:
        from repro.kernels import vision_ops
        return vision_ops.block_sad(ref, frames, block=block,
                                    interpret=interpret)
    return _block_sad_jnp(ref, frames, block)


@jax.jit
def _gate_update(refs, small, admit):
    """Masked reference scatter: admitted rows adopt the new frame."""
    m = admit[:, None, None, None]
    return jnp.where(m, small, refs)


@dataclass
class GateStats:
    offered: int = 0
    admitted: int = 0
    gated: int = 0

    @property
    def skip_fraction(self) -> float:
        return self.gated / self.offered if self.offered else 0.0


class MotionGate:
    """Batched near-duplicate filter for one engine's slot lanes."""

    def __init__(self, slots: int, gate_res: int = 32, block: int = 8,
                 init_thresh: float = 0.02,
                 target_skip: Tuple[float, float] = (0.05, 0.7),
                 step: float = 0.002, decay: float = 0.85,
                 window: int = 16, alpha: float = 0.2,
                 thresh_floor: float = 1e-3, thresh_ceil: float = 1.0,
                 use_pallas: bool = False) -> None:
        assert thresh_floor <= init_thresh <= thresh_ceil, \
            (thresh_floor, init_thresh, thresh_ceil)
        self.slots = slots
        self.gate_res = gate_res
        self.block = block
        self.target_skip = target_skip
        self.step = step
        self.decay = decay
        self.window = window
        self.thresh_floor = thresh_floor
        self.thresh_ceil = thresh_ceil
        self.init_thresh = init_thresh
        self.use_pallas = use_pallas
        self.refs = jnp.zeros((slots, gate_res, gate_res, 3), jnp.float32)
        self.has_ref = np.zeros(slots, bool)
        self.thresh = np.full(slots, init_thresh, np.float32)
        self.skip_ewma = [EWMA(alpha=alpha) for _ in range(slots)]
        self._since_adapt = np.zeros(slots, np.int64)
        self.stats = GateStats()

    def reset(self, slot: int, init_thresh: Optional[float] = None) -> None:
        """Forget a lane's reference/threshold (stream churn re-uses lanes)."""
        self.has_ref[slot] = False
        self.thresh[slot] = (init_thresh if init_thresh is not None
                             else self.init_thresh)
        self.skip_ewma[slot] = EWMA(alpha=self.skip_ewma[slot].alpha)
        self._since_adapt[slot] = 0

    def save(self, slot: int) -> dict:
        """Snapshot a lane's gate state so it can follow its *stream* — a
        time-shared or preempted stream must keep its duplicate-detection
        reference and adapted threshold across re-binds."""
        return {"ref": self.refs[slot],
                "has_ref": bool(self.has_ref[slot]),
                "thresh": float(self.thresh[slot]),
                "skip_ewma": self.skip_ewma[slot],
                "since": int(self._since_adapt[slot])}

    def restore(self, slot: int, state: Optional[dict] = None) -> None:
        """Install a saved stream snapshot into a lane (None = fresh)."""
        if state is None:
            self.reset(slot)
            return
        ref = state["ref"]
        if ref.devices() != self.refs.devices():
            # a stream rebound from a replica on another device (a
            # shard_map fleet) brings its reference from there
            ref = jax.device_put(ref, self.refs.sharding)
        self.refs = self.refs.at[slot].set(ref)
        self.has_ref[slot] = state["has_ref"]
        self.thresh[slot] = state["thresh"]
        self.skip_ewma[slot] = state["skip_ewma"]
        self._since_adapt[slot] = state["since"]

    def admit(self, frames: jax.Array, active: np.ndarray) -> np.ndarray:
        """Gate one engine tick.

        frames: (slots, H, W, 3) staged batch (inactive rows ignored);
        active: (slots,) bool — lanes holding a fresh candidate frame.
        Returns (slots,) bool admit mask (subset of ``active``) and updates
        references, thresholds, and stats.
        """
        if self.use_pallas:
            from repro.kernels import vision_ops
            small = vision_ops.downscale(frames, self.gate_res)
            scores = np.asarray(vision_ops.block_sad(self.refs, small,
                                                     block=self.block))
        else:
            small = downscale(_normalize(frames), self.gate_res)
            scores = np.asarray(block_sad(self.refs, small, self.block))
        admit = self.decide(scores, active)
        self.refs = _gate_update(self.refs, small, jnp.asarray(admit))
        return admit

    def decide(self, scores: np.ndarray, active: np.ndarray) -> np.ndarray:
        """Threshold the motion scores into an admit mask and run the AIMD
        controller + stats.  Does NOT refresh references — callers that own
        the gate-resolution frames (the engine's fused ``ingest_frame`` +
        ``scatter_admit`` path) commit them in the same device pass; the
        legacy :meth:`admit` path commits via :func:`_gate_update`."""
        moving = scores > self.thresh
        # first frame of a stream always admits (no reference yet)
        admit = active & (moving | ~self.has_ref)
        return self.commit_decision(active, admit)

    def commit_decision(self, active: np.ndarray,
                        admit: np.ndarray) -> np.ndarray:
        """Replay the host-state half of :meth:`decide` for an admit mask
        computed elsewhere.  The fleet-parallel tick thresholds on device
        with this gate's own ``thresh``/``has_ref`` (shipped in as fixed-
        shape arrays) and hands the resulting mask back here, so the AIMD
        controller, first-frame bookkeeping, and stats stay host-side and
        bit-identical to the serial :meth:`decide` path."""
        admit = np.asarray(admit, bool)
        self.has_ref = self.has_ref | admit
        self._adapt(active, admit)
        n_act, n_adm = int(active.sum()), int(admit.sum())
        self.stats.offered += n_act
        self.stats.admitted += n_adm
        self.stats.gated += n_act - n_adm
        return admit

    def _adapt(self, active: np.ndarray, admit: np.ndarray) -> None:
        """AIMD threshold update on each lane's skip-fraction EWMA.

        Adjustments fire at most once per ``window`` frames (the counter
        resets after each correction) so the controller settles instead of
        compounding every frame, and the threshold is floored: a parked
        vehicle must not decay its threshold to zero and then admit every
        sensor-noise frame once the scene resumes."""
        lo, hi = self.target_skip
        for s in np.nonzero(active)[0]:
            skip = self.skip_ewma[s].update(0.0 if admit[s] else 1.0)
            self._since_adapt[s] += 1
            if self._since_adapt[s] < self.window:
                continue
            if skip > hi:
                self.thresh[s] = max(self.thresh[s] * self.decay,
                                     self.thresh_floor)
                self._since_adapt[s] = 0
            elif skip < lo:
                # admitting duplicates: raise, bounded by the ceiling (a
                # score can never exceed the frame value range, so an
                # unbounded threshold would gate everything forever)
                self.thresh[s] = min(self.thresh[s] + self.step,
                                     self.thresh_ceil)
                self._since_adapt[s] = 0

    def similar(self) -> "MotionGate":
        """A fresh gate with this gate's configuration (new lane state)."""
        return MotionGate(self.slots, gate_res=self.gate_res,
                          block=self.block, init_thresh=self.init_thresh,
                          target_skip=self.target_skip, step=self.step,
                          decay=self.decay, window=self.window,
                          alpha=self.skip_ewma[0].alpha,
                          thresh_floor=self.thresh_floor,
                          thresh_ceil=self.thresh_ceil,
                          use_pallas=self.use_pallas)
