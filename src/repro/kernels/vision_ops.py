"""Pallas frame-ingest kernel suite: fused downscale + normalize + gate-score.

The ``VisionServeEngine`` hot path runs three materialised passes per tick in
plain jnp — downscale to gate resolution, normalize, block-SAD against the
per-stream reference — then a fourth downscale inside the model jit and a
``dynamic_update_slice`` loop for admission.  Each pass round-trips the frame
batch through HBM.  This suite fuses the ingest stage into two kernels:

  ``ingest_frame``   one VMEM-resident pass per stream: normalize (uint8 ->
                     [0,1] fp32), resample to BOTH the model resolution and
                     the gate resolution, and score per-block SAD against the
                     reference frame.  Emits (model, gate, score) without ever
                     materialising an intermediate in HBM.
  ``scatter_admit``  masked row scatter: admitted lanes adopt the new model
                     frame in the engine batch AND the new gate reference in
                     one pass, replacing the per-lane ``dynamic_update_slice``
                     loop and the separate masked reference update.
  ``downscale``      the resample half alone (``models.vision.downscale``
                     wiring) and ``block_sad`` the score half alone
                     (``streams.filter`` wiring).

Fusion layout
-------------
Grid is ``(S,)`` — one program per stream lane.  The jitted wrappers view
every ``(S, H, W, C)`` operand as ``(S, H, W*C)`` (a free row-major
reshape) outside ``pallas_call``, so each block is one stream's ``(H, W*C)``
plane: rows on the sublane axis, interleaved pixel-channels on the 128-lane
axis, and the kernel bodies never reshape.  Resampling is two MXU matmuls,
``wy @ X @ kron(wx, I_C)^T``; the column matrix carries the channel
interleave, so channels never mix.  For ``method="nearest"`` both matrices
are one-hot and the matmuls run at ``Precision.HIGHEST``, which makes the
result bit-identical to the gather in ``models.vision.downscale`` (a one-hot
matmul adds exact zeros; at default precision the MXU would round the frame
to bf16).  Block-SAD first takes the per-pixel channel mean with three
channel-select matmuls (the same sum-then-divide order as a ``mean`` over
the channel axis), then sums blocks with 0/1 block-membership matmuls and
divides by the per-block valid-pixel count, so H, W need not divide
``block`` (pad-and-mask semantics, matching ``ref.block_sad_ref``).  The
resample, channel-select and block matrices are built once per call in the
wrapper and passed as whole-array operands whose block index never changes,
so they are fetched into VMEM once for the whole grid.

VMEM budget: at the serving geometry (384 px frames, 192 px model input,
32 px gate, fp32) one ingest program holds a 1.8 MB frame block, the
2.7 MB model column matrix and a 0.4 MB model output, each double-buffered,
plus the matmul intermediates.  The v5e compiler accepts that at a 12 MiB
scoped-VMEM limit and refuses it at 8 MiB, so it fits the 16 MiB default;
larger frames need row tiling.  No scalar-per-lane value sits in a VMEM
block, whose last two dims must tile by (8, 128): the motion score is
written to a lane-dense ``(S, 8, 128)`` block and sliced afterwards, and
the admit mask reaches ``scatter_admit`` through scalar prefetch (SMEM).

Host/XLA split assumption
-------------------------
The host owns stream lifecycle, backlog deques and the admission *decision*
(adaptive thresholds are tiny scalar state, host-side in ``MotionGate``); the
device owns everything O(pixels): normalize, resample, score, scatter.  The
fleet tick (``streams.fleet_step``) stages frames into a pinned host buffer
already in the kernels' plane layout and ships one (R, S, H, W*C) array per
tick, which the chip takes row-major with no host transpose (a serial
engine ships its own (S, H, W, C) buffer); only the (S,) score vector
crosses back before the admit mask returns for ``scatter_admit``.  Frames
arrive at engine frame resolution — decode/crop from camera-native
resolution happens upstream — and a whole frame is one block, so the frame
resolution is bounded by VMEM.

``interpret=None`` auto-selects interpreter mode off-TPU, so the CPU parity
suite (``tests/test_vision_kernels.py``) executes the kernel bodies
interpreted against ``ref.py`` goldens; on TPU the same calls compile to
Mosaic (``tests/test_tpu_compile.py`` compiles them for a described v5e).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

METHODS = ("nearest", "box")

# one (8, 128) f32 tile per lane carries its motion score
_SCORE_TILE = (8, 128)


def default_interpret() -> bool:
    """Pallas interpreter mode everywhere except a real TPU backend."""
    return jax.default_backend() != "tpu"


def _norm_scale(dtype) -> float:
    return 1.0 / 255.0 if dtype == jnp.uint8 else 1.0


def _resample_weights(n_out: int, n_in: int, method: str) -> jax.Array:
    """(n_out, n_in) resampling matrix: one-hot rows (nearest) or box rows
    averaging ``[i*n_in//n_out, (i+1)*n_in//n_out)`` (rows sum to 1)."""
    i = jax.lax.broadcasted_iota(jnp.int32, (n_out, n_in), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n_out, n_in), 1)
    if method == "nearest":
        return (j == (i * n_in) // n_out).astype(jnp.float32)
    lo = (i * n_in) // n_out
    hi = ((i + 1) * n_in) // n_out
    w = ((j >= lo) & (j < hi)).astype(jnp.float32)
    return w / (hi - lo).astype(jnp.float32)


def _resample_mats(res: int, H: int, W: int, C: int, method: str):
    """Row matrix (res, H) and interleaved column matrix (W*C, res*C) that
    resample an (H, W*C) plane to (res, res*C)."""
    wy = _resample_weights(res, H, method)
    kx = jnp.kron(_resample_weights(res, W, method).T,
                  jnp.eye(C, dtype=jnp.float32))
    return wy, kx


def _block_weights(n: int, block: int):
    """0/1 membership matrix (nb, n) for fixed-size blocks (last partial)
    plus the per-block valid count (nb, 1) — pad-and-mask block means."""
    nb = pl.cdiv(n, block)
    i = jax.lax.broadcasted_iota(jnp.int32, (nb, n), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (nb, n), 1)
    w = ((j >= i * block) & (j < (i + 1) * block)).astype(jnp.float32)
    k = jax.lax.broadcasted_iota(jnp.int32, (nb, 1), 0)
    cnt = jnp.minimum(block, n - k * block).astype(jnp.float32)
    return w, cnt


def _sad_mats(H: int, W: int, C: int, block: int):
    """Operands of :func:`_sad_score` for an (H, W*C) plane: channel-select
    matrices (C, W*C, W), row/column block membership (nbh, H) / (W, nbw)
    and the per-block valid-pixel counts (nbh, nbw)."""
    r = jax.lax.broadcasted_iota(jnp.int32, (C, W * C, W), 1)
    w = jax.lax.broadcasted_iota(jnp.int32, (C, W * C, W), 2)
    c = jax.lax.broadcasted_iota(jnp.int32, (C, W * C, W), 0)
    sel = (r == w * C + c).astype(jnp.float32)
    wbh, cnt_h = _block_weights(H, block)
    wbw, cnt_w = _block_weights(W, block)
    return sel, wbh, wbw.T, cnt_h * cnt_w.T


def _mm(a: jax.Array, b: jax.Array) -> jax.Array:
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _sad_score(small: jax.Array, ref: jax.Array, sel, wbh, wbwT,
               cnt) -> jax.Array:
    """Max block mean-absolute-difference of two (H, W*C) planes, as a
    (1, 1) value."""
    diff = jnp.abs(small - ref.astype(jnp.float32))          # (H, W*C)
    C = sel.shape[0]
    # per-pixel channel mean, summed channel by channel then divided —
    # the order of a mean over the channel axis
    d = _mm(diff, sel[0])
    for c in range(1, C):
        d = d + _mm(diff, sel[c])
    d = d / C                                                # (H, W)
    # wbh @ d @ wbw^T sums each block; divide by the valid-pixel count so
    # a partial edge block averages only real pixels (pad-and-mask)
    q = _mm(_mm(wbh, d), wbwT) / cnt
    return jnp.max(jnp.max(q, axis=1, keepdims=True), axis=0, keepdims=True)


# ---------------------------------------------------------------------------
# Kernels (every frame ref is one stream's (H, W*C) plane)
# ---------------------------------------------------------------------------


def _ingest_kernel(frames_ref, refs_ref, wym_ref, kxm_ref, wyg_ref, kxg_ref,
                   sel_ref, wbh_ref, wbwT_ref, cnt_ref,
                   model_out, gate_out, score_out, *, scale: float):
    x = frames_ref[0].astype(jnp.float32) * scale
    model_out[0] = _mm(_mm(wym_ref[...], x), kxm_ref[...])
    small = _mm(_mm(wyg_ref[...], x), kxg_ref[...])
    gate_out[0] = small
    score = _sad_score(small, refs_ref[0], sel_ref[...], wbh_ref[...],
                       wbwT_ref[...], cnt_ref[...])
    score_out[0] = jnp.broadcast_to(score, _SCORE_TILE)


def _downscale_kernel(frames_ref, wy_ref, kx_ref, out_ref, *, scale: float):
    x = frames_ref[0].astype(jnp.float32) * scale
    out_ref[0] = _mm(_mm(wy_ref[...], x), kx_ref[...])


def _block_sad_kernel(refs_ref, frames_ref, sel_ref, wbh_ref, wbwT_ref,
                      cnt_ref, score_out):
    score = _sad_score(frames_ref[0].astype(jnp.float32), refs_ref[0],
                       sel_ref[...], wbh_ref[...], wbwT_ref[...],
                       cnt_ref[...])
    score_out[0] = jnp.broadcast_to(score, _SCORE_TILE)


def _scatter_kernel(admit_ref, batch_ref, model_ref, refs_ref, gate_ref,
                    batch_out, refs_out):
    take = admit_ref[pl.program_id(0)] != 0

    @pl.when(take)
    def _adopt():
        batch_out[...] = model_ref[...].astype(batch_out.dtype)
        refs_out[...] = gate_ref[...].astype(refs_out.dtype)

    @pl.when(jnp.logical_not(take))
    def _keep():
        batch_out[...] = batch_ref[...]
        refs_out[...] = refs_ref[...]


# ---------------------------------------------------------------------------
# jit'd wrappers (grid = (S,): one program per stream lane)
# ---------------------------------------------------------------------------


def _planes(x: jax.Array) -> jax.Array:
    """(S, H, W, C) -> (S, H, W*C): the kernels' lane-dense row layout."""
    S, H, W, C = x.shape
    return x.reshape(S, H, W * C)


def _row(shape):
    """BlockSpec for one stream's row of an (S, ...) operand."""
    return pl.BlockSpec((1,) + tuple(shape),
                        lambda s, *_: (s,) + (0,) * len(shape))


def _whole(x):
    """BlockSpec for an operand every program reads whole (same block at
    every grid step, so it is copied into VMEM once)."""
    return pl.BlockSpec(x.shape, lambda s, *_: (0,) * x.ndim)


def _scores(S: int):
    return (pl.BlockSpec((1,) + _SCORE_TILE, lambda s: (s, 0, 0)),
            jax.ShapeDtypeStruct((S,) + _SCORE_TILE, jnp.float32))


@functools.partial(jax.jit, static_argnames=(
    "model_res", "gate_res", "block", "method", "interpret"))
def _ingest_frame_jit(frames, refs, *, model_res, gate_res, block, method,
                      interpret):
    S, H, W, C = frames.shape
    g = refs.shape[1]
    mats = (*_resample_mats(model_res, H, W, C, method),
            *_resample_mats(gate_res, H, W, C, method),
            *_sad_mats(g, g, C, block))
    score_spec, score_shape = _scores(S)
    model, gate, score = pl.pallas_call(
        functools.partial(_ingest_kernel, scale=_norm_scale(frames.dtype)),
        grid=(S,),
        in_specs=[_row((H, W * C)), _row((g, g * C))]
        + [_whole(m) for m in mats],
        out_specs=(_row((model_res, model_res * C)),
                   _row((gate_res, gate_res * C)), score_spec),
        out_shape=(jax.ShapeDtypeStruct((S, model_res, model_res * C),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((S, gate_res, gate_res * C),
                                        jnp.float32),
                   score_shape),
        interpret=interpret,
    )(_planes(frames), _planes(refs), *mats)
    return (model.reshape(S, model_res, model_res, C),
            gate.reshape(S, gate_res, gate_res, C), score[:, 0, 0])


def ingest_frame(frames: jax.Array, refs: jax.Array, *, model_res: int,
                 gate_res: int, block: int = 8, method: str = "nearest",
                 interpret: bool | None = None):
    """Fused ingest: (S,H,W,C) frames + (S,g,g,C) refs ->
    (model (S,m,m,C) fp32, gate (S,g,g,C) fp32, scores (S,) fp32)."""
    # box feasibility must hold for BOTH output resolutions: an upsampling
    # box bucket is empty and would emit NaN, not raise
    _check(frames, method, max(model_res, gate_res))
    assert refs.shape[1] == refs.shape[2] == gate_res, (refs.shape, gate_res)
    return _ingest_frame_jit(
        frames, refs, model_res=model_res, gate_res=gate_res, block=block,
        method=method,
        interpret=default_interpret() if interpret is None else interpret)


@functools.partial(jax.jit, static_argnames=("res", "method", "interpret"))
def _downscale_jit(frames, *, res, method, interpret):
    S, H, W, C = frames.shape
    mats = _resample_mats(res, H, W, C, method)
    out = pl.pallas_call(
        functools.partial(_downscale_kernel,
                          scale=_norm_scale(frames.dtype)),
        grid=(S,),
        in_specs=[_row((H, W * C))] + [_whole(m) for m in mats],
        out_specs=_row((res, res * C)),
        out_shape=jax.ShapeDtypeStruct((S, res, res * C), jnp.float32),
        interpret=interpret,
    )(_planes(frames), *mats)
    return out.reshape(S, res, res, C)


def downscale(frames: jax.Array, res: int, *, method: str = "nearest",
              interpret: bool | None = None) -> jax.Array:
    """(S, H, W, C) -> (S, res, res, C) fp32 normalized resample."""
    _check(frames, method, res)
    return _downscale_jit(
        frames, res=res, method=method,
        interpret=default_interpret() if interpret is None else interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _block_sad_jit(refs, frames, *, block, interpret):
    S, H, W, C = frames.shape
    mats = _sad_mats(H, W, C, block)
    score_spec, score_shape = _scores(S)
    score = pl.pallas_call(
        _block_sad_kernel,
        grid=(S,),
        in_specs=[_row((H, W * C)), _row((H, W * C))]
        + [_whole(m) for m in mats],
        out_specs=score_spec,
        out_shape=score_shape,
        interpret=interpret,
    )(_planes(refs), _planes(frames), *mats)
    return score[:, 0, 0]


def block_sad(refs: jax.Array, frames: jax.Array, block: int = 8, *,
              interpret: bool | None = None) -> jax.Array:
    """Per-stream max block-MAD of (S,H,W,C) frames vs refs -> (S,) fp32."""
    assert refs.shape == frames.shape, (refs.shape, frames.shape)
    return _block_sad_jit(
        refs, frames, block=block,
        interpret=default_interpret() if interpret is None else interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scatter_admit_jit(batch, model, refs, gate, admit, *, interpret):
    batch2, refs2 = _planes(batch), _planes(refs)
    brow, grow = _row(batch2.shape[1:]), _row(refs2.shape[1:])
    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,       # the (S,) admit mask, in SMEM
            grid=(batch.shape[0],),
            in_specs=[brow, brow, grow, grow],
            out_specs=(brow, grow)),
        out_shape=(jax.ShapeDtypeStruct(batch2.shape, batch.dtype),
                   jax.ShapeDtypeStruct(refs2.shape, refs.dtype)),
        # a TPU deployment would add input_output_aliases={1: 0, 3: 1} to
        # update the batch pool in place; kept copying here so callers (and
        # the parity harness) may reuse their inputs after the call
        interpret=interpret,
    )(admit.astype(jnp.int32), batch2, _planes(model), refs2, _planes(gate))
    return out[0].reshape(batch.shape), out[1].reshape(refs.shape)


def scatter_admit(batch: jax.Array, model: jax.Array, refs: jax.Array,
                  gate: jax.Array, admit: jax.Array, *,
                  interpret: bool | None = None):
    """Masked admission scatter: rows of ``admit`` adopt the new model frame
    in ``batch`` and the new gate frame in ``refs``; gated rows keep both.
    Returns (batch', refs')."""
    assert batch.shape == model.shape, (batch.shape, model.shape)
    assert refs.shape == gate.shape, (refs.shape, gate.shape)
    return _scatter_admit_jit(
        batch, model, refs, gate, admit,
        interpret=default_interpret() if interpret is None else interpret)


def _check(frames, method, res):
    assert frames.ndim == 4, frames.shape
    assert method in METHODS, method
    if method == "box":
        # box buckets [i*H//res, (i+1)*H//res) are empty when upsampling
        assert res <= frames.shape[1] and res <= frames.shape[2], \
            (res, frames.shape)
