"""Compile rehearsals for a TPU v5e: the served path, compiled, not run.

The TPU compiler is installed even where no chip is attached, and it
compiles for a described v5e topology.  These tests lower the main-path
Pallas kernels at the smoke geometry of ``chip_smoke.py`` (384 px frames,
192 px model input, 32 px gate, 8 slots) and the fused fleet tick — one
chip under ``vmap`` and the ``shard_map`` replica mesh on a 2x2 host — and
compile them with Mosaic, so a kernel the chip's compiler refuses (block
tiling, in-kernel reshapes, VMEM) fails here, at no chip time.  The fused
tick's staged frames are checked for the layout the upload needs: a
row-major ``(R, slots, H, W*3)`` parameter that the kernels read with no
stage-sized device copy.  Nothing executes: a compile that passes is not
a chip run.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import dataclasses
import difflib
import functools
import gzip
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs.eda_vision import (MOBILENET_V1, detector_config,
                                      movenet_lightning_config, pose_config,
                                      ssd_mobilenet_v1_config)
from repro.kernels import vision_ops
from repro.models import vision as V
from repro.streams.fleet_step import _build_fused

S, FRAME, MODEL, GATE, BLOCK = 8, 384, 192, 32, 8
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


BOOL = jnp.bool_
KERNELS = {
    "ingest_frame": (
        functools.partial(vision_ops.ingest_frame, model_res=MODEL,
                          gate_res=GATE, block=BLOCK, interpret=False),
        [((S, FRAME, FRAME, 3), F32), ((S, GATE, GATE, 3), F32)]),
    "scatter_admit": (
        functools.partial(vision_ops.scatter_admit, interpret=False),
        [((S, MODEL, MODEL, 3), F32), ((S, MODEL, MODEL, 3), F32),
         ((S, GATE, GATE, 3), F32), ((S, GATE, GATE, 3), F32),
         ((S,), BOOL)]),
    "downscale": (
        functools.partial(vision_ops.downscale, res=MODEL, interpret=False),
        [((S, FRAME, FRAME, 3), F32)]),
    "block_sad": (
        functools.partial(vision_ops.block_sad, block=BLOCK,
                          interpret=False),
        [((S, GATE, GATE, 3), F32), ((S, GATE, GATE, 3), F32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_vision_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    text = jax.jit(fn).lower(
        *[_sds(shape, one_chip, dtype) for shape, dtype in shapes]
    ).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


def _fleet_operands(R, sharded, replica_state, models=None, slots=S):
    """Abstract operands of the fused tick for R replicas, by default at
    the smoke geometry: stacked params from ``jax.eval_shape``, everything
    else from its shape, each camera's batch pool at its model's input
    resolution.  ``replica_state(shape)`` builds a per-replica state
    operand (a tuple of arrays)."""
    dc, pc = models or (detector_config(MODEL), pose_config(MODEL))
    key = jax.random.key(0)

    def stacked(tree):
        return jax.tree_util.tree_map(
            lambda s: _sds((R,) + s.shape, sharded, s.dtype), tree)

    ops = {"dp": stacked(jax.eval_shape(
               functools.partial(V.init_detector, dc), key)),
           "pp": stacked(jax.eval_shape(
               functools.partial(V.init_pose, pc), key)),
           "stage": _sds((R, slots, FRAME, FRAME * 3), sharded)}
    for kind, vc in (("outer", dc), ("inner", pc)):
        ops[f"thr_{kind}"] = _sds((R, slots), sharded)
        ops[f"href_{kind}"] = _sds((R, slots), sharded, BOOL)
        ops[f"act_{kind}"] = _sds((R, slots), sharded, BOOL)
        ops[f"batch_{kind}"] = replica_state(
            (slots, vc.input_res, vc.input_res, 3))
        ops[f"refs_{kind}"] = replica_state((slots, GATE, GATE, 3))
    return (dc, pc), ops


def _one_chip_tick(one_chip, R, models=None, slots=S):
    """The fused tick of R replicas under ``vmap`` on one chip,
    compiled."""
    (dc, pc), ops = _fleet_operands(
        R, one_chip, lambda shape: tuple(_sds(shape, one_chip)
                                         for _ in range(R)), models, slots)
    fused = _build_fused("vmap", None, (tuple(range(R)),),
                         ((dc, pc, (dc.input_res, pc.input_res),
                           "float32"),), True, True, GATE, BLOCK, False)
    return fused.lower([ops]).compile()


def _mesh_tick(topo, R, models=None, slots=S):
    """The fused tick of R replicas in ``shard_map`` over the 2x2 replica
    mesh, R / 4 a chip, compiled."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("replica",))
    sharded = NamedSharding(mesh, PartitionSpec("replica"))
    k = R // 4
    # each state operand is k flat arrays whose per-chip shard is one of
    # that chip's replicas' (slots, ...) arrays
    (dc, pc), ops = _fleet_operands(
        R, sharded, lambda shape: tuple(
            _sds((4 * shape[0],) + shape[1:], sharded) for _ in range(k)),
        models, slots)
    fused = _build_fused("shard_map", mesh, (tuple(range(R)),),
                         ((dc, pc, (dc.input_res, pc.input_res),
                           "float32"),), True, True, GATE, BLOCK, False)
    return fused.lower(ops).compile()


@pytest.fixture(scope="module")
def fused_one_chip(one_chip):
    """The fused tick for two replicas on one chip, compiled."""
    return _one_chip_tick(one_chip, 2)


@pytest.fixture(scope="module")
def fused_mesh(topo):
    """The fused tick over the 2x2 replica mesh, one replica a chip,
    compiled."""
    return _mesh_tick(topo, 4)


def test_fused_fleet_tick_compiles_for_one_v5e_chip(fused_one_chip):
    assert "tpu_custom_call" in fused_one_chip.as_text()
    stats = fused_one_chip.memory_analysis()
    # the whole tick fits a v5e's 16 GB of HBM many times over
    assert stats.temp_size_in_bytes + stats.argument_size_in_bytes < 2**30


def test_fused_fleet_tick_compiles_on_v5e_2x2_replica_mesh(fused_mesh):
    text = fused_mesh.as_text()
    assert "tpu_custom_call" in text
    # replicas are independent: the tick needs no cross-chip traffic
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute"):
        assert collective not in text, collective


def _elements(dims: str) -> int:
    return int(np.prod([int(d) for d in dims.split(",") if d]))


def _unplaced(layout: str) -> str:
    """A layout without its memory space: ``3,2,1,0:T(8,128)S(1)`` ->
    ``3,2,1,0:T(8,128)``."""
    return re.sub(r"S\(\d+\)", "", layout)


@pytest.fixture
def program(request):
    """The compiled tick a test names, compiled at set-up: a compile takes
    seconds and belongs to no test's timed call."""
    return request.getfixturevalue(request.param)


@pytest.mark.parametrize("program", ["fused_one_chip", "fused_mesh",
                                     "fused_pair"], indirect=True)
def test_fused_tick_reads_staged_planes_without_a_relayout(program):
    """The stage parameter is row-major (W*3 minor, then H), so the host's
    planes upload as they are, and no copy in the tick writes the stage's
    elements in another layout: the kernels read the parameter itself.  A
    copy into another memory space at the parameter's own layout (the
    compiler's prefetch into VMEM, which it chooses by size) moves the
    bytes without reordering them and is not a relayout."""
    text = program.as_text()
    params = re.findall(r"^\s*%\S+ = f32\[([\d,]+)\]\{([^}]*)\} "
                        r"parameter\(.*op_name=\"[^\"]*stage", text, re.M)
    assert len(params) == 1, params
    dims, layout = params[0]
    assert dims.split(",")[-2:] == [str(FRAME), str(FRAME * 3)], dims
    assert _unplaced(layout).startswith("3,2,1,0:"), layout
    n = _elements(dims)
    copies = re.findall(r"^\s*(?:ROOT )?%[\w.\-]+ = (.+?) copy(?:-start)?\(",
                        text, re.M)
    assert copies, "no copy found: the pattern no longer reads this HLO"
    for outs in copies:
        for out, out_layout in re.findall(r"\w+\[([\d,]*)\]\{([^}]*)\}",
                                          outs):
            if _elements(out) == n:
                assert _unplaced(out_layout) == _unplaced(layout), outs


# ---------------------------------------------------------------------------
# the benchmark's configurations: eda-fleet-192 as it compiled before the
# published pair's models came in, and the pair's own tick
# ---------------------------------------------------------------------------


def canonical(text: str) -> str:
    """Compiled HLO text without what only names or places its parts: the
    source tables, op metadata, instruction names (renumbered by first
    use) and the Mosaic kernels' serialized bodies, which carry source
    locations (the kernels themselves are compiled above)."""
    out, skip = [], False
    for ln in text.split("\n"):
        if ln in ("FileNames", "FunctionNames", "FileLocations",
                  "StackFrames"):
            skip = True
            continue
        if skip and (ln.startswith(("%", "ENTRY")) or not ln):
            skip = False
        if not skip:
            out.append(ln)
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", "\n".join(out))
    text = re.sub(r'"body":"[^"]*"', '"body":""', text)
    names: dict = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  text)


def _eda_fleet_192():
    """eda-fleet-192's models: MobileNetV1 1.0 widths, COCO's 90 classes
    and 6 anchors a cell, at 192."""
    return (dataclasses.replace(detector_config(MODEL), channels=MOBILENET_V1,
                                num_classes=90, num_anchors=6),
            dataclasses.replace(pose_config(MODEL), channels=MOBILENET_V1))


# ``canonical`` of eda-fleet-192's compiled fused ticks, 16 lanes a
# replica: two replicas on one chip, and eight over the 2x2 mesh, two a
# chip.  Recorded from the program before the published pair's models
# came in, with JAX 0.9.0 and its TPU compiler; a new compiler rewrites
# them (``golden/eda-fleet-192.<program>.hlo.gz``) once its programs are
# checked to be the same.
GOLDEN = Path(__file__).resolve().parent / "golden"
EDA_FLEET_192 = ("mesh", "one_chip")


@pytest.fixture(scope="module", params=EDA_FLEET_192)
def eda_fleet_192_tick(request, topo, one_chip):
    """(program, compiled) of eda-fleet-192's fused tick."""
    if request.param == "one_chip":
        return request.param, _one_chip_tick(one_chip, 2, _eda_fleet_192(),
                                             slots=16)
    return request.param, _mesh_tick(topo, 8, _eda_fleet_192(), slots=16)


def test_eda_fleet_192_compiles_as_before_the_published_pair(
        eda_fleet_192_tick):
    program, compiled = eda_fleet_192_tick
    got = canonical(compiled.as_text())
    with gzip.open(GOLDEN / f"eda-fleet-192.{program}.hlo.gz", "rt") as f:
        want = f.read()
    if got != want:
        diff = difflib.unified_diff(want.split("\n"), got.split("\n"),
                                    "before", "now", n=1, lineterm="")
        pytest.fail("eda-fleet-192's compiled tick changed:\n"
                    + "\n".join(list(diff)[:80]))


@pytest.fixture(scope="module")
def fused_pair(one_chip):
    """eda-fleet-ssd300-movenet's fused tick: two replicas of 16 lanes,
    SSD-MobileNetV1 at 300 and MoveNet Lightning at 192, compiled."""
    return _one_chip_tick(one_chip, 2, (ssd_mobilenet_v1_config(),
                                        movenet_lightning_config()),
                          slots=16)


def test_the_published_pairs_tick_compiles_with_a_plane_per_camera(
        fused_pair):
    """The 384 px frames resample to a 300x900 plane for SSD and a
    192x576 one for MoveNet, each in an ingest kernel of its own, and the
    tick fits a v5e's 16 GB many times over."""
    text = fused_pair.as_text()
    # the ingest kernel's three outputs: the model plane, the gate plane
    # and the scores, each with the replica and lane axes ahead
    planes = re.findall(r"^\s*%\S+ = \(f32\[[\d,]*?(\d+),(\d+)\]\S*, "
                        r"f32\[[\d,]+\]\S*, f32\[[\d,]+\]\S*\) custom-call\(",
                        text, re.M)
    assert sorted(set(planes)) == [("192", "576"), ("300", "900")]
    stats = fused_pair.memory_analysis()
    assert stats.temp_size_in_bytes + stats.argument_size_in_bytes < 2**31
