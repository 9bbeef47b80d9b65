"""Compile rehearsals for a TPU v5e: the served path, compiled, not run.

The TPU compiler is installed even where no chip is attached, and it
compiles for a described v5e topology.  These tests lower the main-path
Pallas kernels at the smoke geometry of ``chip_smoke.py`` (384 px frames,
192 px model input, 32 px gate, 8 slots) and the fused fleet tick — one
chip under ``vmap`` and the ``shard_map`` replica mesh on a 2x2 host — and
compile them with Mosaic, so a kernel the chip's compiler refuses (block
tiling, in-kernel reshapes, VMEM) fails here, at no chip time.  Nothing
executes: a compile that passes is not a chip run.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs.eda_vision import detector_config, pose_config
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


def _fleet_operands(R, sharded, replica_state):
    """Abstract operands of the fused tick for R replicas at the smoke
    geometry: stacked params from ``jax.eval_shape``, everything else
    from its shape.  ``replica_state(shape)`` builds a per-replica state
    operand (a tuple of arrays)."""
    dc, pc = detector_config(MODEL), pose_config(MODEL)
    key = jax.random.key(0)

    def stacked(tree):
        return jax.tree_util.tree_map(
            lambda s: _sds((R,) + s.shape, sharded, s.dtype), tree)

    ops = {"dp": stacked(jax.eval_shape(
               functools.partial(V.init_detector, dc), key)),
           "pp": stacked(jax.eval_shape(
               functools.partial(V.init_pose, pc), key)),
           "stage": _sds((R, S, FRAME, FRAME, 3), sharded)}
    for kind in ("outer", "inner"):
        ops[f"thr_{kind}"] = _sds((R, S), sharded)
        ops[f"href_{kind}"] = _sds((R, S), sharded, BOOL)
        ops[f"act_{kind}"] = _sds((R, S), sharded, BOOL)
        ops[f"batch_{kind}"] = replica_state((S, MODEL, MODEL, 3))
        ops[f"refs_{kind}"] = replica_state((S, GATE, GATE, 3))
    return (dc, pc), ops


def test_fused_fleet_tick_compiles_for_one_v5e_chip(one_chip):
    R = 2
    (dc, pc), ops = _fleet_operands(
        R, one_chip, lambda shape: tuple(_sds(shape, one_chip)
                                         for _ in range(R)))
    fused = _build_fused("vmap", None, (tuple(range(R)),),
                         ((dc, pc, MODEL, "float32"),), True, True, GATE,
                         BLOCK, False)
    compiled = fused.lower([ops]).compile()
    assert "tpu_custom_call" in compiled.as_text()
    stats = compiled.memory_analysis()
    # the whole tick fits a v5e's 16 GB of HBM many times over
    assert stats.temp_size_in_bytes + stats.argument_size_in_bytes < 2**30


def test_fused_fleet_tick_compiles_on_v5e_2x2_replica_mesh(topo):
    R = 4
    mesh = Mesh(np.asarray(topo.devices).reshape(R), ("replica",))
    sharded = NamedSharding(mesh, PartitionSpec("replica"))
    # one replica per chip: each state operand is one flat array whose
    # per-chip shard is that replica's (slots, ...) array
    (dc, pc), ops = _fleet_operands(
        R, sharded, lambda shape: (_sds((R * shape[0],) + shape[1:],
                                        sharded),))
    fused = _build_fused("shard_map", mesh, (tuple(range(R)),),
                         ((dc, pc, MODEL, "float32"),), True, True, GATE,
                         BLOCK, False)
    compiled = fused.lower(ops).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # replicas are independent: the tick needs no cross-chip traffic
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute"):
        assert collective not in text, collective
