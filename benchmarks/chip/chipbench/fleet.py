"""Build the system under test from a configuration file.

The served path is the program's own: ``VisionServeEngine`` replicas with
the Pallas ingest kernels, behind ``FleetGateway(parallel=True)``, whose
``FleetStep`` runs every replica's device work in one fused dispatch per
tick and compiles it (its warm-up) when the gateway is built.  The
benchmark supplies the models and the weights, the same on every
replica.  ``VisionServeEngine`` builds its models' configurations
(``dc``, ``pc``) from the input resolution alone, so the benchmark
applies each key of the configuration's ``detector`` and ``pose``
sections to them before the fleet is built; the engine's forwards and
``FleetStep`` read them from there.  A key that the program's
``VisionConfig`` lacks is refused: a configuration that names a model
part the program does not have fails at set-up, and never runs today's
model under its name.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from chipbench.spec import SECTION, model_res
from repro.models import vision as V
from repro.streams import FleetGateway, VisionServeEngine
from repro.streams.filter import MotionGate

ATTR = {"outer": "dc", "inner": "pc"}      # camera -> the engine's model


def engines(cfg: dict) -> list:
    """The configuration's replicas; their Pallas kernels run compiled on
    a TPU and interpreted elsewhere (the CPU tests)."""
    return [VisionServeEngine(
        f"r{i}", slots=cfg["slots"], frame_res=cfg["frame_res"],
        input_res=cfg["input_res"], fps=cfg["fps"], use_pallas=True,
        gate=MotionGate(cfg["slots"], gate_res=cfg["gate_res"],
                        block=cfg["gate_block"]))
        for i in range(cfg["replicas"])]


def _frozen(v):
    """A JSON value as a field of a frozen config: lists become tuples."""
    return tuple(_frozen(x) for x in v) if isinstance(v, list) else v


def section_fields(cfg: dict, kind: str, vc) -> dict:
    """The fields that camera ``kind``'s section gives, as ``vc``'s
    types hold them; a key that is no field of ``vc`` raises."""
    given = cfg[SECTION[kind]]
    unknown = sorted(set(given) - {f.name for f in dataclasses.fields(vc)})
    if unknown:
        raise ValueError(
            f"configuration {cfg.get('name')!r}: {SECTION[kind]} key(s) "
            f"{unknown} name no field of the program's "
            f"{type(vc).__name__}")
    return {k: _frozen(v) for k, v in given.items()}


def set_models(cfg: dict, replica) -> None:
    """Every key of the configuration's model sections in the replica's
    model configs."""
    for kind, attr in ATTR.items():
        vc = getattr(replica, attr)
        setattr(replica, attr,
                dataclasses.replace(vc, **section_fields(cfg, kind, vc)))


def check_shapes(cfg: dict, replica, params: dict) -> None:
    """The engine's models are the configuration's, field by field, each
    at its camera's resolution, which is also the rows the engine's ingest
    writes for that camera; and the benchmark's weights fit the tree the
    program builds for them."""
    for kind, attr in ATTR.items():
        vc = getattr(replica, attr)
        for k, v in section_fields(cfg, kind, vc).items():
            if getattr(vc, k) != v:
                raise ValueError(f"{kind} model {k} {getattr(vc, k)} != "
                                 f"configuration {v}")
        if vc.width_mult != 1:
            raise ValueError(f"{kind} model width_mult {vc.width_mult} "
                             f"!= 1")
        res = model_res(cfg, kind)
        rows = tuple(replica.batches[kind].shape[1:3])
        if vc.input_res != res or rows != (res, res):
            raise ValueError(f"{kind} model input {vc.input_res}, ingest "
                             f"rows {rows} != configuration {res}")
    key = jax.random.key(0)
    served = {"outer": jax.eval_shape(lambda k: V.init_detector(replica.dc, k),
                                      key),
              "inner": jax.eval_shape(lambda k: V.init_pose(replica.pc, k),
                                      key)}
    for kind in served:
        a = jax.tree.map(np.shape, served[kind])
        b = jax.tree.map(np.shape, params[kind])
        if a != b:
            raise ValueError(f"{kind} weights do not fit the engine: "
                             f"{b} != {a}")


def build(cfg: dict, params: dict, replicas: list) -> FleetGateway:
    """Install the models and weights and build (and warm) the fused
    fleet."""
    for r in replicas:
        set_models(cfg, r)
        check_shapes(cfg, r, params)
        r.dp, r.pp = params["outer"], params["inner"]
    return FleetGateway(replicas, parallel=True, fleet_mode=cfg["mode"])
