"""Build the system under test from a configuration file.

The served path is the program's own: ``VisionServeEngine`` replicas with
the Pallas ingest kernels, behind ``FleetGateway(parallel=True)``, whose
``FleetStep`` runs every replica's device work in one fused dispatch per
tick and compiles it (its warm-up) when the gateway is built.  The
benchmark supplies the models' widths and the weights, the same on every
replica.  ``VisionServeEngine`` takes no width: it builds its models'
configurations (``dc``, ``pc``) from the input resolution alone, so the
benchmark sets them to the configuration's widths before the fleet is
built; the engine's forwards and ``FleetStep`` read them from there.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.models import vision as V
from repro.streams import FleetGateway, VisionServeEngine
from repro.streams.filter import MotionGate


def engines(cfg: dict) -> list:
    """The configuration's replicas; their Pallas kernels run compiled on
    a TPU and interpreted elsewhere (the CPU tests)."""
    return [VisionServeEngine(
        f"r{i}", slots=cfg["slots"], frame_res=cfg["frame_res"],
        input_res=cfg["input_res"], fps=cfg["fps"], use_pallas=True,
        gate=MotionGate(cfg["slots"], gate_res=cfg["gate_res"],
                        block=cfg["gate_block"]))
        for i in range(cfg["replicas"])]


def set_widths(cfg: dict, replica) -> None:
    """The configuration's model widths in the replica's model configs."""
    d, p = cfg["detector"], cfg["pose"]
    replica.dc = dataclasses.replace(
        replica.dc, channels=tuple(d["channels"]),
        num_classes=d["num_classes"], num_anchors=d["num_anchors"])
    replica.pc = dataclasses.replace(
        replica.pc, channels=tuple(p["channels"]),
        num_keypoints=p["num_keypoints"])


def check_shapes(cfg: dict, replica, params: dict) -> None:
    """The engine's models have the configuration's widths, and the
    benchmark's weights fit the tree the program builds for them."""
    want = {"outer": (replica.dc, cfg["detector"]),
            "inner": (replica.pc, cfg["pose"])}
    for kind, (vc, model) in want.items():
        if list(vc.channels) != list(model["channels"]) or vc.width_mult != 1:
            raise ValueError(f"{kind} model widths {vc.channels} x "
                             f"{vc.width_mult} != configuration "
                             f"{model['channels']}")
        if vc.input_res != cfg["input_res"]:
            raise ValueError(f"{kind} input {vc.input_res} != "
                             f"{cfg['input_res']}")
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
    """Install the widths and weights and build (and warm) the fused
    fleet."""
    for r in replicas:
        set_widths(cfg, r)
        check_shapes(cfg, r, params)
        r.dp, r.pp = params["outer"], params["inner"]
    return FleetGateway(replicas, parallel=True, fleet_mode=cfg["mode"])
