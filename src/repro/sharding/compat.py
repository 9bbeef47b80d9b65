"""Mesh construction shared by the serving and distribution layers."""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(axis_shapes: Sequence[int], axis_names: Tuple[str, ...],
              *, devices=None, **kw) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` — the mode the
    partition rules and the fleet's ``replica`` axis assume.

    ``devices=None`` takes the first ``prod(axis_shapes)`` local devices, so
    a mesh smaller than the host device pool (the fleet's ``replica`` axis
    on a four-chip host, or on an ``--xla_force_host_platform_device_count``
    CPU mesh) needs no slicing of ``jax.devices()`` by the caller.
    """
    if devices is None:
        n = 1
        for s in axis_shapes:
            n *= int(s)
        pool = jax.devices()
        if len(pool) < n:
            raise ValueError(f"mesh {tuple(axis_shapes)} needs {n} devices, "
                             f"only {len(pool)} available")
        devices = pool[:n]
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names, devices=devices,
                         axis_types=(AxisType.Auto,) * len(names), **kw)
