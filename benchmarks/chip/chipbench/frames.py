"""Seeded dash-cam frame pools, made on the device in one jitted call.

One general generator serves every traffic mix: a mix's JSON file gives
each camera's scene parameters, and only those differ between ``motion``
(driving) and ``parked`` (idling) traffic.

* ``road`` (outer camera): sky above a horizon, a perspective road whose
  lane dashes and roadside texture advance by ``ego_speed`` depth units a
  frame (ego-motion toward the camera), and moving objects.
* ``cabin`` (inner camera): a cabin wall, a side window showing the
  outside move by ``ego_speed``, and a driver's head and hands, swaying.

Objects are ellipses, one for each entry of ``size`` (its radius) and
``speed``, with a seeded height and shade, and a seeded start across the
frame unless ``x`` fixes it.  Each moves in a straight line
at its ``speed`` in frame widths a frame, wrapping over a span of
``span`` frame widths (a span above 1 keeps an object off screen part of
the time: a pedestrian who crosses now and then), and sways by ``sway``
with period ``sway_period`` frames.  Sensor noise of ``noise`` is added
before frames are quantised to 1/255, the program's input contract.

The object count, sizes, speeds and arrivals are the mix's and do not
depend on the seed; the seed draws where each object starts and its
shade, and the noise.
"""
from __future__ import annotations

import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

QUANT = np.float32(255.0)


def seed_words(seed: int, n: int) -> list:
    """``n`` 31-bit integers from any whole-number seed (JAX's PRNG keys
    take 32-bit seeds; benchmark seeds may be larger)."""
    words = np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)
    return [int(w) >> 1 for w in words]


def _objects(key, spec: dict):
    """Per-object start (y, x), radii, shade and phase drawn from ``key``."""
    n = len(spec["size"])
    k1, k2, k3, k4 = jax.random.split(key, 4)
    lo, hi = spec["y_range"]
    y0 = jax.random.uniform(k1, (n,), minval=lo, maxval=hi)
    x0 = (jnp.asarray(spec["x"], jnp.float32) if "x" in spec
          else jax.random.uniform(k2, (n,), minval=0.0, maxval=1.0))
    size = jnp.asarray(spec["size"], jnp.float32)
    shade = jax.random.uniform(k3, (n, 3), minval=0.05, maxval=0.95)
    phase = jax.random.uniform(k4, (n,), minval=0.0, maxval=2 * np.pi)
    return y0, x0, size, shade, phase


def _background(kind: str, yy, xx, k, p: dict):
    """(R, R, 3) background of frame ``k``."""
    hor = p.get("horizon", 0.4)
    ego = p["ego_speed"] * k
    if kind == "road":
        below = jnp.clip(yy - hor, 1e-3, None)
        depth = 0.25 / below                          # far rows: deep
        half = 0.05 + 0.5 * below / (1.0 - hor)       # road half-width
        on_road = (jnp.abs(xx - 0.5) < half) & (yy > hor)
        dash = ((jnp.abs(xx - 0.5) < 0.01 + 0.02 * below)
                & (jnp.mod(depth + ego, 1.0) < 0.5) & (yy > hor))
        grass = 0.45 + 0.1 * jnp.sin(2 * np.pi * (3 * depth + ego))
        sky = 0.55 + 0.35 * (1.0 - yy / hor)
        g = jnp.where(yy <= hor, sky, jnp.where(on_road, 0.33, grass))
        g = jnp.where(dash, 0.92, g)
        tint = jnp.where((yy <= hor)[..., None], jnp.asarray([0.8, 0.9, 1.0]),
                         jnp.where(on_road[..., None],
                                   jnp.asarray([1.0, 1.0, 1.0]),
                                   jnp.asarray([0.7, 1.0, 0.6])))
        return g[..., None] * tint
    if kind == "cabin":
        wall = 0.2 + 0.15 * yy + 0.03 * jnp.sin(12 * np.pi * xx)
        window = (xx > 0.72) & (yy < 0.45)
        outside = 0.6 + 0.2 * jnp.sin(2 * np.pi * (4 * xx + ego))
        g = jnp.where(window, outside, wall)
        return g[..., None] * jnp.asarray([1.0, 0.95, 0.9])
    raise ValueError(f"unknown scene background {kind!r}")


def _frame(key_noise, objs, yy, xx, k, p: dict):
    img = _background(p["background"], yy, xx, k, p)
    o = p["objects"]
    y0, x0, size, shade, phase = objs
    span = float(o.get("span", 1.2))
    t = k
    sway = o.get("sway", 0.0) * jnp.sin(
        2 * np.pi * t / o.get("sway_period", 30.0) + phase)
    speed = jnp.asarray(o["speed"], jnp.float32)
    cx = jnp.mod(x0 + speed * t + 0.1, span) - 0.1 + sway
    cy = y0 + 0.5 * sway
    ry, rx = size, size * o.get("aspect", 1.0)
    for i in range(y0.shape[0]):
        inside = (((yy - cy[i]) / ry[i]) ** 2
                  + ((xx - cx[i]) / rx[i]) ** 2) < 1.0
        img = jnp.where(inside[..., None], shade[i], img)
    img = img + p["noise"] * jax.random.normal(key_noise, img.shape)
    return jnp.clip(img, 0.0, 1.0)


@partial(jax.jit, static_argnames=("n", "res", "params"))
def _pool(key, *, n: int, res: int, params: str):
    p = json.loads(params)
    k_obj, k_noise = jax.random.split(key)
    objs = _objects(k_obj, p["objects"])
    ax = (jnp.arange(res, dtype=jnp.float32) + 0.5) / res
    yy, xx = ax[:, None], ax[None, :]
    keys = jax.random.split(k_noise, n)
    frames = jax.vmap(lambda kk, k: _frame(kk, objs, yy, xx, k, p))(
        keys, jnp.arange(n, dtype=jnp.float32))
    # levels 0..255 as bytes, rows of (width x channels): a quarter of the
    # float32 bytes to bring to the host, in a layout the device gives up
    # without a transpose
    return jnp.round(frames * QUANT).astype(jnp.uint8).reshape(n, res, -1)


def make_pool(camera: dict, *, frames: int, res: int, seed: int) -> np.ndarray:
    """``(frames, res, res, 3)`` float32 frames in [0, 1], quantised to
    1/255, for one camera's scene parameters; host memory, as a camera
    hands them over."""
    levels = np.asarray(_pool(jax.random.key(seed), n=frames, res=res,
                              params=json.dumps(camera, sort_keys=True)))
    return levels.reshape(frames, res, res, 3).astype(np.float32) / QUANT
