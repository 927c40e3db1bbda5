"""Mesh builders.  Functions, not module constants: importing this module
must never touch jax device state."""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, devices=None) -> Mesh:
    """The one mesh builder.  Every axis is ``Auto``: ``jax.make_mesh``
    defaults to ``Explicit`` axes, which ``with_sharding_constraint``
    (``models/sharding.py``) rejects.  ``devices`` (default: all) is laid
    out row-major into ``shape``."""
    shape, axes = tuple(shape), tuple(axes)
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=types)
    return Mesh(np.asarray(devices).reshape(shape), axes, axis_types=types)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips, 'pod' over DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_dev_mesh(n_devices: int = 0):
    """Small mesh over whatever devices exist (tests / CPU dev box)."""
    n = n_devices or len(jax.devices())
    return make_mesh((1, n), ("data", "model"))


def trainer_generator_submeshes(theta: float = 0.5):
    """Split the device set into disjoint trainer/generator submeshes
    (paper Def. 7.4's theta fraction).  Requires >= 2 devices."""
    devs = jax.devices()
    n = len(devs)
    n_train = max(1, int(n * theta))
    if n - n_train < 1:
        n_train = n - 1
    t = make_mesh((1, n_train), ("data", "model"), devs[:n_train])
    g = make_mesh((1, n - n_train), ("data", "model"), devs[n_train:])
    return t, g
