"""Production mesh construction (multi-pod dry-run spec) + the round engine's
data mesh.

Functions, not module-level constants: importing this module never touches
jax device state.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np


def _auto_axes(n: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto_axes(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU demos)."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"need {data*model} devices, have {n}")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto_axes(2))


ROUND_AXIS = "data"   # the axis the round engine shards clients / D over


def make_round_mesh(num_devices: Optional[int] = None):
    """1-D ``("data",)`` mesh over the first ``num_devices`` devices — the
    mesh the sharded round stages (``local_sgd_sharded`` /
    ``fused_int8_sharded``) shard over.

    Built directly from a device slice (not ``jax.make_mesh``) so a test can
    hold 1-, 2- and 8-device meshes of one forced-device CPU process at
    once."""
    devs = jax.devices()
    n = len(devs) if num_devices is None else num_devices
    if n > len(devs):
        raise ValueError(f"need {n} devices, have {len(devs)}")
    return jax.sharding.Mesh(np.array(devs[:n]), (ROUND_AXIS,))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (includes 'pod' when present)."""
    names = mesh.axis_names
    return tuple(a for a in names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"
