"""Production mesh construction (DESIGN.md §6).

Defined as functions (never module-level constants) so importing this
module does not touch jax device state.  Single pod: 16x16 = 256 chips
(data x model).  Multi-pod: 2 x 16 x 16 = 512 chips (pod x data x model);
the 'pod' axis is data-parallel by default and carries only the gradient
all-reduce across the slow inter-pod links.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        # Auto axes: the sharding rules and shard_map code here are
        # GSPMD-style (jax.make_mesh's default is Explicit).
        return jax.make_mesh(shape, axes,
                             axis_types=(AxisType.Auto,) * len(shape))
    if len(devices) > n:
        # dry-run host platform exposes 512 devices; single-pod uses 256
        return Mesh(np.asarray(devices[:n]).reshape(shape), axes)
    raise ValueError(
        f"need {n} devices for mesh {shape}, have {len(devices)} "
        "(set XLA_FLAGS=--xla_force_host_platform_device_count=512 for the "
        "dry-run)")


def make_host_mesh(shape=None, axes=None) -> Mesh:
    """Small mesh over whatever devices exist (tests, examples).

    shape=None uses every device on a 1-D "data" axis.  An explicit
    shape without axes gets generated axis names ("ax0", "ax1", ...) —
    passing axes=None through to Mesh() used to crash.
    """
    devices = jax.devices()
    if shape is None:
        shape = (len(devices),)
        axes = axes or ("data",)
    shape = tuple(shape)
    if axes is None:
        axes = tuple(f"ax{i}" for i in range(len(shape)))
    axes = tuple(axes)
    if len(axes) != len(shape):
        raise ValueError(f"mesh shape {shape} needs {len(shape)} axis "
                         f"names, got {axes}")
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(f"need {n} devices for host mesh {shape}, "
                         f"have {len(devices)}")
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)
