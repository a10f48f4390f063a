"""Production mesh factory.

Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi-pod:  2x16x16 = 512 chips, axes ("pod", "data", "model") — the pod
axis is an outer data-parallel axis crossing the (scarce) inter-pod links.

A function, not a module constant: importing this module must never touch
jax device state (the dry-run pins the device count before first jax use).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as np
    need = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devices)} — "
            "run under dryrun.py (it sets xla_force_host_platform_device_count)")
    return jax.make_mesh(shape, axes, devices=devices[:need],
                         axis_types=(AxisType.Auto,) * len(shape))


def make_local_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    if model_parallel < 1 or n % model_parallel != 0:
        raise ValueError(
            f"model_parallel={model_parallel} must divide the device "
            f"count ({n} available) — force more host devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N or pick "
            "a TP degree that divides the machine")
    # Auto axes: GSPMD propagates shardings through the serve waves; the
    # default Explicit axes would demand an out_sharding on every gather
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"), axis_types=(AxisType.Auto,) * 2)
