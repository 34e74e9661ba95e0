"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device state
(the dry-run must set XLA_FLAGS before any jax initialization).

Target hardware: TPU v5e pods — 256 chips/pod in a (16,16) ICI torus;
multi-pod couples 2 pods over DCN. Constants used by the roofline analysis
live in benchmarks/roofline.py.
"""
from __future__ import annotations

import math

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devs)} — the "
            f"dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count"
            f"=512 before any jax import")
    return _auto_mesh(shape, axes, devs[:need])


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CI-sized sharding tests (devices permitting)."""
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(f"need {need} devices, have {len(devs)}")
    return _auto_mesh(shape, axes, devs[:need])


def _auto_mesh(shape, axes, devices):
    """A mesh whose axes GSPMD partitions (``AxisType.Auto``): the plans
    place arrays with NamedShardings and let propagation do the rest.
    ``jax.make_mesh`` defaults to Explicit axes, under which the engine's
    reshapes of sharded client state are refused at trace time."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
