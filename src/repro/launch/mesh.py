"""Production mesh construction.

Functions, not module-level constants: importing this module never
touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import (see dryrun.py); tests and benches see the real (single) device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the model code shards
    through ``with_sharding_constraint``, which only accepts Auto axes
    (``make_mesh`` defaults to Explicit ones)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for subprocess integration tests (8 fake devices)."""
    return auto_mesh(shape, axes)
