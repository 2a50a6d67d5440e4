"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — only the dry-run (which sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any import)
actually builds the 256/512-device meshes.
"""
from __future__ import annotations

import jax


def _mk(shape, axes, devices=None):
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto, devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_local_mesh(devices=None):
    """Mesh over this host's devices (default: all of them), with the
    production axis names: ``("data", "model") = (n, 1)``."""
    devices = jax.devices() if devices is None else list(devices)
    return _mk((len(devices), 1), ("data", "model"), devices)
