"""Production mesh factory (required shape per the dry-run contract).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """All local devices on 'data', no model parallelism."""
    return make_mesh((len(jax.devices()), 1), ("data", "model"))


def make_local_mesh(axis: str = "streams"):
    """1-D mesh over THIS process's devices only (``jax.local_devices()``)
    — the default fleet mesh.  Unlike ``make_mesh`` without ``devices``
    (which fills from the global device list), this can never silently
    span another process's devices: multi-process fleets get one
    per-process mesh each, coordinated by
    ``repro.parallel.topology.FleetTopology``."""
    devices = jax.local_devices()
    return make_mesh((len(devices),), (axis,), devices=devices)
