"""Production meshes.

Single pod: (16, 16) = 256 chips, axes ('data', 'model').
Multi-pod:  (2, 16, 16) = 512 chips, axes ('pod', 'data', 'model') — the
'pod' axis carries only int8 vote counts (DESIGN.md §2).
One host:   (n, 1) over its n chips, axes ('data', 'model') — one voting
replica per chip (a v5e 2x2 host gives data=4).

A function, not a module-level constant: importing this module never
touches jax device state.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax

from repro import compat


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(
        shape, axes, axis_types=(compat.AxisType.Auto,) * len(shape))


def make_replica_mesh(devices: Optional[Sequence] = None):
    """(data=n, model=1) over `devices` (default ``jax.devices()``)."""
    devices = list(jax.devices() if devices is None else devices)
    return compat.make_mesh(
        (len(devices), 1), ("data", "model"),
        axis_types=(compat.AxisType.Auto,) * 2, devices=devices)


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def pod_stride(mesh) -> int:
    """Linear device-id stride between pods (for HLO group attribution)."""
    sizes = mesh_axis_sizes(mesh)
    return sizes.get("data", 1) * sizes.get("model", 1)
