"""Mesh construction.

The production mesh is built by :func:`repro.launch.mesh.make_production_mesh`;
this module holds the generic builders shared by tests (small fake-device
meshes) and the launcher.

Logical axes:
  * ``pod``   — cross-pod axis (DCN); pure data parallelism.
  * ``data``  — intra-pod batch axis (ICI).
  * ``model`` — tensor-parallel axis (ICI).
  * ``stage`` — optional pipeline-parallel axis (tests / PP configs only).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

from repro.config import MeshConfig


def make_mesh(cfg: MeshConfig) -> Mesh:
    """Build a Mesh for ``cfg``; requires cfg.num_devices visible devices."""
    n = cfg.num_devices
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {cfg.shape} needs {n} devices, have {len(devices)} "
            "(dry-run scripts must set XLA_FLAGS=--xla_force_host_platform_device_count "
            "before importing jax)"
        )
    return auto_mesh(cfg.shape, cfg.axis_names, devices=devices[:n])


def auto_mesh(axis_shapes, axis_names, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis Auto (its default is Explicit), so
    logical sharding constraints apply inside jit."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices,
    )


def local_mesh(data: int = 1, model: int = 1, pod: int = 1) -> Mesh:
    """Small mesh over however many (possibly fake) devices exist — tests."""
    return make_mesh(MeshConfig(data=data, model=model, pod=pod))


def single_device_mesh() -> Mesh:
    """A 1x1 mesh so the same pjit code paths run on one CPU device."""
    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    return Mesh(dev, ("data", "model"))


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape.get(name, 1)


def dp_axis_names(mesh: Mesh) -> tuple[str, ...]:
    """Axes that carry batch parallelism on this mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
