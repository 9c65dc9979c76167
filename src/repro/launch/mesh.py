"""Production mesh builder (the brief's required interface).

A function — not a module-level constant — so importing this module never
touches jax device state.  The single-pod production mesh is 16x16 = 256
chips (data x model over ICI); the multi-pod job adds a leading pod axis
(2 x 16 x 16 = 512 chips, pod axis over DCN)."""

from __future__ import annotations

from repro.distributed.mesh import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def mesh_desc(mesh) -> str:
    return "x".join(f"{k}={v}" for k, v in mesh.shape.items())
