"""Production mesh construction.

The target is TPU v5e: one pod = a 16x16 slice (256 chips); multi-pod = 2
pods (512 chips) joined over the slow DCI/network hop. Axes:

    single-pod:  ("data", "model")        = (16, 16)
    multi-pod :  ("pod", "data", "model") = (2, 16, 16)

``make_production_mesh`` is a function (never a module constant) so importing
this module never touches jax device state; the dry-run sets
``--xla_force_host_platform_device_count=512`` before any jax import.

Every mesh here has ``Auto`` axes (``repro.core.engine.mesh.make_mesh``).
"""
from __future__ import annotations

from typing import Tuple

import jax

from repro.core.engine.mesh import make_mesh

SINGLE_POD_SHAPE: Tuple[int, ...] = (16, 16)
SINGLE_POD_AXES: Tuple[str, ...] = ("data", "model")
MULTI_POD_SHAPE: Tuple[int, ...] = (2, 16, 16)
MULTI_POD_AXES: Tuple[str, ...] = ("pod", "data", "model")


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = MULTI_POD_AXES if multi_pod else SINGLE_POD_AXES
    return make_mesh(shape, axes)


def make_abstract_mesh(shape: Tuple[int, ...],
                       axes: Tuple[str, ...]) -> "jax.sharding.AbstractMesh":
    """``AbstractMesh`` over ``(shape, axes)`` (``Auto`` axes)."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / smoke runs)."""
    n = len(jax.devices())
    if n % model:
        raise ValueError(f"{n} devices do not split into model={model}")
    return make_mesh((n // model, model), SINGLE_POD_AXES)


def data_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """Axes the global batch is sharded over (pod included when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def axis_size(mesh: jax.sharding.Mesh, name: str) -> int:
    # mesh.shape works for both Mesh and AbstractMesh
    return dict(mesh.shape).get(name, 1)
