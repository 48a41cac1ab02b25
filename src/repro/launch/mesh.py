"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS *before* first jax
init, and smoke tests must keep seeing one CPU device.

Single pod:  (16, 16)        axes ("data", "model")      — 256 chips (v5e pod)
Multi-pod:   (2, 16, 16)     axes ("pod", "data", "model") — 512 chips

The "pod" axis composes with "data" for gradient reduction (batch is
sharded over ("pod", "data")); "model" carries tensor/expert parallelism
inside a pod, where ICI is fastest.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

__all__ = ["make_production_mesh", "make_mesh"]


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(
    shape: Tuple[int, ...], axes: Tuple[str, ...]
) -> jax.sharding.Mesh:
    """Arbitrary mesh for tests / the tuner's candidate configurations.

    Every axis is Auto: the sharding rules place arrays with
    `with_sharding_constraint`, which refuses Explicit axes (the
    `jax.make_mesh` default)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def data_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """The batch-parallel axes of a mesh ("pod" composes with "data")."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names) or (names[0],)


def model_axis(mesh: jax.sharding.Mesh) -> Optional[str]:
    return "model" if "model" in mesh.axis_names else None
