"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run must set XLA_FLAGS
before any jax initialization.
"""

from __future__ import annotations

import jax

from jax.sharding import AxisType

__all__ = [
    "make_production_mesh",
    "make_mesh",
    "data_axes",
    "MODEL_AXIS",
]

MODEL_AXIS = "model"


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types: the installed JAX defaults
    to Explicit axes, and every sharding rule here is written for Auto."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2 pods x 256 = 512 chips (pod, data, model).

    Nothing downstream binds to these sizes — sharding rules name axes,
    so (8, 16, 16) or larger pods lower identically.
    """
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def data_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """All batch-parallel axes: ('pod', 'data') when the pod axis exists."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
