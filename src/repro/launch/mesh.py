"""Production meshes.  Functions, not module constants — importing this
module must never touch jax device state (DESIGN.md §4)."""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips ("data","model").
    Multi-pod: 2x16x16 = 512 chips ("pod","data","model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over however many real devices exist (tests/examples)."""
    n = len(jax.devices())
    return _make_mesh((n // model, model), ("data", "model"))


def dp_axes(mesh) -> tuple[str, ...]:
    """Batch axes of a production mesh ('pod' included when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_axis(mesh) -> str:
    return "model"
