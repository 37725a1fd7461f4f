"""Production mesh construction.

Mesh shapes (TPU v5e-pod-scale):
    single pod : (16, 16)      axes ("data", "model")    = 256 chips
    multi-pod  : (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
device query.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with every axis in Auto mode.

    ``jax.make_mesh`` of jax 0.9 defaults to Explicit axes, which
    ``with_sharding_constraint`` rejects; the logical-axis rules here
    (sharding/specs.py ``constrain``) and every caller in this repo assume
    Auto sharding propagation."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1, data: Optional[int] = None) -> Mesh:
    """Mesh over whatever devices exist (tests / laptop runs)."""
    n = jax.device_count()
    data = data or (n // model)
    return make_mesh((data, model), ("data", "model"))


def mesh_chip_count(mesh: Mesh) -> int:
    return mesh.size


def describe(mesh: Mesh) -> str:
    return "×".join(f"{k}={v}" for k, v in mesh.shape.items())
