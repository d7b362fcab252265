"""Mesh construction.  A FUNCTION, not a module constant — importing this
module never touches jax device state (dry-run sets XLA_FLAGS first).

Every axis is ``AxisType.Auto``: the model is written for GSPMD propagation
(params carry NamedShardings, activations follow), and explicit-sharding
axes would make ops such as the embedding gather demand an out_sharding."""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: 16x16 = 256 chips ("data", "model").
    Multi-pod: 2x16x16 = 512 chips ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(tp: int = 1) -> Mesh:
    """Mesh over whatever devices exist (tests, CPU examples)."""
    n = jax.device_count()
    assert n % tp == 0, (n, tp)
    return jax.make_mesh((n // tp, tp), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
