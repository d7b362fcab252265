"""Trace-time mesh context for activation sharding constraints.

GSPMD propagates input/param shardings well, but the remat layer stash is
shaped by the scan-body *boundary* layout.  ``constrain`` lets model code
pin activations (e.g. sequence-sharded residual stream — Megatron-style SP)
when a mesh is installed; it is a no-op otherwise, so models stay runnable
on bare CPU.  The installed mesh also places the ``shard_map`` boundary that
``nn.layers`` puts around the Pallas kernels, which GSPMD cannot partition.
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Optional, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: Optional[Mesh] = None


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _MESH
    _MESH = mesh


def get_mesh() -> Optional[Mesh]:
    return _MESH


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]) -> Iterator[None]:
    """Install ``mesh`` for a with-block; the previous mesh comes back."""
    prev = _MESH
    set_mesh(mesh)
    try:
        yield
    finally:
        set_mesh(prev)


def constrain(x: jax.Array, *parts) -> jax.Array:
    """with_sharding_constraint with auto-drop: each entry of ``parts`` is a
    mesh-axis name / tuple / None; axes missing from the mesh or not
    dividing the dim are dropped (same policy as sharding.spec_for)."""
    if _MESH is None:
        return x
    used: set = set()
    out = []
    for dim, part in zip(x.shape, parts):
        if part is None:
            out.append(None)
            continue
        axes = part if isinstance(part, tuple) else (part,)
        sel = [a for a in axes if a in _MESH.shape and a not in used]
        tot = int(np.prod([_MESH.shape[a] for a in sel])) if sel else 1
        if sel and dim % tot == 0:
            out.append(tuple(sel) if len(sel) > 1 else sel[0])
            used.update(sel)
        else:
            out.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_MESH, P(*out)))
