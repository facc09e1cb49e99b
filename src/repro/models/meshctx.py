"""Ambient-mesh helpers for model code.

Model functions stay mesh-agnostic on CPU (tests) and pick up the production
sharding strategy automatically under ``jax.set_mesh`` — the same pattern as
models/moe.py's expert-parallel path.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def current_mesh():
    """The ambient mesh installed by ``jax.set_mesh``, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_size(mesh) -> int:
    n = 1
    for a in data_axes(mesh):
        n *= mesh.shape[a]
    return n


def model_size(mesh) -> int:
    return mesh.shape.get("model", 1) if "model" in mesh.axis_names else 1


def dspec(mesh):
    ax = data_axes(mesh)
    return ax if len(ax) > 1 else (ax[0] if ax else None)


def sp_applicable(mesh, batch: int, seq: int) -> bool:
    """Sequence-parallel attention needs batch % data == 0 and
    seq % model == 0."""
    if mesh is None or "model" not in mesh.axis_names or not data_axes(mesh):
        return False
    return batch % data_size(mesh) == 0 and seq % model_size(mesh) == 0 \
        and seq >= model_size(mesh) * 16


def constrain(x, spec_tuple):
    """with_sharding_constraint under the ambient mesh (no-op without)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.NamedSharding(mesh, P(*spec_tuple)))
