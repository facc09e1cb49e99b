"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model").

The Byzantine worker axis is ``data`` (x ``pod`` on multi-pod) — see
DESIGN.md §4.  Functions, not module constants: importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before first init).
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    jax 0.9 defaults to Explicit axes, under which the model's
    ``with_sharding_constraint`` calls change a scan carry's type; the
    sharding rules here are written for Auto (GSPMD-propagated) axes."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(*, data: int = 2, model: int = 2, pod: int | None = None):
    """Small virtual mesh for CI-scale dry-run tests (8 host devices)."""
    if pod is not None:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    """The worker/batch axes: ("data",) or ("pod", "data")."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


def data_size(mesh) -> int:
    out = 1
    for a in data_axes(mesh):
        out *= mesh.shape[a]
    return out


def model_size(mesh) -> int:
    return mesh.shape["model"]
