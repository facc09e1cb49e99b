"""Device time of the expert layer's parts, by the program's named scopes
(``router``, ``dispatch``, ``experts``, ``shared_experts``, ``mla``).

The TPU compiler rewrites each grouped matmul (``jax.lax.ragged_dot``) into
a kernel of its own, ``ragged-dot-none.N`` with its ``ragged-dot-metadata.N``,
and drops the instruction's ``op_name`` on the way, so the ``experts``
scope finds them by that name.  Nothing here imports the program.
"""

from __future__ import annotations

from bench import scopes

GROUPED_MATMUL = "ragged-dot"


def is_grouped_matmul(event_name: str) -> bool:
    return scopes.instruction(event_name).startswith(GROUPED_MATMUL)


def layer_seconds(r, names: tuple, grouped_matmuls: bool = False):
    """Device seconds of the ops under any of the scopes ``names`` (and of
    the grouped matmul kernels), their union, inside the window, averaged
    over the chips.  None where the program names none of these scopes."""
    prog = scopes.of_reading(r)
    if prog is None:
        return None
    inside = {n for n, path in prog.scopes.items()
              if any(scopes.in_scope(path, s) for s in names)}
    if not inside:
        return None
    return r.trace.op_seconds(
        lambda e: scopes.instruction(e) in inside
        or (grouped_matmuls and is_grouped_matmul(e)))


def busy_share(r, names: tuple, grouped_matmuls: bool = False):
    """``layer_seconds`` over the device's busy time, in percent."""
    t = layer_seconds(r, names, grouped_matmuls)
    if t is None:
        return None
    return 100.0 * t / r.trace.busy_s()
