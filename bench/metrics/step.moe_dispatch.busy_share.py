"""Routing and dispatch of the expert layers (the program's ``router`` and
``dispatch`` scopes: scores, top-k, the balance loss, the sort by expert,
the permute and unpermute), over the device's busy time in the traced
window.  Nothing to read where the program names no such scope."""

from bench import moe_layers


def read(r):
    return moe_layers.busy_share(r, ("router", "dispatch"))
