"""Latent attention (the program's ``mla`` scope: the projections, the
latent's norm, the rotary embeddings and the blocked attention core),
forward and backward, over the device's busy time in the traced window.
Nothing to read where the program names no such scope."""

from bench import moe_layers


def read(r):
    return moe_layers.busy_share(r, ("mla",))
