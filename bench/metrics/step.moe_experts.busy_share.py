"""The routed experts' grouped matmuls (the program's ``experts`` scope
and the grouped matmul kernels the compiler makes of it), forward,
backward and remat's second forward, over the device's busy time in the
traced window.  Nothing to read where the program names no such scope."""

from bench import moe_layers


def read(r):
    return moe_layers.busy_share(r, ("experts",), grouped_matmuls=True)
