"""Routing balance over the held experts: each traced step's
``moe_load_max`` (per expert layer, the largest held expert's assignments
over the held experts' mean, summed over the k groups; the worst layer),
averaged over the window's steps.  A program counter; nothing to read
where the step does not count its assignments."""


def read(r):
    return r.counters.get("moe_load_max")
