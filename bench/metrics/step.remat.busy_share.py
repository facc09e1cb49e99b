"""Work the backward pass recomputes: the ops JAX marks as rematerialised
(an ``op_name`` path through ``rematted_computation``, the forward of a
checkpointed block run again before its backward) over the device's busy
time in the traced window.  A fused op counts under the path of the op it
is named after.  Nothing to read where the program names no scopes."""

from bench.scopes import busy_share


def read(r):
    return busy_share(r, "rematted_computation")
