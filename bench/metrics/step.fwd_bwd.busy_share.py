"""The group step's forward and backward passes over the k batch groups
(the program's ``group_fwd_bwd`` scope) over the device's busy time in the
traced window.  Nothing to read where the program names no such scope."""

from bench.scopes import busy_share


def read(r):
    return busy_share(r, "group_fwd_bwd")
