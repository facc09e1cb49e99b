"""Robust aggregation of the reported group gradients (the program's
``aggregate`` scope: wire codec, batch means, trim, Weiszfeld or the rule's
own work) over the device's busy time in the traced window.  Nothing to
read where the program names no such scope."""

from bench.scopes import busy_share


def read(r):
    return busy_share(r, "aggregate")
