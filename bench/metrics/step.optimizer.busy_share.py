"""The optimizer's update and the parameter add (the program's
``optimizer`` scope) over the device's busy time in the traced window.
Nothing to read where the program names no such scope."""

from bench.scopes import busy_share


def read(r):
    return busy_share(r, "optimizer")
