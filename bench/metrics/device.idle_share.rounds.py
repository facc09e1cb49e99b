"""Share of the traced window of GD jobs in which no operation ran on the
device, averaged over the chips."""


def read(r):
    return 100.0 * r.trace.idle_share()
