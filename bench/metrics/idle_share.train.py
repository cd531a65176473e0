"""Share of the traced window in which no operation ran on the device:
1 - the union of device-op intervals over the window, as the mean over
the cell's chips."""


def read(r):
    return 100.0 * r.trace.idle_share
