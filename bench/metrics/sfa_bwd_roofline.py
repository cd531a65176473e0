"""Roofline share of the ``sfa_bwd`` operation: its least time on the chip
(``ops/sfa_bwd.py``'s work, summed over the traced window) over the summed
device time of its kernels' events in the trace (the same file's
``NAMES``)."""


def read(r):
    return r.roofline("sfa_bwd")
