"""Roofline share of the ``sfa_fwd`` operation: its least time on the chip
(``ops/sfa_fwd.py``'s work, summed over the traced window) over the summed
device time of its kernels' events in the trace (the same file's
``NAMES``)."""


def read(r):
    return r.roofline("sfa_fwd")
