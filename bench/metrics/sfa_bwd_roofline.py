"""Roofline share of the ``sfa_bwd`` operation: its least time on the chip
(``benchlib/work.py``, summed over the traced window) over the summed device
time of its kernels' events in the trace (``kernels.json``)."""


def read(r):
    return r.roofline("sfa_bwd")
