"""Share of the traced window's device time that FlashSFA's block-skip
pre-pass (the ``sfa.block_maps`` scope, its recomputation in the backward
pass included) takes: over the device time of all ops, mean over the
cell's chips (``benchlib/scopes.py``). None where the program names no
scope."""
from benchlib import scopes


def read(r):
    red = scopes.of(r)
    return red.share(red.scope_s.get("sfa.block_maps", 0.0))
