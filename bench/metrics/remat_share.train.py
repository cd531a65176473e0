"""Share of the traced window's device time spent re-running the forward
in the backward pass (ops of a forward scope under ``jax.checkpoint``'s
``rematted_computation``, the ``remat="codes"`` recompute): over the device
time of all ops, mean over the cell's chips (``benchlib/scopes.py``).
None where the program names no scope."""
from benchlib import scopes


def read(r):
    red = scopes.of(r)
    return red.share(sum(red.recomputed_s.values()))
