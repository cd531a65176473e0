"""Shared runtime switch for the Pallas kernels.

``resolve_interpret`` is the one switch behind every kernel's ``interpret``
default: kernels declare ``interpret: bool | None = None`` and resolve it
here, so TPU runs never need per-call overrides and CPU CI keeps running the
kernels in interpret mode. The ``REPRO_PALLAS_INTERPRET`` env var (``1``/
``0``, ``true``/``false``, ``on``/``off``) forces either mode; unset/``auto``
means "interpret everywhere except on a real TPU backend". The env var is
read at trace time — set it before the first kernel call (jit caches traces).
"""
from __future__ import annotations

import os

import jax

INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"

_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve a kernel's ``interpret`` argument (None = module default)."""
    if interpret is not None:
        return interpret
    env = os.environ.get(INTERPRET_ENV, "auto").strip().lower()
    if env in _TRUTHY:
        return True
    if env in _FALSY:
        return False
    return jax.default_backend() != "tpu"
