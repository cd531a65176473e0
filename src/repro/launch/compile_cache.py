"""Where JAX's persistent compilation cache lives, for the entry points.

The launchers (``launch/train.py``, ``launch/serve.py``), the benchmark
driver and ``chip_smoke.py`` call ``use_compile_cache()`` once before their
first compile; library code and tests never do. The cache's path is part of
its key, so it must not move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; nothing else is
    set here.
  * unset — ``<checkout>/.jax_cache`` (ignored by git).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
