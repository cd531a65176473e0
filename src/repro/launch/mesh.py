"""Production meshes. 16×16 (data, model) per pod; 2×16×16 multi-pod.

A FUNCTION (not a module-level constant) so importing never touches jax
device state — the dry-run must set XLA_FLAGS before first jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    # Auto axes: the model code shards through with_sharding_constraint and
    # shard_map, which Explicit axes (make_mesh's default) reject.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(model: int = 1, data: int | None = None, seq: int = 1,
                    devices=None):
    """Small mesh over ``devices`` (default: every visible device).

    ``seq > 1`` inserts a "seq" axis between data and model for ring-SFA
    context parallelism (distributed/ring.py); the 2D shape is kept when
    ``seq == 1`` so existing (data, model) specs are unchanged."""
    devices = list(jax.devices() if devices is None else devices)
    data = data or (len(devices) // (model * seq))
    devices = devices[:data * seq * model]
    if seq > 1:
        return _mesh((data, seq, model), ("data", "seq", "model"), devices)
    return _mesh((data, model), ("data", "model"), devices)
