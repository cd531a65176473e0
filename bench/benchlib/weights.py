"""Random weights from the seed, made on the device in one jitted call.

The tree is the configuration's weight layout (``configs/<config>.layout
.json``, written from the system's own init by ``tools/layout.py``): each
leaf's path and shape, in the order the system's tree flattens, which is the
order in which leaves take their keys. ``registry.check_layout`` compares
the tree with the program's own init shapes before a run. The scales follow
the usual fan-in rule: matrices N(0, 1/fan_in), the token table
N(0, 0.02^2), the position table N(0, 0.01^2), norm scales 1 and biases 0.
Parameters are float32, as the system stores them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _leaf(key, path: str, shape):
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        return jnp.ones(shape, jnp.float32)
    if name == "bias":
        return jnp.zeros(shape, jnp.float32)
    if path.startswith("embed"):
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if path.startswith("pos"):
        return 0.01 * jax.random.normal(key, shape, jnp.float32)
    fan_in = shape[-2]
    return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (64 bits are used)."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def nest(paths, values):
    """The tree whose leaf at each path (a tuple of dict keys and list
    indices) is the value at the same place in ``values``."""
    root: dict = {}
    for path, value in zip(paths, values):
        node = root
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = value

    def lists(node):
        if not isinstance(node, dict):
            return node
        if node and all(isinstance(k, int) for k in node):
            return [lists(node[i]) for i in range(len(node))]
        return {k: lists(v) for k, v in node.items()}

    return lists(root)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, spec):
    leaves = [_leaf(jax.random.fold_in(key, i), "/".join(map(str, path)),
                    shape) for i, (path, shape) in enumerate(spec)]
    return nest([path for path, _ in spec], leaves)


def make(seed: int, layout: list):
    """The weights of a configuration's ``layout`` (its list of leaves)
    for ``seed``."""
    spec = tuple((tuple(leaf["path"]), tuple(leaf["shape"]))
                 for leaf in layout)
    return _make(jax.random.fold_in(seed_key(seed), 1), spec)
