"""Random weights from the seed, made on the device in one jitted call.

The tree has the layout the system under test reads (a stacked per-layer
dict); ``registry.check_layout`` compares it with the program's own init
shapes before a run. The scales follow the usual fan-in rule: matrices
N(0, 1/fan_in), the token table N(0, 0.02^2), the position table
N(0, 0.01^2), norm scales 1 and biases 0. Parameters are float32, as the
system stores them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def layout(m: dict) -> dict:
    """Shapes of every leaf, in the system's tree layout."""
    d, L = m["d_model"], m["num_layers"]
    h, hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]

    def norm(dim, lead=()):
        out = {"scale": lead + (dim,)}
        if m["norm"] == "layernorm":
            out["bias"] = lead + (dim,)
        return out

    attn = {"w_qkv": {"w": (L, d, (h + 2 * hkv) * hd)},
            "w_o": {"w": (L, h * hd, d)}}
    if m["qk_norm"]:
        attn["q_norm"] = {"scale": (L, hd)}
        attn["k_norm"] = {"scale": (L, hd)}
    if m["glu"]:
        mlp = {"up_gate": {"w": (L, d, 2 * m["d_ff"])},
               "down": {"w": (L, m["d_ff"], d)}}
    else:
        mlp = {"up": {"w": (L, d, m["d_ff"])},
               "down": {"w": (L, m["d_ff"], d)}}
    tree = {"embed": {"w": (m["vocab_size"], d)},
            "final_norm": norm(d),
            "segments": [{"ln1": norm(d, (L,)), "attn": attn,
                          "ln2": norm(d, (L,)), "mlp": mlp}]}
    if m["pos_embedding"] == "learned":
        tree["pos"] = {"w": (m["pos_rows"], d)}
    return tree


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


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, m_items):
    m = dict(m_items)
    shapes = layout(m)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        leaves.append(_leaf(jax.random.fold_in(key, i), name, shape))
    return jax.tree_util.tree_unflatten(tree, leaves)


def make(seed: int, m: dict):
    """The weights of ``m`` (a configuration's ``model`` block) for ``seed``."""
    return _make(jax.random.fold_in(seed_key(seed), 1),
                 tuple(sorted(m.items())))
