"""The weight layout as data (``configs/<config>.layout.json``, written by
``tools/layout.py``): each file is what the system's init makes, and the
weights made from it are those that the hand-written GPT-2 tree gave."""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import small
from benchlib import registry, weights

BENCH = registry.benchmark()


# -- the GPT-2 tree and weights as the benchmark wrote them by hand, frozen --

def old_layout(m: dict) -> dict:
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


def old_leaves(m: dict) -> list:
    flat = jax.tree_util.tree_flatten_with_path(
        old_layout(m), is_leaf=lambda x: isinstance(x, tuple))[0]
    return [(tuple(getattr(p, "key", getattr(p, "idx", p)) for p in path),
             shape) for path, shape in flat]


def old_leaf(key, path: str, shape):
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


@functools.partial(jax.jit, static_argnums=(1,))
def old_make_jit(key, m_items):
    m = dict(m_items)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        old_layout(m), is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        leaves.append(old_leaf(jax.random.fold_in(key, i), name, shape))
    return jax.tree_util.tree_unflatten(tree, leaves)


def old_make(seed: int, m: dict):
    return old_make_jit(jax.random.fold_in(weights.seed_key(seed), 1),
                        tuple(sorted(m.items())))


# ---------------------------------------------------------------------------

def test_gpt2_layout_is_the_old_tree():
    path = registry.config_file(BENCH, "gpt2-small-sfa8")
    m = registry.load_json(path)["model"]
    got = [(tuple(leaf["path"]), tuple(leaf["shape"]))
           for leaf in registry.layout(path)]
    assert got == old_leaves(m)


@pytest.mark.parametrize("seed", [0, 2 ** 33 + 5, 2 ** 31 + 7])
def test_small_weights_are_the_old_weights(seed):
    """The small cut's weights, from its tool-written layout, are the old
    ``make``'s to the bit."""
    cell = small.cell("gpt2-small-sfa8", "train-b4-n4096")
    new = weights.make(seed, cell.layout)
    old = old_make(seed, cell.config["model"])
    assert jax.tree.structure(new) == jax.tree.structure(old)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_layout_file_is_the_systems(config):
    """Each configuration's layout file is what the tool writes from the
    system's init today, and its weights pass ``check_layout``."""
    path = registry.config_file(BENCH, config)
    cfg = registry.model_config(registry.load_json(path)["model"])
    layout = registry.layout(path)
    assert small.layout_tool().leaves(cfg) == layout
    shapes = jax.eval_shape(lambda: weights.make(1, layout))
    registry.check_layout(cfg, shapes)


def test_tool_writes_the_file(tmp_path):
    path = registry.config_file(BENCH, "gpt2-small-sfa8")
    out = tmp_path / "layout.json"
    assert small.layout_tool().main(["--config", "gpt2-small-sfa8",
                                     "--out", str(out)]) == 0
    assert json.loads(out.read_text()) == registry.load_json(
        registry.layout_file(path))


def test_missing_layout_fails(tmp_path):
    config = tmp_path / "c.json"
    config.write_text("{}")
    with pytest.raises(registry.BenchError, match="layout.py"):
        registry.layout(config)


def test_leaf_rules():
    layout = [{"path": ["embed", "w"], "shape": [64, 8], "role": "lookup"},
              {"path": ["n", "scale"], "shape": [8], "role": "norm"},
              {"path": ["n", "bias"], "shape": [8], "role": "norm"},
              {"path": ["pos", "w"], "shape": [64, 8], "role": "lookup"},
              {"path": ["segments", 0, "w"], "shape": [2, 400, 300],
               "role": "matmul"}]
    p = weights.make(3, layout)
    assert float(jnp.std(p["embed"]["w"])) == pytest.approx(0.02, rel=0.2)
    assert float(jnp.std(p["pos"]["w"])) == pytest.approx(0.01, rel=0.2)
    assert np.all(np.asarray(p["n"]["scale"]) == 1)
    assert np.all(np.asarray(p["n"]["bias"]) == 0)
    w = p["segments"][0]["w"]
    assert float(jnp.std(w)) == pytest.approx(400 ** -0.5, rel=0.05)
