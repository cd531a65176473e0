#!/usr/bin/env python3
"""Writes a configuration's weight layout from the system's own init.

    python3 bench/tools/layout.py --config <name | file.json> [--out <file>]

``--config`` is a configuration of ``BENCHMARK.json`` or the path of a
configuration file; the layout goes beside it (``<config>.layout.json``)
unless ``--out`` says where. The system's ``init`` is traced with
``jax.eval_shape`` for the ``ModelConfig`` that ``registry.model_config``
builds from the file's ``model`` block, so nothing is computed and no chip is
needed. Each leaf is written with its path (dict keys and list indices),
its shape and its role, in the order the tree flattens, which is the order
in which ``benchlib/weights.py`` gives leaves their keys:

- ``norm``: a norm's ``scale`` or ``bias``;
- ``lookup``: a table read by index (the token table ``embed``, the
  position table ``pos``);
- ``experts``: a stack of expert matrices (experts, in, out) in each
  layer; ``router`` names the matrix in the same block whose output width
  is the configuration's expert count, the router that picks among them;
- ``matmul``: every other matrix.

Leaves under ``segments`` carry a leading axis of stacked layers, which a
role does not count.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from benchlib import registry  # noqa: E402


def _key(p):
    return getattr(p, "key", getattr(p, "idx", p))


def leaves(cfg) -> list:
    """The layout of ``cfg`` (a ``ModelConfig``): one dict per leaf."""
    import jax
    from repro.models import init

    shapes = jax.eval_shape(lambda k: init(k, cfg), jax.random.PRNGKey(0))
    flat = [(tuple(_key(p) for p in path), tuple(x.shape))
            for path, x in jax.tree_util.tree_flatten_with_path(shapes)[0]]
    out = []
    for path, shape in flat:
        leaf = {"path": list(path), "shape": list(shape),
                "role": role(path, shape)}
        if leaf["role"] == "experts":
            leaf["router"] = list(router(path, flat, cfg))
        out.append(leaf)
    return out


def role(path: tuple, shape: tuple) -> str:
    if path[-1] in ("scale", "bias"):
        return "norm"
    if path[0] in ("embed", "pos"):
        return "lookup"
    rank = len(shape) - (path[0] == "segments")
    if rank == 2:
        return "matmul"
    if rank == 3:
        return "experts"
    raise registry.BenchError(f"no role for leaf {'/'.join(map(str, path))} "
                              f"of shape {shape}")


def router(path: tuple, flat: list, cfg) -> tuple:
    """The matrix beside an experts leaf whose output width is the
    configuration's expert count."""
    block = path[:-1]
    found = [p for p, s in flat
             if p[:len(block)] == block and p != path
             and role(p, s) == "matmul" and s[-1] == cfg.moe.num_experts]
    if len(found) != 1:
        raise registry.BenchError(
            f"{'/'.join(map(str, path))}: {len(found)} matrices of output "
            f"width {cfg.moe.num_experts} beside it, where one router is "
            f"needed")
    return found[0]


def write(config: dict, out: Path) -> list:
    layout = leaves(registry.model_config(config["model"]))
    about = (f"the weight leaves of {config['name']} as the system's init "
             f"makes them, in flattening order; bench/tools/layout.py "
             f"writes this file")
    lines = ",\n".join("  " + json.dumps(leaf) for leaf in layout)
    out.write_text(f'{{\n"about": {json.dumps(about)},\n'
                   f'"config": {json.dumps(config["name"])},\n'
                   f'"leaves": [\n{lines}\n]\n}}\n')
    return layout


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True,
                    help="a configuration of BENCHMARK.json, or its file")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    path = Path(args.config)
    if path.suffix != ".json":
        path = registry.config_file(registry.benchmark(), args.config)
    out = Path(args.out) if args.out else registry.layout_file(path)
    layout = write(registry.load_json(path), out)
    print(f"{out}: {len(layout)} leaves")
    return 0


if __name__ == "__main__":
    sys.exit(main())
