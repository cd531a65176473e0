"""Finds a cell's files by the names in ``BENCHMARK.json``.

``configs/<config>.json``, ``traffic/<traffic>.json``,
``reference/<reference>.py`` and ``metrics/<metric>.py``: a later change
adds a cell or a metric by adding files and entries, never by editing one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


class BenchError(Exception):
    """A run that cannot be measured: it exits non-zero with no result."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.exists():
        raise BenchError(f"{path} is missing")
    return load_json(path)


def config_file(bench: dict, name: str) -> Path:
    for c in bench["configs"]:
        if c["name"] == name:
            return ROOT / c["file"]
    raise BenchError(f"no configuration named {name!r}")


def traffic_file(name: str) -> Path:
    for suffix in DATA_SUFFIXES:
        p = BENCH / "traffic" / f"{name}{suffix}"
        if p.exists():
            return p
    raise BenchError(f"no traffic file for {name!r} under {BENCH / 'traffic'}")


def load_module(path: Path, name: str):
    if not path.exists():
        raise BenchError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(name: str):
    return load_module(BENCH / "reference" / f"{name}.py", f"ref_{name}")


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py",
                       "metric_" + name.replace(".", "_").replace("-", "_"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def cell(name: str) -> Cell:
    bench = benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise BenchError(f"no workload named {name!r} in BENCHMARK.json")

    def applies(metric):
        return name in metric.get("workloads", [name])

    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(config_file(bench, w["config"])),
        traffic=load_json(traffic_file(w["traffic"])),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def model_config(model: dict):
    """The system's ``ModelConfig`` for a configuration's ``model`` block."""
    from repro.configs import get_config

    check_norm_eps(model)
    base = get_config(model["arch"])
    att = dataclasses.replace(
        base.attention, num_heads=model["num_heads"],
        num_kv_heads=model["num_kv_heads"], head_dim=model["head_dim"],
        sfa_k=model["sfa_k"], rope=model["rope"],
        rope_theta=float(model.get("rope_theta", base.attention.rope_theta)),
        qk_norm=model["qk_norm"], window=None, sfa_rope_protect=0)
    return dataclasses.replace(
        base, num_layers=model["num_layers"], d_model=model["d_model"],
        d_ff=model["d_ff"], vocab_size=model["vocab_size"],
        max_seq_len=model["max_seq_len"], norm=model["norm"],
        act=model["act"], glu=model["glu"],
        tie_embeddings=model["tie_embeddings"],
        pos_embedding=model["pos_embedding"], dtype=model["dtype"],
        attention=att)


def check_layout(cfg, params) -> None:
    """The benchmark's weights must have the tree and shapes that the
    system's own init makes."""
    import jax
    from repro.models import init

    want = jax.eval_shape(lambda k: init(k, cfg), jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    want = jax.tree.map(lambda x: (x.shape, str(x.dtype)), want)
    if got != want:
        raise BenchError(f"weight layout differs from the system's init: "
                         f"{got} != {want}")


def check_norm_eps(model: dict) -> None:
    """The system's norms use eps 1e-6; a configuration stating another
    cannot be run as stated."""
    if abs(model["norm_eps"] - 1e-6) > 1e-12:
        raise BenchError(f"norm_eps {model['norm_eps']} is not the system's")
