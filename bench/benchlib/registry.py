"""Finds a cell's files by the names in ``BENCHMARK.json``.

``configs/<config>.json`` with its weight layout beside it
(``configs/<config>.layout.json``, written by ``tools/layout.py``),
``traffic/<traffic>.json``, ``reference/<reference>.py``,
``metrics/<metric>.py`` and the operations ``ops/<op>.py``: a later change
adds a configuration, a cell, a metric or an operation by adding files and
entries, never by editing one.
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


def layout_file(config: Path) -> Path:
    """Where the weight layout of the configuration in ``config`` lies."""
    return config.with_name(config.stem + ".layout.json")


def layout(config: Path) -> list:
    """The weight leaves of the configuration in ``config``."""
    path = layout_file(config)
    if not path.exists():
        raise BenchError(f"{path} is missing: bench/tools/layout.py writes it")
    return load_json(path)["leaves"]


def reference(name: str):
    return load_module(BENCH / "reference" / f"{name}.py", f"ref_{name}")


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py",
                       "metric_" + name.replace(".", "_").replace("-", "_"))


def ops() -> dict:
    """Every operation of ``ops/``: name -> module with ``NAMES`` (the
    trace names of its kernels, regular expressions) and ``work(cfg,
    mix)`` (its ``work.Work`` in one step, or None)."""
    return {p.stem: load_module(p, "op_" + p.stem)
            for p in sorted((BENCH / "ops").glob("*.py"))}


def kernel_map() -> dict:
    """Operation -> the trace names of its kernels."""
    return {name: list(op.NAMES) for name, op in ops().items()}


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    layout: list         # the weight leaves (``tools/layout.py``)
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

    path = config_file(bench, w["config"])
    return Cell(
        name=name, chips=int(w["chips"]), config=load_json(path),
        layout=layout(path),
        traffic=load_json(traffic_file(w["traffic"])),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


# keys of a ``model`` block that describe the configuration without naming
# a field of the system's config: the preset it starts from, the norm
# epsilon it states (``check_norm_eps``) and the rows of its position table
DESCRIPTIVE = ("arch", "norm_eps", "pos_rows")


def model_config(model: dict):
    """The system's ``ModelConfig`` for a configuration's ``model`` block.

    The block's ``arch`` preset is the start. Every other key names a field
    of ``ModelConfig`` or of ``AttentionConfig`` (both, where both have it)
    and sets it; a nested block (``mla``, ``moe``) becomes the field's own
    config class, field by field. ``window`` and ``sfa_rope_protect`` are
    None and 0 unless the block states them. A key that names no field and
    is not one of ``DESCRIPTIVE`` cannot be run as stated."""
    from repro.configs import get_config

    check_norm_eps(model)
    base = get_config(model["arch"])
    top: dict = {}
    att: dict = {"window": None, "sfa_rope_protect": 0}
    for key, value in model.items():
        if key in DESCRIPTIVE:
            continue
        named = False
        for obj, out in ((base, top), (base.attention, att)):
            if obj is not None and key in _field_names(obj):
                out[key] = _field_value(obj, key, value)
                named = True
        if not named:
            raise BenchError(f"model key {key!r} names no field of the "
                             f"system's ModelConfig or AttentionConfig")
    attention = top.pop("attention", base.attention)
    if attention is not None:
        top["attention"] = dataclasses.replace(attention, **att)
    return dataclasses.replace(base, **top)


def _field_names(obj) -> set:
    return {f.name for f in dataclasses.fields(obj)}


def _field_value(obj, key: str, value):
    """``value`` for the field ``key`` of ``obj``: a nested block becomes the
    config class that the field holds, from the field's value where it has
    one, else from the class's defaults."""
    if not isinstance(value, dict):
        return value
    import typing

    hint = typing.get_type_hints(type(obj))[key]
    (cls,) = [t for t in (hint, *typing.get_args(hint))
              if dataclasses.is_dataclass(t)]
    unknown = set(value) - _field_names(cls)
    if unknown:
        raise BenchError(f"model key {key!r}: {sorted(unknown)} name no "
                         f"field of {cls.__name__}")
    current = getattr(obj, key)
    try:
        return (dataclasses.replace(current, **value) if current is not None
                else cls(**value))
    except TypeError as e:
        raise BenchError(f"model key {key!r}: {e}") from None


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
