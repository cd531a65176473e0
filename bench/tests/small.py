"""Cells of the benchmark cut to a size a CPU test run can hold: the same
files, with the widths, depth, vocabulary and traffic scaled down."""
from __future__ import annotations

import argparse

from benchlib import registry

SMALL_MODEL = dict(num_layers=2, d_model=64, d_ff=128, vocab_size=256,
                   head_dim=32, sfa_k=4, max_seq_len=512)


def layout_tool():
    return registry.load_module(registry.BENCH / "tools" / "layout.py",
                                "tool_layout")


def cell(config: str, traffic: str):
    """``configs/<config>.json`` under ``traffic/<traffic>.json``, cut down,
    with the layout that ``tools/layout.py`` gives the cut;
    ``BENCHMARK.json`` need not name the pair."""
    c = registry.Cell(
        name=f"{config}.{traffic}", chips=1,
        config=registry.load_json(registry.BENCH / "configs" / f"{config}.json"),
        layout=[], traffic=mix(traffic), end_to_end=[], per_layer=[])
    m = c.config["model"]
    m.update(SMALL_MODEL, num_heads=2, num_kv_heads=2, pos_rows=512)
    c.layout = layout_tool().leaves(registry.model_config(m))
    return c


def mix(traffic: str) -> dict:
    """``traffic/<traffic>.json`` at batch 2 x 128."""
    out = registry.load_json(registry.traffic_file(traffic))
    out.update(batch=2, seq_len=128, batches=4)
    out["check"]["q_block"] = 64
    return out


def args(seed=2 ** 33 + 5, seconds=3, trace=0):
    return argparse.Namespace(workload=None, seed=seed, seconds=seconds,
                              trace=trace)
