#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/tools/calibrate.py --workload <name> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--seconds 20] [--out <file.jsonl>]

For every seed, the program's own readings against the reference, as a
run of the training cell gives them (its first steps). For the control
seeds, also the control's: the reference in the program's place at the
precisions below the stated one (int8 and float8 operands on every matrix
product, ``CONTROLS``), and the fault of half the batch left out (the
reference, fed the first half of each batch's rows, in the program's
place). Each set of gaps carries the verdict that the mix's limits give
it. One process holds the chip for all seeds. Each seed's readings are one
JSON line in ``--out``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run as bench_run  # noqa: E402
from benchlib import registry, train_cell, traffic, weights  # noqa: E402

CONTROLS = ("int8", "fp8", "fp8_hybrid")


def half_batch(cell, seed):
    """The reference's readings with each batch cut to its first half."""
    mix = cell.traffic
    m = cell.config["model"]
    ref = registry.reference(cell.config["reference"])
    steps = int(mix["check"]["steps"])
    b, n = int(mix["batch"]), int(mix["seq_len"])
    batches = traffic.markov_batches(seed, int(mix["batches"]), b, n,
                                     registry.model_config(m).vocab_size
                                     )[:steps]
    p0 = weights.make(seed, cell.layout)
    losses, g1, p = ref.train_steps(
        p0, m, [(x["tokens"][:b // 2], x["labels"][:b // 2])
                for x in batches], dict(mix["optimizer"]),
        q_block=int(mix["check"]["q_block"]))
    return (losses, [float(x) for x in train_cell._leaf_norms(g1)],
            [float(x) for x in train_cell._change_norms(p, p0)])


def verdict(cell, g):
    """The gaps with the verdict that the mix's limits give them."""
    lim = cell.traffic["limits"]
    checks = {k: {"value": g[k], "limit": lim[k]} for k in lim}
    return {**g, "correct": bench_run.judge(checks)}


def train_seed(cell, ctx, control):
    out, prog = train_cell.train(cell, ctx)
    ref = train_cell.reference_readings(cell, ctx.seed)
    row = {"program": verdict(cell, train_cell.gaps(prog, ref)),
           "host": out["host"],
           "leaves": {"program": train_cell.leaf_gaps(prog, ref)}}
    if control:
        for mode in CONTROLS:
            ctl = train_cell.reference_readings(cell, ctx.seed, mode=mode)
            row[f"control_{mode}"] = verdict(cell, train_cell.gaps(ctl, ref))
            row["leaves"][f"control_{mode}"] = train_cell.leaf_gaps(ctl, ref)
        row["half_batch"] = verdict(
            cell, train_cell.gaps(half_batch(cell, ctx.seed), ref))
    return row


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=int, default=20)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    cell = registry.cell(args.workload)
    devices, peaks = bench_run.check_device(cell.chips)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    out = open(args.out, "a") if args.out else None
    for seed in seeds:
        ns = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0)
        ctx = bench_run.Context(ns, devices, peaks, args.seconds)
        row = {"workload": args.workload, "seed": seed,
               **train_seed(cell, ctx, seed in controls)}
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()


if __name__ == "__main__":
    main()
