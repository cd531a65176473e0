"""Benchmark driver — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Prints ``name,us_per_call,derived`` CSV. Mapping to the paper:
    bench_attention  -> Figure 3/4, Table 9 (latency vs k, d, n)
    bench_kv_cache   -> Figure 5, Appendix J (cache bytes, decode roofline)
    bench_flops      -> Table 6 (op counts dense vs SFA)
    bench_topk       -> Table 8 (RTopK overhead share)
    bench_pretrain   -> Table 1 (dense vs short-embedding vs SFA parity)
    bench_niah       -> Table 2 / Appendix K (NIAH accuracy & generalization)
    bench_serving    -> beyond-paper: paged-KV serving engine vs slot engine
                        (Poisson traffic, same byte budget)
    bench_ring       -> beyond-paper: Ring-SFA code-payload context
                        parallelism — realized collective-permute bytes vs
                        the analytic per-hop model (needs multi-device:
                        XLA_FLAGS=--xla_force_host_platform_device_count=8)
    bench_memory     -> beyond-paper: compiled peak activation bytes + max
                        trainable n per remat policy (core/remat.py's
                        save-codes-not-dense-activations deliverable)

The attention, serving and ring suites additionally append a snapshot (rows
with their analytic byte models / deterministic scheduling metrics, git SHA,
UTC timestamp) to ``BENCH_<suite>.json`` at the repo root, so the perf
trajectory accumulates run over run instead of scrolling away in CI logs.
A suite that produces no rows (e.g. ring on a single device) appends
nothing — an empty entry must never become the gating baseline.
"""
from __future__ import annotations

import argparse
import datetime
import json
import pathlib
import subprocess
import sys
import time

from benchmarks import (bench_attention, bench_kv_cache, bench_flops,
                        bench_topk, bench_pretrain, bench_niah,
                        bench_serving, bench_ring, bench_memory)
from repro.launch.compile_cache import use_compile_cache

SUITES = {
    "attention": bench_attention,
    "kv_cache": bench_kv_cache,
    "flops": bench_flops,
    "topk": bench_topk,
    "pretrain": bench_pretrain,
    "niah": bench_niah,
    "serving": bench_serving,
    "ring": bench_ring,
    "memory": bench_memory,
}

SNAPSHOT_SUITES = ("attention", "serving", "ring", "memory")


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=pathlib.Path(__file__).resolve().parent, check=True,
        ).stdout.strip()
    except Exception:                                  # noqa: BLE001
        return "unknown"


def write_snapshot(suite: str, rows, *, full: bool,
                   path: pathlib.Path | None = None) -> pathlib.Path:
    """Append one benchmark run to the suite's JSON trajectory file.

    Each entry is self-describing: git SHA, UTC timestamp, sweep mode, and
    the raw rows (the ``derived`` field carries the analytic byte models
    alongside the measured microseconds)."""
    if path is None:
        path = (pathlib.Path(__file__).resolve().parent.parent
                / f"BENCH_{suite}.json")
    try:
        history = json.loads(path.read_text()) if path.exists() else []
    except (json.JSONDecodeError, OSError) as e:
        # a killed run must not poison every future run: start fresh
        print(f"# {path.name} unreadable ({e}); starting a new trajectory",
              file=sys.stderr, flush=True)
        history = []
    history.append({
        "git_sha": _git_sha(),
        "timestamp": datetime.datetime.now(datetime.timezone.utc)
                     .isoformat(timespec="seconds"),
        "mode": "full" if full else "quick",
        "rows": [{"name": r[0], "us_per_call": round(float(r[1]), 1),
                  "derived": r[2]} for r in rows],
    })
    tmp = path.with_suffix(".json.tmp")               # atomic replace
    tmp.write_text(json.dumps(history, indent=1) + "\n")
    tmp.replace(path)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full sweeps (default: quick)")
    ap.add_argument("--only", default=None, choices=list(SUITES))
    ap.add_argument("--no-snapshot", action="store_true",
                    help="skip appending to BENCH_<suite>.json")
    args = ap.parse_args()

    use_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in SUITES.items():
        if args.only and name != args.only:
            continue
        t0 = time.monotonic()
        try:
            rows = mod.run(quick=not args.full)
            for r in rows:
                print(f"{r[0]},{r[1]:.1f},{r[2]}", flush=True)
            if name in SNAPSHOT_SUITES and rows and not args.no_snapshot:
                path = write_snapshot(name, rows, full=args.full)
                print(f"# snapshot appended to {path.name}",
                      file=sys.stderr, flush=True)
        except Exception as e:                         # noqa: BLE001
            failures += 1
            print(f"{name},nan,ERROR:{type(e).__name__}:{e}", flush=True)
        print(f"# {name} done in {time.monotonic() - t0:.0f}s",
              file=sys.stderr, flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
