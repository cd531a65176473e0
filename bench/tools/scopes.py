#!/usr/bin/env python3
"""Device time of a traced run by the program's layer names.

    python3 bench/tools/scopes.py [<trace_dir>]      # default .bench_trace

Reads the newest ``.xplane.pb`` under the directory (a ``--trace 1`` run of
``bench/run.py`` leaves it in ``.bench_trace``) and prints, over the
``bench.window`` span: the device seconds of each scope of
``src/repro/core/scopes.py`` with their recomputed part (``jax.checkpoint``
re-running the forward in the backward pass), the busy and idle time, and
the longest idle gaps by the host span that covers them (``bench.*`` or
``engine.*``). Seconds are the mean over the trace's devices.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib import registry, scopes, trace  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir", nargs="?", default=str(scopes.TRACE_DIR))
    ap.add_argument("--gaps", type=int, default=10,
                    help="idle gaps to list")
    args = ap.parse_args(argv)
    record = scopes.load(args.trace_dir)
    red = scopes.reduce(record)
    kernels = registry.load_json(BENCH / "kernels.json")["operations"]
    busy = trace.reduce(record, kernels, top=args.gaps)
    print(f"{'scope':16s} {'device s':>10s} {'recomputed s':>13s} "
          f"{'share %':>8s}")
    for scope, sec in sorted(red.scope_s.items(), key=lambda kv: -kv[1]):
        print(f"{scope or '(none)':16s} {sec:10.4f} "
              f"{red.recomputed_s.get(scope, 0.0):13.4f} "
              f"{100 * sec / red.total_s:8.2f}")
    redone = sum(red.recomputed_s.values())
    print(f"{'all ops':16s} {red.total_s:10.4f} {redone:13.4f} "
          f"{100.0:8.2f}")
    print(f"window {busy.window_s:.4f} s, busy {busy.busy_s:.4f} s, "
          f"idle {100 * busy.idle_share:.4f}%")
    print("longest idle gaps (covering host span, seconds):")
    for span, sec in busy.idle_gaps:
        print(f"  {span:24s} {sec:.9f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
