#!/usr/bin/env python3
"""Benchmark of SFA training on the chip, one cell per run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads`` entry) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the mix's ``kind`` picks the driver.
With ``--trace 0`` the last line of standard output is the JSON result with
the cell's end-to-end metrics; with ``--trace 1`` it carries the per-layer
metrics, read from the profiler's trace of the window by the readers in
``bench/metrics/<metric>.py``. Each run ends by comparing what the timed
path produced with the plain reference (``bench/reference``); the numbers
compared and their limits are the last lines of standard error and the
``checks`` entry of the result.

A run that finds no TPU, fewer chips than the cell asks for, a device kind
missing from ``bench/peaks.json``, or Pallas forced into interpret mode
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from benchlib import registry  # noqa: E402
from benchlib.registry import BenchError  # noqa: E402

TRACE_DIR = BENCH.parent / ".bench_trace"


def check_device(chips: int, *, need_tpu: bool = True):
    """The devices of the cell and the peaks of their kind."""
    import jax

    if need_tpu and jax.default_backend() != "tpu":
        raise BenchError(f"JAX found no TPU (backend "
                         f"{jax.default_backend()!r})")
    if need_tpu:
        from repro.kernels._compat import resolve_interpret
        if resolve_interpret(None):
            raise BenchError("Pallas is forced into interpret mode")
    devices = jax.devices()
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    peaks = registry.load_json(BENCH / "peaks.json")["devices"]
    kind = devices[0].device_kind
    if need_tpu and kind not in peaks:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return devices[:chips], peaks.get(kind)


class Context:
    """What a driver gets: the run's arguments, its devices, and the hooks
    for the set-up clock, the profiler, the memory reading and freeing."""

    def __init__(self, args, devices, peaks, trace_seconds):
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.trace_seconds = min(trace_seconds, args.seconds)
        self.devices = devices
        self.peaks = peaks
        self.setup_s = None

    def setup_done(self):
        self.setup_s = time.perf_counter() - T_START

    def start_trace(self):
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
        return span

    def stop_trace(self, span):
        import jax

        span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def memory_peak(self):
        return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devices)

    def free(self):
        gc.collect()


def drivers():
    from benchlib import train_cell
    return {"train": train_cell.run}


def metrics_of(cell, ctx, out):
    """The result's metrics, device readings and breakdown."""
    host = out["host"]
    if not ctx.trace:
        metrics = {}
        for m in cell.end_to_end:
            value = ctx.setup_s if m["name"] == "setup_s" else host[m["name"]]
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        return metrics, None, None
    from benchlib import trace as tr

    red = tr.reduce(tr.load_xplane(str(TRACE_DIR)))
    view = Reading(red, out.get("work") or {}, host, ctx)
    metrics = {}
    for m in cell.per_layer:
        value = registry.metric_reader(m["name"]).read(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"busy_s": red.busy_s, "window_s": red.window_s}
    breakdown = {"device_ops": red.top_ops, "idle_gaps": red.idle_gaps}
    return metrics, device, breakdown


class Reading:
    """What a per-layer reader sees: the trace reduction, the operations'
    work over the traced window, the host numbers, the peaks."""

    def __init__(self, red, work, host, ctx):
        self.trace = red
        self.work = work
        self.host = host
        self.peaks = ctx.peaks
        self.chips = len(ctx.devices)

    def roofline(self, op):
        """Share (%) of an operation's device time that its roofline
        time is, or None where the trace holds none of its kernels."""
        w = self.work.get(op)
        t = self.trace.op_seconds.get(op)
        if w is None or not t or w.flops == 0:
            return None
        return 100.0 * w.roofline_s(self.peaks["bf16_flops"],
                                    self.peaks["hbm_bytes_per_s"]) / t


def judge(checks: dict) -> bool:
    """Every number with a limit within it, and at least one such number."""
    limited = [v for v in checks.values() if v.get("limit") is not None]
    ok = bool(limited) and all(v["value"] <= v["limit"] for v in limited)
    return ok and all(v["value"] >= v["min"] for v in checks.values()
                      if "min" in v)


def run(args, *, need_tpu=True, cell=None) -> dict:
    cell = cell or registry.cell(args.workload)
    devices, peaks = check_device(cell.chips, need_tpu=need_tpu)
    if need_tpu:
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
    kind = cell.traffic["kind"]
    if kind not in drivers():
        raise BenchError(f"no driver for traffic kind {kind!r}")
    ctx = Context(args, devices, peaks,
                  float(cell.traffic.get("trace_seconds", args.seconds)))
    out = drivers()[kind](cell, ctx)
    metrics, device, breakdown = metrics_of(cell, ctx, out)
    dev = devices[0]
    result = {
        "correct": judge(out["checks"]), "attempted": out["attempted"],
        "failed": out["failed"], "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": out["memory"]},
        "host": {k: v for k, v in out["host"].items()
                 if isinstance(v, (int, float)) or v is None},
    }
    if device is not None:
        result["device"].update(device)
        result["breakdown"] = breakdown
    result["checks"] = out["checks"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    try:
        result = run(args)
    except (BenchError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 1
    for name, v in result["checks"].items():
        bound = (f"limit {v['limit']}" if "limit" in v
                 else f"at least {v['min']}")
        print(f"check {name}: {v['value']} ({bound})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
