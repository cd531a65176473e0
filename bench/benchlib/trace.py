"""Reduction from a profiler trace to device metrics.

``load_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain record: the operations that ran on each device and the benchmark's
own host spans (``bench.*`` ``TraceAnnotation``s), each as
``[name, start_ns, duration_ns]`` on the profiler's common clock. Every
number below is computed from that record alone, so the reduction can be
checked on a recorded trace without a chip (``tests/data``).
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# the TPU trace names an operation by its whole HLO instruction text,
# "%flash_sfa.19 = (bf16[...]) custom-call(...)"; the name is what precedes
# " = "
OP_NAME = re.compile(r"^%?([^\s=]+)")
# control flow: its events span the whole of its body, idle time included,
# so they are left out of every sum; the body's operations are events of
# their own
CONTROL = re.compile(r"^(while|conditional|call)(\.|$)")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def load_xplane(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as a plain record."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    devices: dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices[m.group(1)] = [[op_name(e.name), e.start_ns,
                                        e.duration_ns] for e in line.events]
            elif not m:
                spans.extend([e.name, e.start_ns, e.duration_ns]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def op_name(text: str) -> str:
    m = OP_NAME.match(text)
    return m.group(1) if m else text


def merge(intervals) -> list:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out = []
    b = merge(b)
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append([cur, bs])
            cur = max(cur, be)
        if cur < e:
            out.append([cur, e])
    return out


@dataclass
class Reduction:
    """Device numbers of one traced window (seconds unless said)."""
    window_s: float
    busy_s: float                      # mean over devices
    busy_per_device: dict
    op_seconds: dict                   # operation -> summed device time
    op_events: dict                    # operation -> event count
    collective_s: float                # mean over devices
    exposed_collective_s: float        # collective time with no compute
    top_ops: list = field(default_factory=list)
    idle_gaps: list = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def kernel_op(name: str, kernel_map: dict) -> str | None:
    """The operation a device event belongs to, by the kernel-name map
    (operation -> list of name patterns; ``registry.kernel_map``, the
    ``NAMES`` of each ``ops/<op>.py``)."""
    for op, patterns in kernel_map.items():
        if any(re.search(p, name) for p in patterns):
            return op
    return None


def reduce(record: dict, kernel_map: dict | None = None, *,
           collective=r"collective-permute|all-reduce|all-gather|"
                      r"reduce-scatter|all-to-all",
           top: int = 10) -> Reduction:
    """Reduce a trace record over its window: the ``bench.window`` span
    when there is one, else the span of all device events. Kernels are
    found by ``kernel_map``, by default every operation of ``ops/``."""
    if kernel_map is None:
        from benchlib import registry
        kernel_map = registry.kernel_map()
    wins = [s for s in record["spans"] if s[0] == WINDOW_SPAN]
    devices = record["devices"]
    if not devices or not any(devices.values()):
        raise ValueError("the trace holds no device operation")
    if wins:
        lo = min(s[1] for s in wins)
        hi = max(s[1] + s[2] for s in wins)
    else:
        lo = min(e[1] for evs in devices.values() for e in evs)
        hi = max(e[1] + e[2] for evs in devices.values() for e in evs)
    window = float(hi - lo)
    is_coll = {}
    busy, coll, exposed = {}, [], []
    by_name: dict = {}            # name -> [seconds, events], all devices
    gaps = []
    spans = sorted(([s[1], s[1] + s[2], s[0]] for s in record["spans"]
                    if s[0] != WINDOW_SPAN), key=lambda s: s[0])
    for dev, evs in sorted(devices.items()):
        ivs, c_ivs = [], []
        for name, start, dur in evs:
            s, e = max(start, lo), min(start + dur, hi)
            if e <= s or CONTROL.match(name):
                continue
            if name not in is_coll:
                is_coll[name] = re.search(collective, name) is not None
            (c_ivs if is_coll[name] else ivs).append([s, e])
            agg = by_name.setdefault(name, [0.0, 0])
            agg[0] += (e - s) / 1e9
            agg[1] += 1
        union = merge(ivs + c_ivs)
        busy[dev] = total(union) / 1e9
        c_union = merge(c_ivs)
        coll.append(total(c_union) / 1e9)
        exposed.append(total(subtract(c_union, ivs)) / 1e9)
        edges = [lo] + [x for iv in union for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps.append([host_activity(spans, gs, ge), (ge - gs) / 1e9])
    n = len(busy)
    op_s: dict = {}
    op_n: dict = {}
    for name, (sec, count) in by_name.items():
        op = kernel_op(name, kernel_map)
        if op is not None:
            op_s[op] = op_s.get(op, 0.0) + sec / n
            op_n[op] = op_n.get(op, 0) + count
    top_ops = sorted(([k, v[0] / n] for k, v in by_name.items()),
                     key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[1])
    return Reduction(
        window_s=window / 1e9, busy_s=sum(busy.values()) / n,
        busy_per_device=busy, op_seconds=op_s, op_events=op_n,
        collective_s=sum(coll) / n, exposed_collective_s=sum(exposed) / n,
        top_ops=top_ops, idle_gaps=gaps[:top])


def host_activity(spans, lo, hi) -> str:
    """The innermost ``bench.*`` span that covers most of ``[lo, hi)``."""
    best, best_cover = "host:none", 0.0
    for s, e, name in spans:
        if s >= hi:
            break
        cover = min(e, hi) - max(s, lo)
        # prefer the later-starting (inner) span on a tie of coverage
        if cover > 0 and cover >= best_cover:
            best, best_cover = name, cover
    return best
