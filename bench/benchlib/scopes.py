"""Device time of the train step by layer, from the names the program gives
its layers (``jax.named_scope``, ``src/repro/core/scopes.py``).

``load`` reads the newest ``.xplane.pb`` under a trace directory into
``trace.load_xplane``'s record, with two additions: the ``engine.*`` host
spans of the serving engine beside the ``bench.*`` ones, and ``scopes``,
one ``[scope, recomputed]`` pair for each device event, aligned with
``devices``. So ``trace.reduce`` reads the same record, and its idle gaps
name engine phases too.

An event's scope is the innermost name of the vocabulary in its op's name
stack: the ``tf_op`` stat that the TPU profiler gives each op. XLA leaves
some fusions with the name stack of the loop they sit in; for those the
ops fused inside, in the HLO module that the profiler keeps in its
metadata plane, decide (the scope most of them carry). An event is
``recomputed`` when its scope is a forward one and its name stack runs
through ``rematted_computation``: the part of the backward pass that
``jax.checkpoint`` re-runs. The transposed ops of a forward scope keep its
name in the backward pass but are not recomputed.

``reduce`` sums, over the ``bench.window`` span as ``trace.reduce`` does
(control-flow events left out), each scope's device time and its
recomputed part, as the mean over devices.
"""
from __future__ import annotations

import collections
import functools
import glob
import os
import re
from dataclasses import dataclass, field

from benchlib import registry, trace

# the program's names (src/repro/core/scopes.py), kept here too because
# the benchmark also reads programs that predate that module; a
# configuration adds its own (``Vocabulary.of``), and bench/tests pins the
# union equal to the program's
SCOPES = ("embed", "norm", "attn.proj", "sfa.proj_rtopk", "sfa.block_maps",
          "sfa.fwd", "sfa.bwd", "sfa.code_grad", "mlp", "loss", "optim")
FORWARD = ("embed", "norm", "attn.proj", "sfa.proj_rtopk", "sfa.block_maps",
           "sfa.fwd", "mlp", "loss")
RECOMPUTE = "rematted_computation"
ENGINE_SPAN = "engine."
# where run.py's Context writes the trace of a --trace 1 run
TRACE_DIR = registry.ROOT / ".bench_trace"


@dataclass(frozen=True)
class Vocabulary:
    """The scope names an op is attributed by, and which of them are
    forward scopes (re-run under ``jax.checkpoint``)."""
    names: tuple
    forward: tuple

    @classmethod
    def of(cls, configs) -> "Vocabulary":
        """``SCOPES`` and ``FORWARD`` with the names that each
        configuration file declares (``"scopes": {"names": [...],
        "forward": [...]}``) added after them."""
        names, forward = list(SCOPES), list(FORWARD)
        for config in configs:
            declared = config.get("scopes", {})
            new, fwd = declared.get("names", []), declared.get("forward", [])
            if not set(fwd) <= set(new):
                raise registry.BenchError(
                    f"{config.get('name')}: forward scopes "
                    f"{sorted(set(fwd) - set(new))} are not among its names")
            names += [x for x in new if x not in names]
            forward += [x for x in fwd if x not in forward]
        return cls(tuple(names), tuple(forward))

    @functools.cached_property
    def pattern(self):
        # a scope is a whole component of the name stack, possibly inside
        # a transformation's parentheses ("jvp(embed)"), never a jitted
        # function's name ("jit(norm)"); the TPU's tf_op ends in ":"
        return re.compile(r"(?:^|/|(?<!jit)\()("
                          + "|".join(map(re.escape, self.names))
                          + r")(?=$|/|\)|:)")

    def attribute(self, name_stack: str):
        """``(scope, recomputed)`` of an op's name stack; scope None when
        it carries none of the names."""
        found = self.pattern.findall(name_stack)
        if not found:
            return None, False
        scope = found[-1]
        return scope, scope in self.forward and RECOMPUTE in name_stack


def vocabulary() -> Vocabulary:
    """The scopes of the program and of every configuration that
    ``BENCHMARK.json`` names."""
    bench = registry.benchmark()
    return Vocabulary.of(registry.load_json(registry.ROOT / c["file"])
                         for c in bench["configs"])


def load(trace_dir) -> dict:
    """The newest trace under ``trace_dir`` as a record (module doc),
    attributed by ``vocabulary()``."""
    import jax

    record = trace.load_xplane(str(trace_dir))
    path = newest_xplane(trace_dir)
    pd = jax.profiler.ProfileData.from_file(path)
    record["spans"] += [[e.name, e.start_ns, e.duration_ns]
                        for plane in pd.planes
                        if not trace.DEVICE_PLANE.match(plane.name)
                        for line in plane.lines for e in line.events
                        if e.name.startswith(ENGINE_SPAN)]
    with open(path, "rb") as f:
        ops = op_scopes(memoryview(f.read()), vocabulary())
    record["scopes"] = {dev: [list(ops.get(dev, {}).get(e[0], (None, False)))
                              for e in evs]
                        for dev, evs in record["devices"].items()}
    return record


def newest_xplane(trace_dir) -> str:
    paths = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


@dataclass
class ScopeReduction:
    """Device seconds of one traced window by scope (mean over devices);
    the key None holds the time of ops in no scope."""
    total_s: float
    scope_s: dict = field(default_factory=dict)
    recomputed_s: dict = field(default_factory=dict)

    @property
    def scoped_s(self) -> float:
        return sum(v for k, v in self.scope_s.items() if k is not None)

    def share(self, seconds: float) -> float | None:
        """Percent of all device time, or None where no op of the window
        carries a scope (a program without the names)."""
        if not self.scoped_s:
            return None
        return 100.0 * seconds / self.total_s


def reduce(record: dict) -> ScopeReduction:
    wins = [s for s in record["spans"] if s[0] == trace.WINDOW_SPAN]
    devices = record["devices"]
    if wins:
        lo = min(s[1] for s in wins)
        hi = max(s[1] + s[2] for s in wins)
    else:
        lo = min(e[1] for evs in devices.values() for e in evs)
        hi = max(e[1] + e[2] for evs in devices.values() for e in evs)
    total = 0.0
    by_scope: dict = collections.defaultdict(float)
    redone: dict = collections.defaultdict(float)
    for dev, evs in devices.items():
        for (name, start, dur), (scope, recomputed) in zip(
                evs, record["scopes"][dev]):
            s, e = max(start, lo), min(start + dur, hi)
            if e <= s or trace.CONTROL.match(name):
                continue
            sec = (e - s) / 1e9 / len(devices)
            total += sec
            by_scope[scope] += sec
            if recomputed:
                redone[scope] += sec
    return ScopeReduction(total, dict(by_scope), dict(redone))


def of(reading) -> ScopeReduction:
    """The scope reduction of a run's trace, made once per reading: the
    per-layer readers of one run share it."""
    red = getattr(reading, "scopes", None)
    if red is None:
        red = reading.scopes = reduce(load(TRACE_DIR))
    return red


# -- the profiler's protobuf, read field by field ---------------------------
# XSpace.planes 1; XPlane.name 2, event_metadata 4 (map entry: 1 key,
# 2 value), stat_metadata 5; XEventMetadata.name 2, stats 5;
# XStatMetadata.name 2; XStat.metadata_id 1, str_value 5, bytes_value 6,
# ref_value 7 (a stat_metadata id whose name is the string). HloProto
# .hlo_module 1; HloModuleProto.computations 3; HloComputationProto
# .instructions 2, id 5; HloInstructionProto.name 1, metadata 7,
# called_computation_ids 38; OpMetadata.op_name 2.

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> dict:
    """A protobuf message's fields: number -> list of values (ints, or
    memoryviews of length-delimited fields)."""
    out = collections.defaultdict(list)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        out[key >> 3].append(v)
    return out


def _ints(values) -> list:
    """A repeated integer field, packed (one length-delimited run of
    varints) or not."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
            continue
        i = 0
        while i < len(v):
            x, i = _varint(v, i)
            out.append(x)
    return out


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stats(meta, stat_names) -> dict:
    """An XEventMetadata's stats by name: strings and bytes."""
    out = {}
    for st in meta[5]:
        f = _fields(st)
        name = stat_names.get(f[1][0] if f[1] else None)
        if f[5]:
            out[name] = _str(f[5][0])
        elif f[7]:
            out[name] = stat_names.get(f[7][0])
        elif f[6]:
            out[name] = f[6][0]
    return out


def _plane_metadata(plane):
    """(plane name, [XEventMetadata fields], {stat id: stat name})."""
    stat_names = {}
    for entry in plane[5]:
        value = _fields(_fields(entry)[2][0])
        stat_names[value[1][0] if value[1] else 0] = _str(value[2][0])
    metas = [_fields(_fields(entry)[2][0]) for entry in plane[4]]
    return _str(plane[2][0]), metas, stat_names


def _hlo_inner_scopes(hlo_proto, vocab: Vocabulary) -> dict:
    """Instruction name -> ``vocab``'s attribution of each op fused or
    called inside it, from one HloProto."""
    comps = {}
    for comp in _fields(_fields(hlo_proto)[1][0])[3]:
        c = _fields(comp)
        rows = []
        for ins in c[2]:
            f = _fields(ins)
            meta = _fields(f[7][0]) if f[7] else {}
            rows.append((_str(f[1][0]), _str(meta[2][0]) if meta.get(2)
                         else "", _ints(f[38])))
        comps[c[5][0] if c[5] else 0] = rows

    def inner(ids, seen):
        for cid in ids:
            if cid in seen or cid not in comps:
                continue
            seen.add(cid)
            for _, op_name, called in comps[cid]:
                yield vocab.attribute(op_name)
                yield from inner(called, seen)

    return {name: [a for a in inner(called, set()) if a[0] is not None]
            for rows in comps.values() for name, _, called in rows if called}


def op_scopes(xspace, vocab: Vocabulary) -> dict:
    """Device -> {op name: (scope, recomputed)} from a serialized XSpace:
    each op's ``tf_op`` name stack, else the scope most of the ops inside
    it carry, both attributed by ``vocab``. An op name that two programs
    give different scopes is left out."""
    planes = [_plane_metadata(_fields(p)) for p in _fields(xspace)[1]]
    inner: dict = {}
    for _, metas, stat_names in planes:
        for meta in metas:
            proto = _stats(meta, stat_names).get("Hlo Proto")
            if proto is not None:
                inner.update(_hlo_inner_scopes(proto, vocab))
    out = {}
    for pname, metas, stat_names in planes:
        m = trace.DEVICE_PLANE.match(pname)
        if not m:
            continue
        ops: dict = {}
        for meta in metas:
            if not meta[2]:
                continue
            name = trace.op_name(_str(meta[2][0]))
            got = vocab.attribute(_stats(meta, stat_names).get("tf_op") or "")
            if got[0] is None and inner.get(name):
                got = collections.Counter(inner[name]).most_common(1)[0][0]
            ops[name] = got if ops.get(name, got) == got else (None, False)
        out[m.group(1)] = ops
    return out
