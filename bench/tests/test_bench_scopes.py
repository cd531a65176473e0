"""Device time by the program's layer names (``benchlib/scopes.py``), on a
step recorded on the chip (``data/train_step_scopes.json``) and on
hand-made name stacks and profiler protobufs."""
import json
import re
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchlib import registry, scopes, trace

DATA = Path(__file__).resolve().parent / "data"
KERNELS = registry.kernel_map()


@pytest.fixture(scope="module")
def step():
    return json.loads((DATA / "train_step_scopes.json").read_text())


@pytest.fixture(scope="module")
def red(step):
    return scopes.reduce(step)


def test_vocabulary_is_the_programs():
    """The names the benchmark attributes by, its own and those that the
    configurations of BENCHMARK.json declare, are the program's."""
    from repro.core import scopes as program
    vocab = scopes.vocabulary()
    assert vocab.names == program.SCOPES
    assert set(vocab.forward) < set(vocab.names)


@pytest.mark.parametrize("stack, want", [
    ("jit(step)/jvp()/while/body/closed_call/mlp/dot_general:",
     ("mlp", False)),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/"
     "dot_general", ("mlp", False)),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attn.proj/sfa.fwd/jit(flash_sfa)/pallas_call",
     ("sfa.fwd", True)),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attn.proj/sfa.code_grad/sfa.bwd/"
     "jit(flash_sfa_bwd)/pallas_call", ("sfa.bwd", False)),
    ("jit(step)/jvp(embed)/embed/gather", ("embed", False)),
    ("jit(step)/jit(norm)/sqrt", (None, False)),
    ("jit(step)/transpose(jvp())/while:", (None, False)),
])
def test_attribute(stack, want):
    assert scopes.vocabulary().attribute(stack) == want


# the attribution before configurations declared scopes, frozen
OLD_SCOPE = re.compile(r"(?:^|/|(?<!jit)\()(" + "|".join(map(re.escape, (
    "embed", "norm", "attn.proj", "sfa.proj_rtopk", "sfa.block_maps",
    "sfa.fwd", "sfa.bwd", "sfa.code_grad", "mlp", "loss", "optim")))
    + r")(?=$|/|\)|:)")
OLD_FORWARD = ("embed", "norm", "attn.proj", "sfa.proj_rtopk",
               "sfa.block_maps", "sfa.fwd", "mlp", "loss")


def old_attribute(stack):
    found = OLD_SCOPE.findall(stack)
    if not found:
        return None, False
    return found[-1], (found[-1] in OLD_FORWARD
                       and "rematted_computation" in stack)


def test_attribution_is_unchanged(step, red):
    """Every scope, alone and inside another, in the forward pass, its
    transpose and its recompute, is attributed as before; and the recorded
    step reads the same seconds by scope."""
    from repro.core import scopes as program
    stacks = []
    for outer in ("", "attn.proj/", "mlp/"):
        for s in program.SCOPES + ("sfa", "fwd", "mlpx", "jit(mlp)"):
            stacks += [f"jit(step)/jvp()/while/body/{outer}{s}/dot:",
                       f"jit(step)/transpose(jvp())/while/body/checkpoint/"
                       f"rematted_computation/{outer}{s}/pallas_call",
                       f"jit(step)/transpose(jvp({outer}{s}))/add",
                       f"jit(step)/{outer}{s}"]
    vocab = scopes.vocabulary()
    for stack in stacks:
        assert vocab.attribute(stack) == old_attribute(stack), stack
    assert red.total_s == pytest.approx(3.2178107360000014, rel=1e-12)
    assert red.scope_s["sfa.block_maps"] == pytest.approx(0.6772982520000008,
                                                          rel=1e-12)
    assert red.recomputed_s["sfa.fwd"] == pytest.approx(0.44636592400000014,
                                                        rel=1e-12)


def test_configuration_declares_scopes():
    """A configuration's own scope names join the vocabulary; its forward
    ones count as recomputed under ``rematted_computation``."""
    vocab = scopes.Vocabulary.of([{"scopes": {
        "names": ["moe.experts", "moe.router"], "forward": ["moe.experts"]}}])
    assert vocab.names[-2:] == ("moe.experts", "moe.router")
    stack = ("jit(step)/transpose(jvp())/checkpoint/rematted_computation/"
             "mlp/moe.experts/dot_general")
    assert vocab.attribute(stack) == ("moe.experts", True)
    assert vocab.attribute(stack.replace("experts", "router")) == (
        "moe.router", False)
    assert scopes.vocabulary().attribute(stack) == ("mlp", True)
    with pytest.raises(registry.BenchError, match="forward"):
        scopes.Vocabulary.of([{"scopes": {"names": [], "forward": ["x"]}}])


def test_scoped_ops_cover_the_step(red):
    assert red.scoped_s >= 0.95 * red.total_s


def test_kernel_scopes_hold_the_kernels(step, red):
    """The FlashSFA scopes hold the kernels that ``ops/`` names, and
    little else: their time agrees within 1%."""
    kern = trace.reduce(step, KERNELS).op_seconds
    by_name = kern["sfa_fwd"] + kern["sfa_bwd"]
    by_scope = red.scope_s["sfa.fwd"] + red.scope_s["sfa.bwd"]
    assert by_scope == pytest.approx(by_name, rel=0.01)


def test_one_flash_sfa_per_layer_is_recomputed(step):
    """``remat="codes"`` saves the codes and LSE, not the output, so each
    layer runs FlashSFA's forward twice a step: once in the forward pass,
    once again in the backward."""
    layers = registry.load_json(
        registry.BENCH / "configs" / "gpt2-small-sfa8.json")["model"][
        "num_layers"]
    fwd = re.compile(KERNELS["sfa_fwd"][0])
    runs = [rec for (name, _, _), (_, rec) in zip(step["devices"]["0"],
                                                  step["scopes"]["0"])
            if fwd.search(name)]
    assert sorted(runs) == [False] * layers + [True] * layers


def test_readers_read_the_reduction(step, red):
    """Both readers give the fixture's sums over the window."""
    (win,) = [s for s in step["spans"] if s[0] == trace.WINDOW_SPAN]
    lo, hi = win[1], win[1] + win[2]
    total = maps = redone = 0.0
    for (name, start, dur), (scope, rec) in zip(step["devices"]["0"],
                                                step["scopes"]["0"]):
        t = max(0, min(start + dur, hi) - max(start, lo))
        if trace.CONTROL.match(name):
            continue
        total += t
        maps += t if scope == "sfa.block_maps" else 0
        redone += t if rec else 0
    reading = SimpleNamespace(scopes=red)
    got_maps = registry.metric_reader("block_maps_share.train").read(reading)
    got_redo = registry.metric_reader("remat_share.train").read(reading)
    assert got_maps == pytest.approx(100 * maps / total)
    assert got_redo == pytest.approx(100 * redone / total)
    assert 0 < got_maps < 100 and 0 < got_redo < 100


def test_no_scope_reads_none(step):
    """A program that names no scope (one before the names existed) gives
    no reading rather than a zero."""
    bare = dict(step, scopes={"0": [[None, False]] * len(step["scopes"]["0"])})
    reading = SimpleNamespace(scopes=scopes.reduce(bare))
    for name in ("block_maps_share.train", "remat_share.train"):
        assert registry.metric_reader(name).read(reading) is None


# -- a profiler protobuf made by hand ----------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """(number, value) pairs: ints as varints, bytes and str as
    length-delimited, lists of ints packed."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
            continue
        if isinstance(v, str):
            v = v.encode()
        elif isinstance(v, list):
            v = b"".join(_varint(x) for x in v)
        out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _plane(name, events, stat_names):
    """events: (id, name, {stat id: str or bytes})."""
    fields = [(2, name)]
    for sid, sname in stat_names.items():
        fields.append((5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    for eid, ename, stats in events:
        st = [(5, _msg((1, k), (5 if isinstance(v, str) else 6, v)))
              for k, v in stats.items()]
        fields.append((4, _msg((1, eid), (2, _msg((1, eid), (2, ename),
                                                  *st)))))
    return _msg(*fields)


def test_op_scopes_from_protobuf():
    """tf_op decides; a fusion whose tf_op names only its loop takes the
    scope of the ops fused inside it, from the HLO module in the metadata
    plane."""
    def ins(name, op_name, called=()):
        return _msg((1, name), (7, _msg((2, op_name))), (38, list(called)))
    hlo = _msg((1, _msg(
        (3, _msg((1, "entry"), (2, ins("fusion.7", "jit(step)/while",
                                       [2])), (5, 1))),
        (3, _msg((1, "fused"), (2, ins("reshape.1", "jit(step)/transpose("
                                       "jvp())/rematted_computation/"
                                       "sfa.block_maps/reshape")),
                 (2, ins("scatter.2", "")), (5, 2))))))
    device = _plane("/device:TPU:0", [
        (1, "%fusion.7 = f32[8] fusion(...)", {1: "jit(step)/while:"}),
        (2, "%dot.3 = f32[8] dot(...)", {1: "jit(step)/jvp()/mlp/dot:"}),
        (3, "%copy.4 = f32[8] copy(...)", {1: "jit(step)/while:"}),
    ], {1: "tf_op"})
    meta = _plane("/host:metadata", [(1, "jit_step", {1: hlo})],
                  {1: "Hlo Proto"})
    space = memoryview(_msg((1, device), (1, meta)))
    assert scopes.op_scopes(space, scopes.vocabulary()) == {"0": {
        "fusion.7": ("sfa.block_maps", True), "dot.3": ("mlp", False),
        "copy.4": (None, False)}}


def test_tool_prints_scopes_and_gaps(step, monkeypatch, capsys):
    tool = registry.load_module(registry.BENCH / "tools" / "scopes.py",
                                "tool_scopes")
    monkeypatch.setattr(scopes, "load", lambda _: step)
    assert tool.main(["unused", "--gaps", "3"]) == 0
    out = capsys.readouterr().out
    assert "sfa.block_maps" in out and "(none)" in out
    assert "idle" in out and out.count("bench.") + out.count("host:") >= 1
