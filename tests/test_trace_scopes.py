"""Every layer of the SFA train step carries one of ``core/scopes.py``'s
names in its HLO ``op_name`` metadata, which the profiler trace reads.

The step is lowered (not compiled) for the CPU with the benchmark's policy:
pallas kernels in interpret mode, compact backward, fused forward and
``remat="codes"``. The HLO is read before XLA's passes, so every
instruction is one the program made; a computation's instructions inherit
the op_name of the instruction that calls it, as XLA's inliner gives them.
"""
from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.configs.base import TrainPolicy
from repro.core import scopes
from repro.models import init
from repro.optim import OptimizerConfig, init_opt_state
from repro.train.train_step import make_train_step

SCOPE = re.compile(r"(?:^|/|\()(" + "|".join(map(re.escape, scopes.SCOPES))
                   + r")(?=$|/|\))")
CALLEES = re.compile(r"(?:calls|to_apply|body|condition|true_computation|"
                     r"false_computation)=%?([\w.\-]+)|"
                     r"branch_computations=\{([^}]*)\}")
INSTR = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.+?) ([a-z][\w\-]*)\(")
# what the layer scan, autodiff and remat add around the layers' work:
# moving values in and out of the loop carry and the stacked per-layer
# arrays, layout moves of loop-invariant operands that autodiff hoists out
# of the scans, remat's barrier; scalars (counters, the loss sum) pass too
BOOKKEEPING = {"parameter", "constant", "tuple", "get-tuple-element",
               "while", "call", "conditional", "bitcast", "copy", "reshape",
               "transpose", "broadcast", "dynamic-slice",
               "dynamic-update-slice", "opt-barrier"}


def _train_step_hlo() -> str:
    cfg = get_config("gpt2-small-sfa8")
    cfg = dataclasses.replace(
        cfg, num_layers=2, d_model=64, d_ff=128, vocab_size=256,
        max_seq_len=512, loss_chunk=128,
        attention=dataclasses.replace(cfg.attention, num_heads=2,
                                      num_kv_heads=2, head_dim=32, sfa_k=4))
    policy = TrainPolicy.from_model(cfg, backend="pallas", bwd_emit="compact",
                                    remat="codes", fwd_fuse=True)
    step = make_train_step(cfg, OptimizerConfig(), policy=policy)
    params = jax.eval_shape(lambda k: init(k, cfg), jax.random.PRNGKey(0))
    opt = jax.eval_shape(init_opt_state, params)
    toks = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    lowered = jax.jit(step).lower(params, opt,
                                  {"tokens": toks, "labels": toks})
    return lowered.as_text(dialect="hlo", debug_info=True)


def _instructions(hlo: str):
    """(opcode, shape, full op_name) of every instruction reachable from the
    entry computation, each computation under each of its call sites."""
    comps, entry, cur = {}, None, None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(2), [])
            entry = head.group(2) if head.group(1) else entry
            continue
        m = INSTR.match(line)
        if cur is None or m is None:
            continue
        own = re.search(r'op_name="([^"]*)"', line)
        callees = [c for a, b in CALLEES.findall(line)
                   for c in ([a] if a else re.findall(r"%?([\w.\-]+)", b))]
        cur.append((m.group(2), m.group(1), own.group(1) if own else "",
                    callees))
    out, seen = [], set()

    def walk(comp, prefix):
        if (comp, prefix) in seen:
            return
        seen.add((comp, prefix))
        for opcode, shape, own, callees in comps[comp]:
            full = "/".join(p for p in (prefix, own) if p)
            out.append((opcode, shape, full))
            for c in callees:
                # a called function's names are its own; loop bodies and
                # reducers already carry the whole name stack
                walk(c, full if opcode == "call" else prefix)

    walk(entry, "")
    return out


@pytest.fixture(scope="module")
def instructions():
    return _instructions(_train_step_hlo())


def test_every_scope_names_some_work(instructions):
    found = {m for _, _, name in instructions for m in SCOPE.findall(name)}
    assert found == set(scopes.SCOPES)


def test_unscoped_instructions_are_bookkeeping(instructions):
    work = [(opcode, shape, name) for opcode, shape, name in instructions
            if not SCOPE.search(name) and opcode not in BOOKKEEPING
            and not re.match(r"^\w+\[\]", shape)]
    assert not work, work[:20]


def test_recomputed_forward_is_marked(instructions):
    """``remat="codes"`` re-runs FlashSFA's forward (and its pre-pass) in
    the backward pass, under ``rematted_computation``; the fused top-k
    kernel is not re-run, its codes are saved."""
    redone = [name for _, _, name in instructions
              if "rematted_computation" in name]
    found = {SCOPE.findall(n)[-1] for n in redone if SCOPE.search(n)}
    assert {scopes.SFA_FWD, scopes.BLOCK_MAPS} <= found
    assert not found & {scopes.SFA_BWD, scopes.CODE_GRAD, scopes.OPTIM}
    assert not any("jit(proj_rtopk)" in n for n in redone)
