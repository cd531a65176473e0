#!/usr/bin/env python3
"""Chip smoke test: SFA training and serving through the normal entry points.

    python3 chip_smoke.py              # one TPU chip
    python3 chip_smoke.py --chips 4    # one host with four chips

One chip runs three phases on ``gpt2-small-sfa8`` at its published widths
(12 layers, d_model 768, 12 heads of 64, k=8, vocab 50257), weights from
``PRNGKey(0)``:

  * train  — 5 steps at batch 8 x 1024 through ``launch/train.py``'s path:
             pallas backend, compact backward emit, ``remat="codes"``, the
             fused forward. Losses must be finite and fall.
  * parity — one forward of the trained weights on the first batch, pallas
             against the xla oracle: max |logit difference| under a bf16
             bound.
  * serve  — the paged engine with the pallas decode kernel: 8 requests of
             256-1024 prompt tokens, chunked prefill of 256, 32 new tokens
             each. Every request must return all its tokens.

``--chips 4`` runs only what exists across chips: one train step with
Ring-SFA over a 4-way seq axis at seq 4096, and one with 4-way tensor
parallelism, each against the one-device step on the same weights and
batch (loss and grad norm within a bf16 bound).

Every compiled step must hold Pallas kernels (``tpu_custom_call``) and no
attention layer may fall back to the XLA oracle. The script prints compile
seconds, losses and token counts (no speed figure), and as its last line
``{"ok": true, "device": {...}}``. Any failure exits non-zero without that
line, and so does a run where JAX finds no TPU or where
``REPRO_PALLAS_INTERPRET`` forces interpret mode. The script runs in one
process and starts none.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "gpt2-small-sfa8"
# lr 1e-3, not the launcher's 3e-3: from random weights at this batch, 3e-3
# overshoots at steps 3-4 (loss 11.0 -> 11.4) through the xla oracle as
# much as through the kernels, so step 5 need not be below step 1
TRAIN = dict(steps=5, batch=8, seq_len=1024, lr=1e-3)
SERVE = dict(requests=8, min_prompt=256, max_prompt=1024, chunk=256,
             new_tokens=32, page=128)
RING = dict(batch=2, seq_len=4096)
# Pallas and XLA run the same bf16 model with different op orders, so the
# two may pick different top-k features where |q| values nearly tie. The
# bounds are a few bf16 steps (2^-8 relative) of the compared quantity.
LOGIT_RTOL = 2.0 ** -4          # of max |logit|
STEP_RTOL = 2.0 ** -5           # of the one-device loss / grad norm


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(msg):
    print(msg, flush=True)


def custom_calls(text: str) -> int:
    return text.count("tpu_custom_call")


def policy(backend="pallas"):
    """The train-time execution policy every phase runs."""
    if backend == "xla":
        return dict(backend="xla", bwd_emit="dense", remat="full")
    return dict(backend="pallas", bwd_emit="compact", remat="codes",
                fwd_fuse=True)


def check_no_fallback(phase):
    from repro.core.reports import collect_reports
    bad = collect_reports("backend")
    check(not bad, f"{phase}: backend fallback to the XLA oracle: {bad}")


def phase_train(cfg, devices, *, steps, batch, seq_len, lr):
    from repro.launch.train import run_training
    run = run_training(cfg, steps=steps, batch=batch, seq_len=seq_len, lr=lr,
                       devices=devices, **policy())
    losses = [m["loss"] for m in run.metrics]
    n_calls = custom_calls(run.hlo_text)
    say(f"train: compile {run.compile_seconds:.1f} s, "
        f"tpu_custom_call {n_calls}")
    say("train: losses " + " ".join(f"{x:.4f}" for x in losses))
    check(n_calls > 0, "train step holds no Pallas kernel")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: step 1 {losses[0]:.4f}, "
          f"step {len(losses)} {losses[-1]:.4f}")
    from repro.core.reports import collect_reports
    seams = collect_reports("compact_seam")
    check(seams and all(r.eligible for r in seams),
          f"compact seam not taken: {seams}")
    check_no_fallback("train")
    return run


def phase_parity(run, devices):
    import jax
    import jax.numpy as jnp
    from repro.configs.base import TrainPolicy
    from repro.data import markov_batch
    from repro.models import forward_logits

    cfgs = [TrainPolicy.from_model(run.cfg, **policy(b)).apply(run.cfg)
            for b in ("pallas", "xla")]
    batch = {k: jax.device_put(jnp.asarray(v), devices[0])
             for k, v in markov_batch(run.data, 0).items()}

    def diff(params, batch):
        lp, lx = (forward_logits(params, batch, c, mode="eval").logits
                  for c in cfgs)
        return jnp.max(jnp.abs(lp - lx)), jnp.max(jnp.abs(lx))

    lowered = jax.jit(diff).lower(run.params, batch)
    n_calls = custom_calls(lowered.as_text())
    d, scale = (float(x) for x in lowered.compile()(run.params, batch))
    say(f"parity: max |logit pallas - xla| {d:.5f} "
        f"(max |logit| {scale:.3f}, bound {LOGIT_RTOL * scale:.5f}), "
        f"tpu_custom_call {n_calls}")
    check(n_calls > 0, "parity forward holds no Pallas kernel")
    check(d <= LOGIT_RTOL * scale, "pallas and xla logits disagree")
    check_no_fallback("parity")


def phase_serve(cfg, params, *, requests, min_prompt, max_prompt, chunk,
                new_tokens, page):
    import time
    import jax.numpy as jnp
    import numpy as np
    from repro.serve import PagedDecodeEngine, PagedEngineConfig

    max_len = max_prompt + new_tokens
    eng = PagedDecodeEngine(params, cfg, PagedEngineConfig(
        max_slots=requests, max_len=max_len, page_size=page,
        prefill_chunk=chunk, decode_backend="pallas"))
    rs = np.random.RandomState(0)
    lens = np.linspace(min_prompt, max_prompt, requests).astype(int)
    rids = [eng.add_request(rs.randint(0, cfg.vocab_size, size=n), new_tokens)
            for n in lens]
    # compile both serving steps up front, to report their compile time and
    # kernel counts; the engine's own calls then hit the compilation cache
    lengths = jnp.zeros((requests,), jnp.int32)
    steps = {
        "decode": eng._decode.lower(eng.params, eng.last_token, eng.caches,
                                    lengths),
        "prefill_chunk": eng._chunk.lower(
            eng.params, jnp.zeros((1, chunk), jnp.int32), eng.caches,
            jnp.int32(0), jnp.int32(chunk), jnp.int32(0)),
    }
    for name, lowered in steps.items():
        t0 = time.perf_counter()
        lowered.compile()
        n_calls = custom_calls(lowered.as_text())
        say(f"serve: {name} compile {time.perf_counter() - t0:.1f} s, "
            f"tpu_custom_call {n_calls}")
        check(n_calls > 0, f"{name} step holds no Pallas kernel")
    ticks = 0
    while eng.busy:
        eng.step()
        ticks += 1
    got = [len(eng.outputs[r]) for r in rids]
    say(f"serve: {len(rids)} requests, prompts {lens.min()}-{lens.max()} "
        f"tokens, {sum(got)} tokens generated in {ticks} engine ticks")
    check(got == [new_tokens] * len(rids),
          f"requests returned {got} tokens, expected {new_tokens} each")
    check_no_fallback("serve")


def phase_multichip(cfg, devices):
    """Ring-SFA and TP train steps against the one-device step."""
    from repro.core.reports import clear_reports, collect_reports
    from repro.launch.train import run_training

    def one(label, n, batch, seq_len, **mesh):
        clear_reports()
        run = run_training(cfg, steps=1, batch=batch, seq_len=seq_len,
                           devices=devices[:n], **mesh, **policy())
        m = run.metrics[0]
        say(f"{label}: loss {m['loss']:.5f} grad_norm {m['grad_norm']:.5f} "
            f"compile {run.compile_seconds:.1f} s "
            f"tpu_custom_call {custom_calls(run.hlo_text)}")
        check(custom_calls(run.hlo_text) > 0, f"{label}: no Pallas kernel")
        check_no_fallback(label)
        return m, collect_reports("ring")

    def close(label, ref, got):
        for key in ("loss", "grad_norm"):
            err = abs(got[key] - ref[key]) / abs(ref[key])
            say(f"{label}: {key} relative difference {err:.2e} "
                f"(bound {STEP_RTOL:.2e})")
            check(err <= STEP_RTOL, f"{label}: {key} off the one-device step")

    ref, _ = one("ring reference (1 device)", 1, **RING)
    got, rings = one("ring (4-way seq)", 4, **RING, ring=4)
    check(rings and all(r.eligible for r in rings),
          f"ring not taken: {rings}")
    close("ring", ref, got)
    ref, _ = one("tp reference (1 device)", 1, TRAIN["batch"],
                 TRAIN["seq_len"])
    got, _ = one("tp (4-way model)", 4, TRAIN["batch"], TRAIN["seq_len"],
                 tp=4)
    close("tp", ref, got)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    try:
        from repro.configs import get_config
        from repro.kernels._compat import resolve_interpret
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        say(f"FAIL: the repro package is not next to this script ({e})")
        return 1
    import jax

    if jax.default_backend() != "tpu":
        say(f"FAIL: JAX found no TPU (backend {jax.default_backend()!r})")
        return 1
    if resolve_interpret(None):
        say("FAIL: REPRO_PALLAS_INTERPRET forces interpret mode")
        return 1
    devices = jax.devices()
    if len(devices) < args.chips:
        say(f"FAIL: --chips {args.chips} but {len(devices)} device(s)")
        return 1
    say(f"cache: {use_compile_cache()}")
    dev = devices[0]
    say(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    cfg = get_config(ARCH)
    try:
        if args.chips == 4:
            phase_multichip(cfg, devices)
        else:
            run = phase_train(cfg, devices[:1], **TRAIN)
            phase_parity(run, devices[:1])
            phase_serve(cfg, run.params, **SERVE)
    except SmokeFailure as e:
        say(f"FAIL: {e}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
