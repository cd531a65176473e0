"""FlashSFA backward (``kernels/flash_sfa_bwd.py``): dQ and dK as code
gradients on the stored top-k coordinates, and dV.

Notation and byte widths as in ``sfa_fwd.py``; code gradients are bfloat16.
"""
from __future__ import annotations

from benchlib import work as W

NAMES = [r"^flash_sfa_bwd\.\d+$"]


def count(*, bh: int, n: int, k: int, dv: int, emit_k: int | None = None,
          pairs: int | None = None, val_b: int = 2, idx_b: int = 4,
          v_b: int = 2, lse_b: int = 4, grad_b: int = 2) -> W.Work:
    """FlashSFA backward: FLOPs = P * 4(k + dv).

    Bytes = codes, V, O, dO and LSE read once, plus the dq and dk code
    gradients (``emit_k`` wide, k unless RoPE widens them to pair
    closures) and dV written once.
    """
    ek = k if emit_k is None else emit_k
    pairs = W.causal_pairs(n) if pairs is None else pairs
    flops = bh * pairs * 4 * (k + dv)
    reads = 2 * n * k * (val_b + idx_b) + 3 * n * dv * v_b + n * lse_b
    writes = 2 * n * ek * grad_b + n * dv * v_b
    return W.Work(flops, bh * (reads + writes))


def work(cfg, mix) -> W.Work | None:
    """One training step of the mix, every attention layer; RoPE widens
    the emitted code gradients to 2k."""
    a = cfg.attention
    if a is None or a.sfa_k is None:
        return None
    b, n = int(mix["batch"]), int(mix["seq_len"])
    emit = 2 * a.sfa_k if a.rope else a.sfa_k
    total = W.Work(0, 0)
    for pairs, layers in W.attention_pairs(cfg, n).items():
        total += count(bh=b * a.num_heads, n=n, k=a.sfa_k,
                       dv=W.value_width(cfg), emit_k=emit,
                       pairs=pairs).scaled(layers)
    return total
