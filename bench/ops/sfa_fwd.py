"""FlashSFA forward (``kernels/flash_sfa.py``): a k-sparse score of each
causal (query, key) pair, then P.V.

Per head, with ``P`` causal pairs, ``k`` = ``sfa_k`` and ``dv`` the value
width. Byte widths are the dtypes of the trained path: code values and V in
bfloat16, code indices int32, LSE float32.
"""
from __future__ import annotations

from benchlib import work as W

# the TPU trace names a Pallas kernel after its pallas_call, with the
# instruction's number
NAMES = [r"^flash_sfa\.\d+$"]


def count(*, bh: int, n: int, k: int, dv: int, pairs: int | None = None,
          val_b: int = 2, idx_b: int = 4, v_b: int = 2,
          lse_b: int = 4) -> W.Work:
    """FlashSFA forward over ``bh`` (batch x head) rows of length ``n``.

    FLOPs = P * 2(k + dv): a k-sparse score, then P.V.
    Bytes = q and k codes (values and indices) + V + O + LSE, once each.
    """
    pairs = W.causal_pairs(n) if pairs is None else pairs
    flops = bh * pairs * 2 * (k + dv)
    codes = 2 * n * k * (val_b + idx_b)
    byts = bh * (codes + n * dv * v_b + n * dv * v_b + n * lse_b)
    return W.Work(flops, byts)


def work(cfg, mix) -> W.Work | None:
    """One training step of the mix, every attention layer."""
    a = cfg.attention
    if a is None or a.sfa_k is None:
        return None
    b, n = int(mix["batch"]), int(mix["seq_len"])
    total = W.Work(0, 0)
    for pairs, layers in W.attention_pairs(cfg, n).items():
        total += count(bh=b * a.num_heads, n=n, k=a.sfa_k,
                       dv=W.value_width(cfg), pairs=pairs).scaled(layers)
    return total
