"""Work counts: the operations and bytes an operation needs, from its shapes.

Each count is the operation's own work, not one kernel's: no densify pass,
no padding, no recompute, no cache tile read twice. A later kernel that does
the same operation with less waste therefore still has a share of at most
100% against these counts. ``PERF.md`` states each formula and which bound
(compute or bytes) sets each roofline.

Notation, per head: ``P`` causal (query, key) pairs, ``k`` = ``sfa_k``,
``dv`` the value width. Byte widths are the dtypes the operation's inputs
and outputs have on the trained path: code values and V in bfloat16, code
indices int32, LSE float32.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def scaled(self, factor: float) -> "Work":
        return Work(self.flops * factor, self.bytes * factor)

    def roofline_s(self, peak_flops: float, peak_bytes_per_s: float) -> float:
        """Least time the chip could take: the larger of the two bounds."""
        return max(self.flops / peak_flops, self.bytes / peak_bytes_per_s)

    def bound(self, peak_flops: float, peak_bytes_per_s: float) -> str:
        return ("compute" if self.flops / peak_flops
                >= self.bytes / peak_bytes_per_s else "bytes")



def causal_pairs(n: int) -> int:
    return n * (n + 1) // 2


def sfa_fwd(*, bh: int, n: int, k: int, dv: int, val_b: int = 2,
            idx_b: int = 4, v_b: int = 2, lse_b: int = 4) -> Work:
    """FlashSFA forward over ``bh`` (batch x head) rows of length ``n``.

    FLOPs = P * 2(k + dv): a k-sparse score, then P.V.
    Bytes = q and k codes (values and indices) + V + O + LSE, once each.
    """
    flops = bh * causal_pairs(n) * 2 * (k + dv)
    codes = 2 * n * k * (val_b + idx_b)
    byts = bh * (codes + n * dv * v_b + n * dv * v_b + n * lse_b)
    return Work(flops, byts)


def sfa_bwd(*, bh: int, n: int, k: int, dv: int, emit_k: int | None = None,
            val_b: int = 2, idx_b: int = 4, v_b: int = 2, lse_b: int = 4,
            grad_b: int = 2) -> Work:
    """FlashSFA backward: FLOPs = P * 4(k + dv).

    Bytes = codes, V, O, dO and LSE read once, plus the dq and dk code
    gradients (``emit_k`` wide, k unless RoPE widens them to pair
    closures) and dV written once.
    """
    ek = k if emit_k is None else emit_k
    flops = bh * causal_pairs(n) * 4 * (k + dv)
    reads = 2 * n * k * (val_b + idx_b) + 3 * n * dv * v_b + n * lse_b
    writes = 2 * n * ek * grad_b + n * dv * v_b
    return Work(flops, bh * (reads + writes))


def matmul_params(m: dict) -> int:
    """N_matmul: non-embedding weights plus the tied logits matmul (the
    position table is a lookup, not a matmul). ``m`` is the configuration
    file's ``model`` block."""
    d, hd = m["d_model"], m["head_dim"]
    h, hkv = m["num_heads"], m["num_kv_heads"]
    attn = d * (h + 2 * hkv) * hd + h * hd * d
    mlp = (3 if m["glu"] else 2) * d * m["d_ff"]
    return m["num_layers"] * (attn + mlp) + m["vocab_size"] * d


def attn_fwd_flops(m: dict, context: float) -> float:
    """SFA attention forward FLOPs of one token that sees ``context`` keys,
    over all layers and query heads: context * 2(k + dv) each."""
    return (m["num_layers"] * m["num_heads"] * context * 2
            * (m["sfa_k"] + m["head_dim"]))


def train_flops_per_token(m: dict, n: int) -> float:
    """MFU's model FLOPs for training at sequence length ``n``: three
    times the forward (6 * N_matmul, and 3 x the SFA attention forward of
    the mean token, which sees (n + 1) / 2 keys)."""
    return 6 * matmul_params(m) + 3 * attn_fwd_flops(m, (n + 1) / 2)

