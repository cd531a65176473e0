"""Work counts: the operations and bytes an operation needs, from its shapes.

Each count is the operation's own work, not one kernel's: no densify pass,
no padding, no recompute, no cache tile read twice. A later kernel that does
the same operation with less waste therefore still has a share of at most
100% against these counts. ``PERF.md`` states each formula and which bound
(compute or bytes) sets each roofline. The operations' own counts are in
``ops/<op>.py``; what they share is here, read from the system's
``ModelConfig`` and the configuration's weight layout.
"""
from __future__ import annotations

import collections
import math
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


def causal_pairs(n: int, window: int | None = None) -> int:
    """(query, key) pairs of a causal sequence of ``n``; with a window,
    each query sees its last ``window`` keys at most."""
    if window is None or window >= n:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


def attention_pairs(cfg, n: int) -> collections.Counter:
    """Causal pairs per head of each attention layer at length ``n``, as
    pairs -> number of layers. A hybrid stack has one attention layer in
    each period; under a local/global pattern every (pattern + 1)-th layer
    is global."""
    a = cfg.attention
    layers = (cfg.num_layers // cfg.hybrid_period if cfg.hybrid_period
              else cfg.num_layers)
    pat = a.local_global_pattern
    return collections.Counter(
        causal_pairs(n, None if pat is not None and i % (pat + 1) == pat
                     else a.window)
        for i in range(layers))


def value_width(cfg) -> int:
    """The width of a head's value: MLA's ``v_head_dim``, else the head's."""
    a = cfg.attention
    return a.mla.v_head_dim if a.mla is not None else a.head_dim


def sfa_fwd_flops(cfg, n: int) -> int:
    """SFA attention forward FLOPs of one sequence of ``n``, over all
    layers and query heads: P * 2(k + dv) each."""
    a = cfg.attention
    pairs = sum(p * count for p, count in attention_pairs(cfg, n).items())
    return pairs * a.num_heads * 2 * (a.sfa_k + value_width(cfg))


def matmul_params(cfg, layout: list) -> float:
    """N_matmul, the weights a token multiplies by, from the layout's
    roles: every ``matmul`` leaf; each ``experts`` leaf times ``top_k``
    over its router's output width (a token visits top_k of the experts
    the router chooses among, whatever number of them the tree holds);
    and the tied logits matmul. Lookups and norms are not matmuls."""
    shapes = {tuple(leaf["path"]): leaf["shape"] for leaf in layout}
    n = 0.0
    for leaf in layout:
        size = math.prod(leaf["shape"])
        if leaf["role"] == "matmul":
            n += size
        elif leaf["role"] == "experts":
            n += size * cfg.moe.top_k / shapes[tuple(leaf["router"])][-1]
    if cfg.tie_embeddings:
        n += cfg.vocab_size * cfg.d_model
    return n


def train_flops_per_token(cfg, layout: list, n: int) -> float:
    """MFU's model FLOPs for training at sequence length ``n``: three
    times the forward (6 * N_matmul, and 3 x the SFA attention forward of
    the mean token)."""
    return 6 * matmul_params(cfg, layout) + 3 * sfa_fwd_flops(cfg, n) / n
