"""Model-level attention: GQA / MLA, qk-norm, RoPE, SFA, windows, KV caches.

Three call modes share parameters:
  * ``mode="train"``   — full-sequence causal (or bidirectional) attention.
  * ``mode="prefill"`` — same compute, additionally returns the KV cache
                         (a typed ``KVCache`` pytree, sparse for SFA layers)
                         for the decode engine.
  * ``mode="decode"``  — one new token against the cache; SFA scoring reads
                         the cache *sparsely* (O(nk) gathered bytes — the IO
                         pattern the roofline measures).
  * ``mode="chunk"``   — chunked prefill for the paged serving engine: a
                         chunk of one slot's prompt lands via ``write_chunk``
                         and is scored through the decode backend's
                         multi-token ``verify`` entry, each query a
                         single-token decode at its own prefix length
                         (DESIGN.md §5).

Execution backends are resolved through the typed registry
(``repro.models.backends``): ``cfg.attention.backend`` selects the
full-sequence path (XLA chunked softmax vs fused rtopk→FlashSFA Pallas
kernels) and ``cfg.attention.decode_backend`` the serving decode path (XLA
gather oracle vs the ``flash_sfa_decode`` / ``flash_sfa_decode_fm`` Pallas
kernels). Capability mismatches (windowed layers, protected RoPE dims, MLA)
fall back to ``xla`` with a structured, queryable ``FallbackReport`` instead
of a trace-time warning.

SFA-with-RoPE (paper A.1): ``sfa_rope_protect`` leading head dims are kept
dense (always-selected) so positional phase survives sparsification; Top-k
applies to the remaining dims.

MLA (+SFA, paper Table 10) uses the *absorbed* formulation: scores are taken
in the shared latent space (q_eff = q_nope·W_ukᵀ against c_kv), and SFA
sparsifies the latent codes — the decode cache stores c_kv sparsely for
scoring plus densely for the value aggregation, and k_pe densely.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AttentionConfig, ModelConfig
from repro.core.attention import chunked_attention
from repro.core import reports as _ureports
from repro.core.remat import tag_lse
from repro.core.kv_cache import (
    DenseKV, FeatureMajorKV, KVCache, MLAKV, MLASparseKV, PagedDenseKV,
    PagedFeatureMajorKV, PagedKV, PagedMLAKV, PagedMLASparseKV, PagedSparseKV,
    SparseKV, idx_dtype, pack_indices,
)
from repro.core.sparse import topk_st, sparsify, SparseCode
from repro.distributed.ring import ring_degree, ring_sfa_op
from repro.distributed.shard import tp_flash_sfa, tp_flash_sfa_bwd
from repro.distributed.sharding import axis_size, constrain
from repro.kernels.flash_sfa_bwd import pair_closure_indices
from repro.kernels.flash_sfa_decode import LANES as _FM_TILE, \
    feature_major_prefill
from repro.kernels.ops import (
    _sfa_pallas_fwd, fold_heads, fused_qk_codes, unfold_heads,
)
from repro.models.backends import (
    AttentionRequest, DecodeQuery, expand_kv as _expand_kv,
    resolve_backend_name, select_backend,
)
from repro.models.layers import (
    dense, dense_init, norm_init, apply_norm, rope, rope_code_vjp,
    sparse_proj_bwd,
)


def _pad_heads(q, num_heads: int):
    """Zero-pad the q-head axis up to the TP degree.

    Measured on llama3.2 train_4k (§Perf i6): padding 24->32 heads + classic
    head-TP costs 10.0 s of collectives vs 7.3 s for sequence-parallel q —
    the classic-TP backward's residual-sized f32 all-reduces outweigh the SP
    dk reduce. So padding is DISABLED (pad=0) and indivisible-head archs use
    SP; kept for A/B re-runs on other topologies."""
    return q, 0


def _constrain_qkv(q, k, v, num_heads: int):
    """Attention activation sharding (§Perf i1): heads take the model axis
    when divisible (classic TP); otherwise sequence-parallel q — XLA's
    fallback for unshardable heads is involuntary full replication
    (338 GB/step measured)."""
    msize = axis_size("model")
    if num_heads % msize == 0:
        q = constrain(q, ("batch", None, "heads", None))
    else:
        q = constrain(q, ("batch", "seq_sp", None, None))
    k = constrain(k, ("batch", None, None, None))
    v = constrain(v, ("batch", None, None, None))
    return q, k, v


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def attention_init(rng, cfg: ModelConfig):
    a = cfg.attention
    d = cfg.d_model
    rs = jax.random.split(rng, 12)
    if a.mla is not None:
        m = a.mla
        h = a.num_heads
        p = {
            "w_dq": dense_init(rs[0], d, m.q_lora_rank),
            "q_norm": norm_init(m.q_lora_rank),
            "w_uq_nope": dense_init(rs[1], m.q_lora_rank, h * m.nope_head_dim),
            "w_uq_pe": dense_init(rs[2], m.q_lora_rank, h * m.rope_head_dim),
            "w_dkv": dense_init(rs[3], d, m.kv_lora_rank),
            "kv_norm": norm_init(m.kv_lora_rank),
            "w_uk": dense_init(rs[4], m.kv_lora_rank, h * m.nope_head_dim),
            "w_kpe": dense_init(rs[5], d, m.rope_head_dim),
            "w_uv": dense_init(rs[6], m.kv_lora_rank, h * m.v_head_dim),
            "w_o": dense_init(rs[7], h * m.v_head_dim, d),
        }
        return p
    # fused QKV (§Perf i7): one column-parallel matmul -> one backward
    # dL/dx all-reduce instead of three, and a bigger MXU tile
    p = {
        "w_qkv": dense_init(rs[0], d,
                            (a.num_heads + 2 * a.num_kv_heads) * a.head_dim),
        "w_o": dense_init(rs[3], a.num_heads * a.head_dim, d),
    }
    if a.qk_norm:
        p["q_norm"] = norm_init(a.head_dim)
        p["k_norm"] = norm_init(a.head_dim)
    return p


# --------------------------------------------------------------------------
# SFA helpers
# --------------------------------------------------------------------------

def _sfa_code(x, a: AttentionConfig) -> SparseCode:
    """Sparse code of the non-protected dims (cache storage format)."""
    p = a.sfa_rope_protect
    return sparsify(x[..., p:], a.sfa_k)


def _request(a: AttentionConfig, *, mode: str, window, paged: bool = False,
             speculative: bool = False) -> AttentionRequest:
    """Static backend request for this layer (trace-time selection)."""
    return AttentionRequest(
        mode=mode,
        causal=a.causal if mode == "full" else True,
        window=(window is not None) or (a.window is not None),
        rope_protect=a.sfa_k is not None and a.sfa_rope_protect > 0,
        mla=a.mla is not None,
        sparse=a.sfa_k is not None,
        paged=paged,
        speculative=speculative,
    )


# --------------------------------------------------------------------------
# fused projection + attention seam for compact code-gradients
# --------------------------------------------------------------------------

def compact_seam_ineligible_reason(cfg: ModelConfig,
                                   window=None) -> Optional[str]:
    """None when a train-mode layer can take the fused compact-backward
    seam; else a human reason (recorded as a ``CompactSeamReport``).

    The seam spans the QKV projection through the FlashSFA kernels in one
    custom_vjp. RoPE *is* admitted: it is a per-pair rotation on known
    indices, so the backward stays compact — the kernel emits the (n, 2k)
    pair closure (``emit="compact2"``) and ``rope_code_vjp`` inverse-rotates
    the codes in place before the projection seam consumes them. Everything
    else between projection and kernel must be identity: qk-norm rescales
    the cotangent by data-dependent per-row statistics (off any fixed
    support), and windows / rope-protect / MLA / distill need the dense
    q/k/v outside the seam. Tensor parallelism IS admitted (DESIGN.md §9):
    the seam's kernels route through shard_map over the model axis
    (``distributed/shard.py``) with whole-head slices per device, so the
    dQ/dK code gradients need no cross-device reduction — eligibility is
    just that both head counts divide the TP degree (per-device slices must
    be whole head blocks; otherwise the layer falls back to the
    ``_constrain_qkv``-annotated path below, op-level compact emit).
    Ineligible ``bwd_emit="compact"`` layers still get the compact kernel
    emit at the op level (ops.py scatters once for the generic vjp)."""
    a = cfg.attention
    if a is None or a.sfa_k is None:
        return "not an SFA layer (sfa_k unset)"
    if a.bwd_emit not in ("compact", "compact2"):
        return "bwd_emit is dense"
    if a.mla is not None:
        return "MLA projects through the latent space outside the seam"
    if a.qk_norm:
        return ("qk-norm rescales the cotangent by per-row statistics, "
                "off the stored support")
    if window is not None or a.window is not None:
        return "windowed layers need the dense q/k for the mask fallback"
    if a.sfa_rope_protect > 0:
        return "sfa_rope_protect keeps leading dims dense outside the codes"
    if cfg.sfa_distill > 0:
        return "distill needs the dense q/k/v for the stop-grad teacher"
    if a.ring and ring_degree() > 1:
        return ("ring context parallelism routes through the op-level ring "
                "path (distributed/ring.py), not the projection seam")
    tp = axis_size("model")
    if tp > 1 and (a.num_heads % tp or a.num_kv_heads % tp):
        return (f"heads {a.num_heads}/{a.num_kv_heads} do not divide the TP "
                f"degree {tp}: the shard_map'd seam needs whole per-device "
                f"head slices to keep dQ/dK code grads reduction-free")
    return None


def compact_train_eligible(cfg: ModelConfig, window=None) -> bool:
    """True when a train-mode layer takes the fused compact-backward seam."""
    return compact_seam_ineligible_reason(cfg, window) is None


def remat_codes_ineligible_reason(cfg: ModelConfig) -> Optional[str]:
    """None when the stack can honour ``remat="codes"``; else a reason.

    The "codes" policy saves only ``checkpoint_name``-tagged saveables
    (core/remat.py::CODE_SAVEABLES), and only the SFA kernel paths
    (kernels/ops.py) tag them. On a stack whose forward never produces the
    tags, ``save_only_these_names`` saves nothing — silently identical to
    "full" but with the user believing codes are banked — so the layer scan
    degrades to "full" *explicitly* and records why (``record_remat``).
    """
    a = cfg.attention
    if a is None or a.sfa_k is None:
        return "not an SFA stack (sfa_k unset): no code saveables to tag"
    if a.mla is not None:
        return "MLA latent attention bypasses the code-tagging q/k paths"
    if compact_seam_ineligible_reason(cfg) is None:
        return None          # fused seam tags codes whatever the backend
    resolved = resolve_backend_name(
        a.backend, _request(a, mode="full", window=None))
    if resolved != "pallas":
        return (f"backend {a.backend!r} resolves to {resolved!r} for train "
                f"forwards: only the pallas kernel paths (and the fused "
                f"seam, ineligible here) tag the code saveables")
    return None


@dataclasses.dataclass(frozen=True)
class CompactSeamReport:
    """Structured record of a compact-seam routing decision (trace-time).

    The analogue of ``backends.FallbackReport`` for the fused
    projection+attention backward: every train-mode layer that *asked* for a
    compact emit gets exactly one record per (site, outcome) saying whether
    it took the seam, and if not, why — queryable instead of grepping logs.
    """
    where: str
    taken: bool
    reason: Optional[str] = None     # set when the seam was NOT taken
    fused_fwd: bool = False          # taken seam ran the fused forward path


_SEAM_REPORTS: dict = {}


def compact_seam_reports() -> tuple:
    """All deduped seam routing decisions since the last clear."""
    return tuple(_SEAM_REPORTS.values())


def clear_compact_seam_reports() -> None:
    _SEAM_REPORTS.clear()


def _record_seam(where: str, taken: bool, reason: Optional[str],
                 fused_fwd: bool = False) -> None:
    key = (where, taken, reason, fused_fwd)
    if key not in _SEAM_REPORTS:
        _SEAM_REPORTS[key] = CompactSeamReport(where=where, taken=taken,
                                               reason=reason,
                                               fused_fwd=fused_fwd)


def ring_ineligible_reason(cfg: ModelConfig, window=None,
                           n: Optional[int] = None) -> Optional[str]:
    """None when a train-mode layer with ``ring=True`` can take the
    Ring-SFA path (distributed/ring.py); else a human reason.

    The ring shards the *sequence*, so anything row-wise (projection,
    qk-norm, RoPE) is free — the constraints are the hop schedule's:
    causal SFA with fully-sparse codes, and a sequence divisible by the
    ring degree. The windowed / rope-protect / MLA fallbacks need dense
    K beyond a single shard's reach."""
    a = cfg.attention
    if a is None or a.sfa_k is None:
        return "not an SFA layer (sfa_k unset)"
    if not a.causal:
        return "ring hop schedule is the causal triangle"
    if a.mla is not None:
        return "MLA latent attention has no ring path"
    if window is not None or a.window is not None:
        return "windowed layers mask outside the ring hop schedule"
    if a.sfa_rope_protect > 0:
        return "rope-protected dims make the hop payload dense"
    p = ring_degree()
    if p <= 1:
        return "no seq mesh axis of size > 1 in the active context"
    if n is not None and n % p:
        return f"sequence {n} does not divide the ring degree {p}"
    return None


@dataclasses.dataclass(frozen=True)
class RingReport:
    """Structured record of a Ring-SFA routing decision (trace-time) —
    the ring analogue of ``CompactSeamReport``."""
    where: str
    taken: bool
    reason: Optional[str] = None     # set when the ring was NOT taken


_RING_REPORTS: dict = {}


def ring_reports() -> tuple:
    """All deduped ring routing decisions since the last clear."""
    return tuple(_RING_REPORTS.values())


def clear_ring_reports() -> None:
    _RING_REPORTS.clear()


def _record_ring(where: str, taken: bool, reason: Optional[str]) -> None:
    key = (where, taken, reason)
    if key not in _RING_REPORTS:
        _RING_REPORTS[key] = RingReport(where=where, taken=taken,
                                        reason=reason)


# unified report protocol (core/reports.py): read-only adapters exposing the
# native seam/ring records as "compact_seam"/"ring" components. The native
# accessors (``compact_seam_reports()`` etc.) keep working.
def _collect_seam_reports():
    return tuple(
        _ureports.make_report("compact_seam", r.where, eligible=r.taken,
                              reason=r.reason,
                              details={"fused_fwd": r.fused_fwd})
        for r in compact_seam_reports())


def _collect_ring_reports():
    return tuple(
        _ureports.make_report("ring", r.where, eligible=r.taken,
                              reason=r.reason)
        for r in ring_reports())


_ureports.register_provider("compact_seam", _collect_seam_reports,
                            clear_compact_seam_reports)
_ureports.register_provider("ring", _collect_ring_reports,
                            clear_ring_reports)


def _sfa_proj_attend_fwd_impl(w, x, positions, h, hkv, hd, sfa_k, causal,
                              scale, rope_spec, fwd_fuse=False):
    """Primal: qkv projection [-> rope] -> GQA expand -> ops.py's pallas
    primal (one source of truth for the rtopk -> FlashSFA dispatch).
    rope_spec: None, or the static ``(theta, rot_dim)`` pair.

    With ``fwd_fuse`` the q/k side runs ``ops.fused_qk_codes`` (projection ->
    RoPE -> top-k entirely in VMEM, only the (n, k) codes written to HBM) and
    FlashSFA runs with overlap-aware block skipping — same outputs, and the
    *identical* residual tuple, so the compact backward below is untouched.
    V stays a dense projection either way: the kernel streams it in full."""
    b, n, _ = x.shape
    dt = x.dtype
    if fwd_fuse:
        qv, qi, kv_, ki = fused_qk_codes(x, w, positions, h=h, hkv=hkv,
                                         hd=hd, sfa_k=sfa_k,
                                         rope_spec=rope_spec)
        wv = w[:, (h + hkv) * hd:].astype(dt)
        vf = fold_heads(_expand_kv((x @ wv).reshape(b, n, hkv, hd), h))
        out, lse = tp_flash_sfa(qv, qi, kv_, ki, vf, d=hd, causal=causal,
                                scale=scale, return_residuals=True,
                                block_skip=True)
        return (unfold_heads(out, b, h),
                (x, w, positions, qv, qi, kv_, ki, vf, out, tag_lse(lse)))
    qkv = x @ w.astype(dt)
    q, k, v = jnp.split(qkv, [h * hd, (h + hkv) * hd], axis=-1)
    q = q.reshape(b, n, h, hd)
    k = k.reshape(b, n, hkv, hd)
    if rope_spec is not None:
        theta, rot = rope_spec
        q = rope(q, positions, theta=theta, rot_dim=rot)
        k = rope(k, positions, theta=theta, rot_dim=rot)
    k = _expand_kv(k, h)
    v = _expand_kv(v.reshape(b, n, hkv, hd), h)
    out, res = _sfa_pallas_fwd(q, k, v, sfa_k, causal, scale,
                               return_residuals=True)
    return out, (x, w, positions) + res


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _sfa_proj_attend_compact(w, x, positions, h, hkv, hd, sfa_k, causal,
                             scale, rope_spec, req_emit, fwd_fuse):
    """Fused QKV-projection [+ RoPE] + SFA attention, compact-code backward.

    Forward is exactly the pallas train path (projection [-> rope] -> rtopk
    -> FlashSFA). The backward runs ``flash_sfa_bwd`` with a compact emit —
    ``"compact"`` (n, k) on rope-free layers, ``"compact2"`` (n, 2k) pair
    closures on rope'd layers, where ``rope_code_vjp`` inverse-rotates the
    codes in place (a rope-free layer explicitly configured with
    ``req_emit="compact2"`` also gets the widened emit, honoring the
    launch-flag contract of forcing the pair-widened kernel path) — and
    hands the code-gradients straight to the projection vjp seam
    (``layers.sparse_proj_bwd`` -> ``kernels/code_grad.py``): a dense (n, d)
    dQ/dK is never materialized in HBM anywhere on this path (grep-able
    contract, tests/test_code_grad.py + tests/test_rope_seam.py).
    """
    out, _ = _sfa_proj_attend_fwd_impl(w, x, positions, h, hkv, hd, sfa_k,
                                       causal, scale, rope_spec, fwd_fuse)
    return out


def _sfa_proj_attend_fwd(w, x, positions, h, hkv, hd, sfa_k, causal, scale,
                         rope_spec, req_emit, fwd_fuse):
    return _sfa_proj_attend_fwd_impl(w, x, positions, h, hkv, hd, sfa_k,
                                     causal, scale, rope_spec, fwd_fuse)


def _sfa_proj_attend_bwd(h, hkv, hd, sfa_k, causal, scale, rope_spec,
                         req_emit, fwd_fuse, res, g):
    # fwd_fuse changes only how the residual codes were produced, not their
    # layout — the compact backward is byte-for-byte the same seam.
    del fwd_fuse
    x, w, positions, qv, qi, kv_, ki, vf, out, lse = res
    b, n, _, _ = g.shape
    m = x.shape[-1]
    group = h // hkv
    gf = fold_heads(g)
    pair_widen = rope_spec is not None or req_emit == "compact2"
    emit = "compact2" if pair_widen else "compact"
    rot = hd if rope_spec is None else rope_spec[1]
    dqc, dkc, dvf = tp_flash_sfa_bwd(qv, qi, kv_, ki, vf, out, lse, gf, d=hd,
                                     causal=causal, scale=scale, emit=emit,
                                     rot_dim=rot)
    if not pair_widen:
        qi_c, ki_c = qi, ki
    else:
        # pair-widened path: the kernel emitted the (n, 2k) pair closure of
        # the stored indices — still O(n·k) work and bytes, still no dense
        # dQ/dK anywhere. With rope, inverse-rotate the code cotangents in
        # place; a forced compact2 on a rope-free layer skips the rotation
        # (the closure relayout alone is lossless).
        qi_c = pair_closure_indices(qi, rot)
        ki_c = pair_closure_indices(ki, rot)
        if rope_spec is not None:
            theta, rot = rope_spec
            posf = jnp.broadcast_to(positions, (b, n))
            posf = jnp.broadcast_to(posf[:, None],
                                    (b, h, n)).reshape(b * h, n)
            dqc = rope_code_vjp(dqc, qi_c, posf, theta=theta, rot_dim=rot)
            dkc = rope_code_vjp(dkc, ki_c, posf, theta=theta, rot_dim=rot)
    kq = dqc.shape[-1]                    # code width: k, or 2k pair-widened
    # per-head code-grad stacks over the flattened (b·n) token axis
    dq_vals = (dqc.reshape(b, h, n, kq).transpose(1, 0, 2, 3)
               .reshape(h, b * n, kq))
    dq_idx = (qi_c.reshape(b, h, n, kq).transpose(1, 0, 2, 3)
              .reshape(h, b * n, kq))
    # GQA: the head repeat precedes rtopk, so group members carry identical
    # indices (hence identical pair closures) — the group reduction is a
    # plain aligned sum of code values
    dk_vals = (dkc.reshape(b, hkv, group, n, kq).sum(2)
               .transpose(1, 0, 2, 3).reshape(hkv, b * n, kq))
    dk_idx = (ki_c.reshape(b, hkv, group, n, kq)[:, :, 0]
              .transpose(1, 0, 2, 3).reshape(hkv, b * n, kq))
    dv = dvf.reshape(b, hkv, group, n, hd).sum(2)            # (b, hkv, n, hd)
    dv_flat = jnp.moveaxis(dv, 1, 2).reshape(b * n, hkv * hd)
    x_flat = x.reshape(b * n, m)
    wq_heads = jnp.moveaxis(w[:, :h * hd].reshape(m, h, hd), 1, 0)
    wk_heads = jnp.moveaxis(
        w[:, h * hd:(h + hkv) * hd].reshape(m, hkv, hd), 1, 0)
    wv = w[:, (h + hkv) * hd:]
    dx_q, dwq = sparse_proj_bwd(x_flat, wq_heads, dq_vals, dq_idx, d=hd)
    dx_k, dwk = sparse_proj_bwd(x_flat, wk_heads, dk_vals, dk_idx, d=hd)
    dv32 = dv_flat.astype(jnp.float32)
    dx_v = dv32 @ wv.astype(jnp.float32).T
    dwv = x_flat.astype(jnp.float32).T @ dv32
    dw = jnp.concatenate(
        [jnp.moveaxis(dwq, 0, 1).reshape(m, h * hd),
         jnp.moveaxis(dwk, 0, 1).reshape(m, hkv * hd), dwv],
        axis=1).astype(w.dtype)
    dx = (dx_q + dx_k + dx_v).reshape(b, n, m).astype(x.dtype)
    # positions are integer coordinates: their cotangent is the float0 zero
    dpos = np.zeros(positions.shape, jax.dtypes.float0)
    return dw, dx, dpos


_sfa_proj_attend_compact.defvjp(_sfa_proj_attend_fwd, _sfa_proj_attend_bwd)


# --------------------------------------------------------------------------
# cache
# --------------------------------------------------------------------------

def _decode_uses_persistent_cache(cfg: ModelConfig) -> bool:
    """Cache layout follows the *selected decode backend*, not vice versa:
    a backend with the ``persistent_cache`` capability (pallas_fm) keeps its
    feature-major K image resident in the cache. Capability mismatches
    (window, rope-protect, MLA, dense) resolve to the oracle here exactly
    as they would at decode time, so allocation and serving always agree."""
    a = cfg.attention
    sel = select_backend(a.decode_backend,
                         _request(a, mode="decode", window=None),
                         where=f"{cfg.name}/cache")
    return sel.backend.caps.persistent_cache


def decode_cache_token_multiple(cfg: ModelConfig) -> int:
    """Allocation granularity of the decode cache's token axis.

    The persistent feature-major image is streamed by the kernel in
    128-lane token tiles; a token axis that is not a whole number of tiles
    makes the kernel's pad fallback copy the entire cache every step —
    exactly the re-materialization the layout retires. The engine rounds
    its ``max_len`` up by this multiple (1 for every other layout)."""
    if cfg.attention is None or cfg.attention.sfa_k is None:
        return 1
    return _FM_TILE if _decode_uses_persistent_cache(cfg) else 1


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> KVCache:
    """Per-layer typed decode cache (caller stacks across layers)."""
    a = cfg.attention
    if a.mla is not None:
        m = a.mla
        ckv = jnp.zeros((batch, max_len, m.kv_lora_rank), dtype)
        kpe = jnp.zeros((batch, max_len, m.rope_head_dim), dtype)
        if a.sfa_k is not None:
            kk = min(a.sfa_k, m.kv_lora_rank)
            return MLASparseKV(
                ckv=ckv, kpe=kpe,
                ckv_sp_vals=jnp.zeros((batch, max_len, kk), dtype),
                ckv_sp_idx=jnp.zeros((batch, max_len, kk),
                                     idx_dtype(m.kv_lora_rank)))
        return MLAKV(ckv=ckv, kpe=kpe)
    hkv, hd = a.num_kv_heads, a.head_dim
    if a.sfa_k is not None:
        if _decode_uses_persistent_cache(cfg):
            return FeatureMajorKV(
                k_feat=jnp.zeros((batch, hkv, hd, max_len), dtype),
                v=jnp.zeros((batch, hkv, max_len, hd), dtype))
        p = a.sfa_rope_protect
        kk = min(a.sfa_k, hd - p)
        return SparseKV(
            k_vals=jnp.zeros((batch, max_len, hkv, kk), dtype),
            k_idx=jnp.zeros((batch, max_len, hkv, kk), idx_dtype(hd - p)),
            v=jnp.zeros((batch, max_len, hkv, hd), dtype),
            k_protect=(jnp.zeros((batch, max_len, hkv, p), dtype)
                       if p else None))
    return DenseKV(k=jnp.zeros((batch, max_len, hkv, hd), dtype),
                   v=jnp.zeros((batch, max_len, hkv, hd), dtype))


def init_paged_cache(cfg: ModelConfig, *, slots: int, num_pages: int,
                     page_size: int, max_pages: int,
                     dtype=jnp.bfloat16) -> PagedKV:
    """Per-layer paged decode cache: shared page pool + zeroed block table.

    ``num_pages`` includes the reserved trash page 0 (DESIGN.md §5); the
    engine allocates pages 1.. on demand and swaps the ``block_table`` leaf
    as slots grow. The layout mirrors ``init_cache``: the selected decode
    backend's ``persistent_cache`` capability picks the feature-major image.
    """
    a = cfg.attention
    bt = jnp.zeros((slots, max_pages), jnp.int32)
    if a.mla is not None:
        m = a.mla
        ckv = jnp.zeros((num_pages, page_size, m.kv_lora_rank), dtype)
        kpe = jnp.zeros((num_pages, page_size, m.rope_head_dim), dtype)
        if a.sfa_k is not None:
            kk = min(a.sfa_k, m.kv_lora_rank)
            return PagedMLASparseKV(
                ckv=ckv, kpe=kpe,
                ckv_sp_vals=jnp.zeros((num_pages, page_size, kk), dtype),
                ckv_sp_idx=jnp.zeros((num_pages, page_size, kk),
                                     idx_dtype(m.kv_lora_rank)),
                block_table=bt)
        return PagedMLAKV(ckv=ckv, kpe=kpe, block_table=bt)
    hkv, hd = a.num_kv_heads, a.head_dim
    if a.sfa_k is not None:
        if _decode_uses_persistent_cache(cfg):
            return PagedFeatureMajorKV(
                k_feat=jnp.zeros((hkv, num_pages, hd, page_size), dtype),
                v=jnp.zeros((hkv, num_pages, page_size, hd), dtype),
                block_table=bt)
        p = a.sfa_rope_protect
        kk = min(a.sfa_k, hd - p)
        return PagedSparseKV(
            k_vals=jnp.zeros((hkv, num_pages, page_size, kk), dtype),
            k_idx=jnp.zeros((hkv, num_pages, page_size, kk),
                            idx_dtype(hd - p)),
            v=jnp.zeros((hkv, num_pages, page_size, hd), dtype),
            k_protect=(jnp.zeros((hkv, num_pages, page_size, p), dtype)
                       if p else None),
            block_table=bt)
    return PagedDenseKV(
        k=jnp.zeros((hkv, num_pages, page_size, hd), dtype),
        v=jnp.zeros((hkv, num_pages, page_size, hd), dtype),
        block_table=bt)


# --------------------------------------------------------------------------
# apply
# --------------------------------------------------------------------------

class AttentionOut(NamedTuple):
    out: jax.Array
    cache: Optional[KVCache]
    distill: jax.Array = jnp.zeros((), jnp.float32)


def attention_apply(params, x, *, cfg: ModelConfig, positions=None,
                    window=None, mode: str = "train", cache=None,
                    cache_len=None, slot=None) -> AttentionOut:
    a = cfg.attention
    if mode in ("chunk", "verify") and a is not None and a.mla is not None:
        raise NotImplementedError(
            f"{mode} mode does not cover MLA caches — serve MLA configs "
            f"through whole-prompt prefill (insert_pages), non-speculative")
    # "eval" is a gradient-free train-shape forward (long-context scoring);
    # it rides the train execution paths — seam, ring, remat — everywhere
    # except the distill loss term, which only exists under the loss.
    wants_seam = (mode in ("train", "eval") and a is not None
                  and a.sfa_k is not None
                  and a.bwd_emit in ("compact", "compact2"))
    if a.mla is not None:
        if wants_seam:
            _record_seam(f"{cfg.name}/attention", False,
                         compact_seam_ineligible_reason(cfg, window))
        return _mla_apply(params, x, cfg=cfg, positions=positions, mode=mode,
                          cache=cache, cache_len=cache_len)
    b, n, d_model = x.shape
    h, hkv, hd = a.num_heads, a.num_kv_heads, a.head_dim
    dt = x.dtype
    if wants_seam:
        reason = compact_seam_ineligible_reason(cfg, window)
        if reason is None:
            sel = select_backend(a.backend,
                                 _request(a, mode="full", window=window),
                                 where=f"{cfg.name}/attention")
            if sel.backend.name != "pallas":
                reason = (f"backend resolved to {sel.backend.name!r}; the "
                          f"seam wraps the pallas kernels")
        if reason is None:
            # fused projection+attention custom_vjp: the backward consumes
            # the kernels' compact code-gradients directly — (n, k), or the
            # (n, 2k) pair closure rotated through rope_code_vjp on rope'd
            # layers — no dense dQ/dK round-trip (DESIGN.md §3)
            _record_seam(f"{cfg.name}/attention", True, None,
                         fused_fwd=a.fwd_fuse)
            if a.rope:
                pos = (positions if positions is not None
                       else jnp.arange(n)[None, :])
                rope_spec = (a.rope_theta, hd)
            else:
                pos = jnp.zeros((1, 1), jnp.int32)       # unused by the seam
                rope_spec = None
            o = _sfa_proj_attend_compact(params["w_qkv"]["w"], x, pos, h,
                                         hkv, hd, a.sfa_k, a.causal,
                                         hd ** -0.5, rope_spec, a.bwd_emit,
                                         a.fwd_fuse)
            out = dense(params["w_o"], o.reshape(b, n, h * hd).astype(dt), dt)
            return AttentionOut(out, None)
        _record_seam(f"{cfg.name}/attention", False, reason)
    qkv = dense(params["w_qkv"], x, dt)
    q, k, v = jnp.split(qkv, [h * hd, (h + hkv) * hd], axis=-1)
    q = q.reshape(b, n, h, hd)
    k = k.reshape(b, n, hkv, hd)
    v = v.reshape(b, n, hkv, hd)
    if a.qk_norm:
        q = apply_norm(params["q_norm"], q)
        k = apply_norm(params["k_norm"], k)
    if a.rope:
        if positions is None:
            positions = jnp.arange(n)[None, :]
        q = rope(q, positions, theta=a.rope_theta)
        k = rope(k, positions, theta=a.rope_theta)
    scale = hd ** -0.5

    if mode == "decode":
        assert cache is not None and cache_len is not None
        # write new token K/V, then score against the (sparse) cache
        if a.sfa_k is not None:
            p = a.sfa_rope_protect
            kc = _sfa_code(k, a)                      # (b, 1, hkv, k)
            cache = cache.write(cache_len, k_vals=kc.values, k_idx=kc.indices,
                                v=v, k_protect=k[..., :p] if p else None)
        else:
            cache = cache.write(cache_len, k=k, v=v)
        sel = select_backend(a.decode_backend,
                             _request(a, mode="decode", window=window,
                                      paged=isinstance(cache, PagedKV)),
                             where=f"{cfg.name}/attention")
        ctx = sel.backend.decode(DecodeQuery(q=q), cache, cache_len,
                                 scale=scale, window=window, sfa_k=a.sfa_k,
                                 rope_protect=a.sfa_rope_protect,
                                 draft_k=a.sfa_draft_k)
        o = ctx.astype(dt).reshape(b, 1, h * hd)
        return AttentionOut(dense(params["w_o"], o, dt), cache)

    if mode in ("chunk", "verify"):
        # chunked prefill / speculative verify: land C tokens of one slot
        # (prompt tokens, or the draft's FULL-k codes overwriting its low-k'
        # decode writes — the K/V-resolution half of the rewind contract,
        # DESIGN.md §6), then score every query at its own causal length
        # (query i sees cache_len + i + 1 tokens) in ONE batched pass
        # through the backend's multi-token verify entry point. Each query
        # is exactly a single-token decode, so chunk boundaries never
        # change which tokens are visible. Backends without the capability
        # fall back to the oracle with a structured report.
        assert cache is not None and cache_len is not None and slot is not None
        if a.sfa_k is not None:
            p = a.sfa_rope_protect
            kc = _sfa_code(k, a)                      # (1, C, hkv, k)
            cache = cache.write_chunk(slot, cache_len, k_vals=kc.values,
                                      k_idx=kc.indices, v=v,
                                      k_protect=k[..., :p] if p else None)
        else:
            cache = cache.write_chunk(slot, cache_len, k=k, v=v)
        sel = select_backend(a.decode_backend,
                             _request(a, mode="decode", window=window,
                                      paged=isinstance(cache, PagedKV),
                                      speculative=True),
                             where=f"{cfg.name}/attention")
        g = cache.gather_slot(slot)                   # batch-1 contiguous
        lens = cache_len + jnp.arange(n)              # (C,)
        block_n = cache.page_size if isinstance(cache, PagedKV) else 128
        ctx = sel.backend.verify(DecodeQuery(q=q), g, lens, scale=scale,
                                 window=window, sfa_k=a.sfa_k,
                                 rope_protect=a.sfa_rope_protect,
                                 block_n=block_n)
        o = ctx.astype(dt).reshape(1, n, h * hd)
        return AttentionOut(dense(params["w_o"], o, dt), cache)

    # train / prefill: full-sequence attention (heads padded to TP degree).
    # backend="pallas" routes through the fused rtopk->FlashSFA kernels (fwd
    # AND bwd — kernels/flash_sfa_bwd.py); windowed / rope-protected layers
    # fall back to the XLA path via the registry (structured report).
    o = None
    if mode in ("train", "eval") and a.sfa_k is not None and a.ring:
        # Ring-SFA context parallelism (distributed/ring.py): the rope'd
        # dense q/k fold and shard over the seq mesh axis; rtopk and the
        # hop loop run per shard inside the ring's shard_map, rotating
        # (n/P, k) K-code payloads instead of dense K. GQA expands BEFORE
        # rtopk so group members carry identical codes, matching the
        # single-device composition row-for-row.
        reason = ring_ineligible_reason(cfg, window, n=n)
        _record_ring(f"{cfg.name}/attention", reason is None, reason)
        if reason is None:
            o = unfold_heads(
                ring_sfa_op(fold_heads(q), fold_heads(_expand_kv(k, h)),
                            fold_heads(_expand_kv(v, h)), sfa_k=a.sfa_k,
                            scale=scale), b, h)
    if o is None:
        sel = select_backend(a.backend,
                             _request(a, mode="full", window=window),
                             where=f"{cfg.name}/attention")
        qp, pad_h = _pad_heads(q, h)
        h_eff = h + pad_h
        qp, kp, vp = _constrain_qkv(qp, k, v, h_eff)
        # k/v stay at hkv heads: the backend sparsifies first, then expands
        o = sel.backend.full(qp, kp, vp, num_heads=h_eff, sfa_k=a.sfa_k,
                             rope_protect=a.sfa_rope_protect, causal=a.causal,
                             window=window, scale=scale, bwd_emit=a.bwd_emit)
        if pad_h:
            o = o[:, :, :h]
    distill = jnp.zeros((), jnp.float32)
    if mode == "train" and a.sfa_k is not None and cfg.sfa_distill > 0:
        # paper Eq. 8: pull SFA head outputs toward stop-grad dense outputs
        o_dense = jax.lax.stop_gradient(chunked_attention(
            q, _expand_kv(k, h), _expand_kv(v, h), causal=a.causal,
            window=window, scale=scale, chunk_size=min(1024, max(n, 128))))
        distill = jnp.mean(jnp.square(o.astype(jnp.float32) -
                                      o_dense.astype(jnp.float32)))
    o = o.reshape(b, n, h * hd)
    out = dense(params["w_o"], o, dt)
    new_cache = None
    if mode == "prefill":
        if a.sfa_k is not None:
            p = a.sfa_rope_protect
            kc = _sfa_code(k, a)
            if _decode_uses_persistent_cache(cfg):
                # feature-major prefill-write: build the persistent (d, n)
                # image (and the kernel-native heads-major V) once; decode
                # steps extend both column-by-column
                new_cache = FeatureMajorKV(
                    k_feat=feature_major_prefill(kc.values.astype(dt),
                                                 kc.indices, hd),
                    v=jnp.moveaxis(v, 1, 2))
            else:
                new_cache = SparseKV(k_vals=kc.values.astype(dt),
                                     k_idx=pack_indices(kc.indices, hd - p),
                                     v=v,
                                     k_protect=k[..., :p] if p else None)
        else:
            new_cache = DenseKV(k=k, v=v)
    return AttentionOut(out, new_cache, distill)


# --------------------------------------------------------------------------
# MLA (+ SFA on the latent) — absorbed formulation
# --------------------------------------------------------------------------

def _mla_project(params, x, *, cfg: ModelConfig, positions):
    a, m = cfg.attention, cfg.attention.mla
    b, n, _ = x.shape
    h = a.num_heads
    dt = x.dtype
    cq = apply_norm(params["q_norm"], dense(params["w_dq"], x, dt))
    q_nope = dense(params["w_uq_nope"], cq, dt).reshape(b, n, h, m.nope_head_dim)
    q_pe = dense(params["w_uq_pe"], cq, dt).reshape(b, n, h, m.rope_head_dim)
    ckv = apply_norm(params["kv_norm"], dense(params["w_dkv"], x, dt))
    kpe = dense(params["w_kpe"], x, dt).reshape(b, n, 1, m.rope_head_dim)
    if positions is None:
        positions = jnp.arange(n)[None, :]
    q_pe = rope(q_pe, positions, theta=a.rope_theta)
    kpe = rope(kpe, positions, theta=a.rope_theta)
    # absorb W_uk: q_eff[h] = q_nope[h] @ W_uk[h]^T  -> latent-space query
    w_uk = params["w_uk"]["w"].reshape(m.kv_lora_rank, h, m.nope_head_dim)
    q_eff = jnp.einsum("bnhd,rhd->bnhr", q_nope, w_uk.astype(dt))
    return q_eff, q_pe, ckv, kpe


def _mla_out(params, o_lat, *, cfg: ModelConfig):
    a, m = cfg.attention, cfg.attention.mla
    b, n, h, r = o_lat.shape
    dt = o_lat.dtype
    w_uv = params["w_uv"]["w"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    o = jnp.einsum("bnhr,rhd->bnhd", o_lat, w_uv.astype(dt))
    return dense(params["w_o"], o.reshape(b, n, h * m.v_head_dim), dt)


def _mla_apply(params, x, *, cfg: ModelConfig, positions, mode, cache,
               cache_len) -> AttentionOut:
    a, m = cfg.attention, cfg.attention.mla
    b, n, _ = x.shape
    h = a.num_heads
    dt = x.dtype
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    q_eff, q_pe, ckv, kpe = _mla_project(params, x, cfg=cfg, positions=positions)

    if mode == "decode":
        assert cache is not None and cache_len is not None
        code = sparsify(ckv, a.sfa_k) if a.sfa_k is not None else None
        cache = cache.write(
            cache_len, ckv=ckv, kpe=kpe[:, :, 0],
            ckv_sp_vals=None if code is None else code.values,
            ckv_sp_idx=None if code is None else code.indices)
        sel = select_backend(a.decode_backend,
                             _request(a, mode="decode", window=None),
                             where=f"{cfg.name}/mla")
        o_lat = sel.backend.decode(
            DecodeQuery(q=q_eff, q_pe=q_pe), cache, cache_len,
            scale=scale, window=None, sfa_k=a.sfa_k, rope_protect=0)
        o_lat = o_lat[:, None].astype(dt)             # (b, 1, h, r)
        return AttentionOut(_mla_out(params, o_lat, cfg=cfg), cache)

    # train / prefill: latent attention with 1 shared kv "head"; the latent
    # sparsification is MLA-specific, so the backend runs the pre-sparsified
    # dense-layout latents (registry still reports pallas fallbacks).
    sel = select_backend(a.backend, _request(a, mode="full", window=None),
                         where=f"{cfg.name}/mla")
    if a.sfa_k is not None:
        q_eff = topk_st(q_eff, a.sfa_k)
        ckv_s = topk_st(ckv, a.sfa_k)
    else:
        ckv_s = ckv
    qcat = jnp.concatenate([q_eff, q_pe], axis=-1)          # (b,n,h,r+dr)
    qcat, pad_h = _pad_heads(qcat, h)
    h_eff = h + pad_h
    kcat = jnp.concatenate([ckv_s[:, :, None], kpe], axis=-1)  # (b,n,1,r+dr)
    kcat = jnp.broadcast_to(kcat, (b, n, h_eff, kcat.shape[-1]))
    vlat = jnp.broadcast_to(ckv[:, :, None], (b, n, h_eff, m.kv_lora_rank))
    qcat, kcat, vlat = _constrain_qkv(qcat, kcat, vlat, h_eff)
    o_lat = sel.backend.full(qcat, kcat, vlat, num_heads=h_eff, sfa_k=None,
                             rope_protect=0, causal=a.causal, window=None,
                             scale=scale)
    if pad_h:
        o_lat = o_lat[:, :, :h]
    out = _mla_out(params, o_lat, cfg=cfg)
    new_cache = None
    if mode == "prefill":
        if a.sfa_k is not None:
            code = sparsify(ckv, a.sfa_k)
            new_cache = MLASparseKV(
                ckv=ckv, kpe=kpe[:, :, 0],
                ckv_sp_vals=code.values.astype(dt),
                ckv_sp_idx=pack_indices(code.indices, m.kv_lora_rank))
        else:
            new_cache = MLAKV(ckv=ckv, kpe=kpe[:, :, 0])
    return AttentionOut(out, new_cache)
