"""Typed attention-backend registry: capability-based kernel selection.

One seam for every attention execution path in the repo. A backend is an
object with a ``Capabilities`` record and two entry points:

  * ``full(q, k, v, ...)``   — full-sequence attention (train / prefill) on
                               already head-expanded ``(b, n, h, d)``
                               activations;
  * ``decode(query, cache, lengths, ...)`` — one new token against a typed
                               ``KVCache`` (repro/core/kv_cache.py),
                               returning the per-head context ``(b, h, dv)``.

Registered backends:

  * ``xla``       — pure-JAX paths: chunked online-softmax for full
                    sequences, gather-scoring for sparse decode. Supports
                    everything (windows, protected RoPE dims, MLA, both
                    cache layouts) and is the correctness oracle.
  * ``pallas``    — fused rtopk→FlashSFA kernels for full sequences
                    (forward AND backward — kernels/flash_sfa_bwd.py) and
                    the token-major sparse-cache decode kernel
                    ``flash_sfa_decode`` (O(nk) K-bytes per step).
  * ``pallas_fm`` — decode-only: the beyond-paper feature-major decode
                    kernel ``flash_sfa_decode_fm`` (sparse query selects k
                    feature rows of the *persistent* dense feature-major K
                    image kept in ``FeatureMajorKV`` — its
                    ``persistent_cache`` capability is what makes the cache
                    allocator pick that layout; the hot path performs zero
                    per-step re-materialization).
  * ``auto``      — not a backend but a selection policy: the first
                    registered backend whose capabilities cover the request,
                    preferring the Pallas kernels on TPU and the XLA paths
                    elsewhere (interpret-mode Pallas on CPU is a correctness
                    tool, not a serving path).

Selection replaces the old scattered ``impl``/``bwd_impl`` strings and the
silent ``use_pallas`` predicate: ``select_backend`` either returns the
requested backend or falls back to ``xla`` with a structured
``FallbackReport`` (deduped, surfaced through ONE ``logging.warning`` here
and queryable via ``fallback_reports()`` — no more trace-time
``warnings.warn``).
"""
from __future__ import annotations

import dataclasses
import logging
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import reports as _reports
from repro.core.attention import chunked_attention, NEG_INF
from repro.core.kv_cache import (
    FeatureMajorKV, KVCache, MLAKV, MLASparseKV, PagedFeatureMajorKV,
    PagedKV, PagedSparseKV, SparseKV, unpack_indices,
)
from repro.core.sparse import sparsify, sub_k, to_feature_major, topk_st
from repro.kernels.flash_sfa_decode import (
    flash_sfa_decode, flash_sfa_decode_fm, flash_sfa_decode_fm_paged,
    flash_sfa_decode_multi, flash_sfa_decode_paged,
)
from repro.kernels.ops import dense_attention_op, sfa_attention_op

_LOG = logging.getLogger(__name__)


# --------------------------------------------------------------------------
# request / capabilities
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttentionRequest:
    """Static description of what a layer needs from a backend."""
    mode: str                    # "full" (train/prefill) | "decode"
    causal: bool = True
    window: bool = False         # sliding-window mask required
    rope_protect: bool = False   # SFA with protected leading RoPE dims
    mla: bool = False            # latent (MLA) attention
    sparse: bool = False         # sfa_k is set
    paged: bool = False          # cache is a paged (block-table) PagedKV
    speculative: bool = False    # multi-token verify pass required


@dataclasses.dataclass(frozen=True)
class Capabilities:
    full: bool = False           # full-sequence (train / prefill) path
    decode: bool = False         # single-token cached decode path
    causal: bool = True
    bidirectional: bool = False
    window: bool = False
    rope_protect: bool = False
    mla: bool = False
    sparse: bool = True
    dense: bool = True
    differentiable: bool = False
    # the backend keeps its decode layout resident in the cache itself
    # (FeatureMajorKV): the cache allocator picks the cache type from the
    # selected backend — not the other way around
    persistent_cache: bool = False
    # the backend can decode against a PagedKV block-table cache (reads
    # indirected through the block table); backends without it fall back
    # to the oracle with a structured report when the engine serves paged
    paged: bool = False
    # the backend has a multi-token verify pass (``verify``): C drafted
    # queries scored against one slot's cache in a single launch, each at
    # its own causal length — the speculative engine's full-k re-check
    speculative: bool = False


class DecodeQuery(NamedTuple):
    """Query pieces for one decode step. Sparsification is a backend
    concern (each backend runs exactly one top-k pass, in the form its
    kernel wants — dense-layout for the gather/token-major paths, compact
    (vals, idx) for the feature-major kernel).

    q    (b, 1, h, d)  dense post-RoPE query (for MLA: the latent q_eff)
    q_pe (b, 1, h, dr) MLA RoPE query part (None outside MLA)
    """
    q: jax.Array
    q_pe: Optional[jax.Array] = None


class AttentionBackend:
    name: str = "?"
    caps: Capabilities = Capabilities()

    def unsupported_reason(self, req: AttentionRequest) -> Optional[str]:
        """None if this backend can serve ``req``, else a human reason."""
        c = self.caps
        if req.mode == "full" and not c.full:
            return "no full-sequence path"
        if req.mode == "decode" and not c.decode:
            return "no decode path"
        if req.causal and not c.causal:
            return "causal masking not supported"
        if not req.causal and not c.bidirectional:
            return "bidirectional attention not supported"
        if req.window and not c.window:
            return "windowed attention not supported"
        if req.rope_protect and not c.rope_protect:
            return "sfa_rope_protect dims not supported"
        if req.mla and not c.mla:
            return "MLA latent attention not supported"
        if req.sparse and not c.sparse:
            return "SFA sparse attention not supported"
        if not req.sparse and not c.dense:
            return "dense attention not supported"
        if req.paged and not c.paged:
            return "paged KV cache (block-table reads) not supported"
        if req.speculative and not c.speculative:
            return "no multi-token speculative verify path"
        return None

    # entry points ------------------------------------------------------
    def full(self, q, k, v, *, num_heads, sfa_k, rope_protect, causal,
             window, scale, bwd_emit="dense"):
        """q: (b, n, h, d); k/v: (b, n, hkv, d) — the backend expands KV
        heads itself (after any sparsification, so top-k runs at hkv).
        ``bwd_emit`` is the FlashSFA backward emit layout (Pallas only;
        the XLA oracle's autodiff has no dense/compact distinction)."""
        raise NotImplementedError(self.name)

    def decode(self, query: DecodeQuery, cache: KVCache, lengths, *,
               scale, window, sfa_k, rope_protect, draft_k=None):
        raise NotImplementedError(self.name)

    def verify(self, query: DecodeQuery, cache: KVCache, lengths, *,
               scale, window, sfa_k, rope_protect, block_n=128):
        """Speculative verify: score C drafted queries ``query.q (1, C, h,
        d)`` against ONE slot's contiguous cache view in a single pass.
        ``lengths (C,)`` are per-query cache lengths (query j sees positions
        ``< lengths[j] + 1`` — the same +1 convention as ``decode``).
        Returns ``(C, h, dv)``. ``block_n`` is the accumulation tile width
        (set to the serving page size so logits match the paged decode
        kernel bit-for-bit)."""
        raise NotImplementedError(self.name)


# --------------------------------------------------------------------------
# shared helpers
# --------------------------------------------------------------------------

def expand_kv(t, h):
    """(b, n, hkv, ...) -> (b, n, h, ...) GQA head repeat."""
    hkv = t.shape[2]
    if hkv == h:
        return t
    return jnp.repeat(t, h // hkv, axis=2)


def _fold_expand(t, h):
    """(b, n, hkv, ...) -> (b*h, n, ...) for the per-(b,h) decode kernels."""
    b, n = t.shape[:2]
    t = jnp.moveaxis(expand_kv(t, h), 2, 1)          # (b, h, n, ...)
    return t.reshape((b * h, n) + t.shape[3:])


def _expand_feature_major(t, h):
    """(b, hkv, ...) heads-major FeatureMajorKV leaf -> (b, h, ...) GQA
    head repeat (oracle-side only; the kernel shares per-group rows via its
    index maps instead)."""
    hkv = t.shape[1]
    if hkv == h:
        return t
    return jnp.repeat(t, h // hkv, axis=1)


def _st_protect(x, sfa_k, p):
    """Straight-through top-k keeping p leading dims dense (paper A.1)."""
    if sfa_k is None:
        return x
    if p:
        return jnp.concatenate([x[..., :p], topk_st(x[..., p:], sfa_k)], -1)
    return topk_st(x, sfa_k)


def _prefix_mask(nmax, lengths, window):
    """(b, n) validity mask: cache prefix (incl. the just-written token),
    optionally restricted to a sliding window."""
    posn = jnp.arange(nmax)[None, :]
    limit = (lengths + 1)[:, None] if jnp.ndim(lengths) else lengths + 1
    ok = posn < limit
    if window is not None:
        ok = ok & (posn > limit - 1 - window)
    return ok


def _gather_score(q, k_vals, k_idx, scale):
    """Sparse decode scoring: s[b,n,h] = Σ_t k_vals[b,n,h,t]·q[b,h,idx].

    q: (b, h, d); k_vals/k_idx: (b, n, h, k). O(n·k) touched K bytes — the
    paper's decode IO claim, expressed as an XLA gather (the oracle the
    Pallas decode kernels are checked against).
    """
    b, n, h, k = k_vals.shape
    qb = jnp.broadcast_to(q[:, None].astype(jnp.float32),
                          (b, n, h, q.shape[-1]))
    qg = jnp.take_along_axis(qb, k_idx, axis=-1)            # (b, n, h, k)
    return (qg * k_vals.astype(jnp.float32)).sum(-1) * scale  # (b, n, h)


# --------------------------------------------------------------------------
# XLA backend — the oracle; supports everything
# --------------------------------------------------------------------------

class XLABackend(AttentionBackend):
    name = "xla"
    caps = Capabilities(full=True, decode=True, causal=True,
                        bidirectional=True, window=True, rope_protect=True,
                        mla=True, sparse=True, dense=True,
                        differentiable=True, paged=True, speculative=True)

    def full(self, q, k, v, *, num_heads, sfa_k, rope_protect, causal,
             window, scale, bwd_emit="dense"):
        if sfa_k is not None:
            # sparsify at hkv heads, BEFORE the GQA repeat (group-size-x
            # cheaper; expanded copies would re-run identical top-k rows)
            q = _st_protect(q, sfa_k, rope_protect)
            k = _st_protect(k, sfa_k, rope_protect)
        k = expand_kv(k, num_heads)
        v = expand_kv(v, num_heads)
        n = q.shape[1]
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 scale=scale,
                                 chunk_size=min(1024, max(n, 128)))

    def decode(self, query: DecodeQuery, cache: KVCache, lengths, *,
               scale, window, sfa_k, rope_protect, draft_k=None):
        if isinstance(cache, PagedKV):
            # oracle paged path: gather the block-table view back into the
            # contiguous layout and score as usual. O(n) extra copies — a
            # correctness tool; the paged Pallas kernels read in place.
            cache = cache.gather()
        if isinstance(cache, (MLAKV, MLASparseKV)):
            return self._decode_mla(query, cache, lengths, scale=scale,
                                    sfa_k=sfa_k)
        h = query.q.shape[2]
        if isinstance(cache, FeatureMajorKV):
            # the persistent image is dense: no stored code to re-threshold,
            # so a draft pass narrows the *query* support to k' (the image
            # layout's cost is query feature rows, not cache entries)
            return self._decode_feature_major(query, cache, lengths,
                                              scale=scale, window=window,
                                              sfa_k=draft_k or sfa_k)
        nmax = cache.v.shape[1]
        if isinstance(cache, SparseKV):
            p = rope_protect
            qs = _st_protect(query.q, draft_k or sfa_k, p)[:, 0]  # (b, h, d)
            kv_c, ki_c = cache.k_vals, unpack_indices(cache.k_idx)
            if draft_k:
                # nested-k draft: re-threshold the stored top-k codes to k'
                # (sub_k before the GQA repeat — group-size-x cheaper)
                kv_c, ki_c = sub_k(kv_c, ki_c, draft_k)
            kv_r = expand_kv(kv_c, h)                        # (b, n, h, k)
            ki_r = expand_kv(ki_c, h)
            s = _gather_score(qs[..., p:] if p else qs, kv_r, ki_r, scale)
            if p:
                kp = expand_kv(cache.k_protect, h)           # (b, n, h, p)
                s = s + jnp.einsum(
                    "bhp,bnhp->bnh",
                    query.q[:, 0, :, :p].astype(jnp.float32),
                    kp.astype(jnp.float32)) * scale
        else:
            kr = expand_kv(cache.k, h)
            s = jnp.einsum("bqhd,bnhd->bnh",
                           query.q.astype(jnp.float32),
                           kr.astype(jnp.float32)) * scale
        ok = _prefix_mask(nmax, lengths, window)
        s = jnp.where(ok[..., None], s, NEG_INF)
        pr = jax.nn.softmax(s, axis=1)                       # over n
        vr = expand_kv(cache.v, h)
        return jnp.einsum("bnh,bnhd->bhd", pr, vr.astype(jnp.float32))

    def verify(self, query: DecodeQuery, cache: KVCache, lengths, *,
               scale, window, sfa_k, rope_protect, block_n=128):
        # oracle verify: each drafted query is exactly a single-token decode
        # at its own causal length — the same vmapped-oracle arithmetic the
        # chunked-prefill path scores with (bit-identical by construction)
        def one(qt, ln):
            return self.decode(DecodeQuery(q=qt[None, None]), cache,
                               ln[None], scale=scale, window=window,
                               sfa_k=sfa_k, rope_protect=rope_protect)[0]
        return jax.vmap(one)(query.q[0], jnp.asarray(lengths, jnp.int32))

    def _decode_feature_major(self, query, cache, lengths, *, scale, window,
                              sfa_k):
        """Persistent-image oracle: sparse q against the dense (d, n)
        feature-major K image and the kernel-native heads-major V — same
        math the pallas_fm kernel streams."""
        h = query.q.shape[2]
        nmax = cache.k_feat.shape[-1]
        qs = topk_st(query.q, sfa_k)[:, 0]                   # (b, h, d)
        kf = _expand_feature_major(cache.k_feat, h)          # (b, h, d, n)
        s = jnp.einsum("bhd,bhdn->bnh", qs.astype(jnp.float32),
                       kf.astype(jnp.float32)) * scale
        ok = _prefix_mask(nmax, lengths, window)
        s = jnp.where(ok[..., None], s, NEG_INF)
        pr = jax.nn.softmax(s, axis=1)                       # over n
        vr = _expand_feature_major(cache.v, h)               # (b, h, n, dv)
        return jnp.einsum("bnh,bhnd->bhd", pr, vr.astype(jnp.float32))

    def _decode_mla(self, query, cache, lengths, *, scale, sfa_k):
        nmax = cache.ckv.shape[1]
        if isinstance(cache, MLASparseKV):
            # packed sparse-latent scoring: codes are head-independent (one
            # per token), so the gather runs on the token axis only —
            # O(n·k) touched latent bytes, no per-head gather pathology
            qlat = topk_st(query.q, sfa_k)[:, 0]             # (b, h, r)
            idx = unpack_indices(cache.ckv_sp_idx)           # (b, n, k)
            qb = jnp.broadcast_to(
                qlat[:, None].astype(jnp.float32),
                (qlat.shape[0], nmax) + qlat.shape[1:])      # (b, n, h, r)
            qg = jnp.take_along_axis(
                qb, jnp.broadcast_to(idx[:, :, None],
                                     idx.shape[:2] + (qlat.shape[1],)
                                     + idx.shape[2:]), axis=-1)  # (b, n, h, k)
            s = (qg * cache.ckv_sp_vals[:, :, None].astype(jnp.float32)
                 ).sum(-1) * scale
        else:
            s = jnp.einsum("bqhr,bnr->bnh", query.q.astype(jnp.float32),
                           cache.ckv.astype(jnp.float32)) * scale
        s = s + jnp.einsum("bqhp,bnp->bnh",
                           query.q_pe.astype(jnp.float32),
                           cache.kpe.astype(jnp.float32)) * scale
        ok = _prefix_mask(nmax, lengths, None)
        s = jnp.where(ok[..., None], s, NEG_INF)
        pr = jax.nn.softmax(s, axis=1)
        return jnp.einsum("bnh,bnr->bhr", pr,
                          cache.ckv.astype(jnp.float32))


# --------------------------------------------------------------------------
# Pallas backends
# --------------------------------------------------------------------------

class PallasBackend(AttentionBackend):
    """Fused rtopk→FlashSFA (full) + token-major sparse decode kernel."""
    name = "pallas"
    caps = Capabilities(full=True, decode=True, causal=True,
                        bidirectional=True, window=False, rope_protect=False,
                        mla=False, sparse=True, dense=True,
                        differentiable=True, paged=True, speculative=True)

    def __init__(self, bwd: str = "pallas"):
        self._bwd = bwd

    def unsupported_reason(self, req):
        r = super().unsupported_reason(req)
        if r is not None:
            return r
        if req.mode == "decode" and not req.sparse:
            return "dense KV cache: no Pallas dense-decode kernel"
        return None

    def full(self, q, k, v, *, num_heads, sfa_k, rope_protect, causal,
             window, scale, bwd_emit="dense"):
        k = expand_kv(k, num_heads)
        v = expand_kv(v, num_heads)
        if sfa_k is not None:
            return sfa_attention_op(q, k, v, sfa_k=sfa_k, causal=causal,
                                    scale=scale, impl="pallas",
                                    bwd_impl=self._bwd, bwd_emit=bwd_emit)
        return dense_attention_op(q, k, v, causal=causal, scale=scale,
                                  impl="pallas")

    def decode(self, query: DecodeQuery, cache: SparseKV, lengths, *,
               scale, window, sfa_k, rope_protect, draft_k=None):
        b, _, h, d = query.q.shape
        qs = topk_st(query.q[:, 0], draft_k or sfa_k)        # (b, h, d)
        if isinstance(cache, PagedSparseKV):
            kv_p, ki_p = cache.k_vals, cache.k_idx
            if draft_k:
                # nested-k draft: narrow the pools to their top-k' sub-codes
                # (sub_k runs on the (hkv, P, page, k) leaves directly), so
                # the kernel streams (page, k') tiles — the k'/k read cut
                # the draft pass exists for. Unpacking is part of the
                # narrowing copy; the full-k pass below never pays it.
                kv_p, ki_p = sub_k(kv_p, unpack_indices(ki_p), draft_k)
            # paged kernel reads the shared pools in place through the
            # block table (scalar-prefetched index maps): no per-step
            # gather, no head repeat, and the packed uint8 indices are
            # unpacked per-tile in VMEM
            o = flash_sfa_decode_paged(
                qs.reshape(b * h, d), kv_p, ki_p, cache.v,
                cache.block_table, lengths + 1, d=d, scale=scale,
                heads=h)
            return o.reshape(b, h, -1)
        kv_c, ki_c = cache.k_vals, unpack_indices(cache.k_idx)
        if draft_k:
            kv_c, ki_c = sub_k(kv_c, ki_c, draft_k)
        kv = _fold_expand(kv_c, h)                           # (b*h, n, k)
        ki = _fold_expand(ki_c, h)
        # f32 V: the kernel emits in V's dtype; keep the f32 accumulator
        # precision end-to-end so greedy tokens match the XLA oracle exactly
        vf = _fold_expand(cache.v, h).astype(jnp.float32)
        lens = jnp.repeat(lengths + 1, h)                    # incl. new token
        o = flash_sfa_decode(qs.reshape(b * h, d), kv, ki, vf,
                             lens, d=d, scale=scale)
        return o.reshape(b, h, -1)

    def verify(self, query: DecodeQuery, cache: SparseKV, lengths, *,
               scale, window, sfa_k, rope_protect, block_n=128):
        # one slot's contiguous (gather_slot) view, C queries, one launch:
        # the multi kernel shares each cache tile across the C queries via
        # its (b % heads, n, 0) index maps. ``block_n`` arrives as the
        # serving page size, so every tile matches the paged decode
        # kernel's accumulation order — verify logits are bit-identical to
        # the sequential decode logits the acceptance rule compares against.
        _, cq, h, d = query.q.shape
        qs = topk_st(query.q[0], sfa_k)                      # (C, h, d)
        kv = _fold_expand(cache.k_vals, h)                   # (h, n, k)
        ki = _fold_expand(unpack_indices(cache.k_idx), h)
        vf = _fold_expand(cache.v, h)
        lens = jnp.repeat(jnp.asarray(lengths, jnp.int32) + 1, h)
        o = flash_sfa_decode_multi(qs.reshape(cq * h, d), kv, ki, vf, lens,
                                   d=d, scale=scale, heads=h,
                                   block_n=block_n)
        return o.reshape(cq, h, -1)


# Debug switch for the pallas_fm persistent-image integrity check (set via
# ``set_fm_debug`` / ``--fm-debug`` on the serve launcher). Off by default:
# the check re-derives the feature-major image from its own columns, which
# costs exactly the re-materialization the persistent cache retired.
_FM_DEBUG = False


def set_fm_debug(enabled: bool) -> None:
    """Toggle the ``pallas_fm`` persistent-image integrity assertion.

    The flag is read at *trace* time, so the engine's cached decode
    executables are dropped here — engines built after this call pick the
    new setting up; engines already constructed keep the behavior they
    were traced with (they hold their compiled functions directly)."""
    global _FM_DEBUG
    _FM_DEBUG = bool(enabled)
    from repro.serve.engine import _jitted_fns, _paged_jitted_fns
    from repro.serve.speculative import _spec_jitted_fns
    _jitted_fns.cache_clear()
    _paged_jitted_fns.cache_clear()
    _spec_jitted_fns.cache_clear()


def _assert_fm_image_equal(persistent, recomputed):
    if not np.array_equal(np.asarray(persistent, np.float32),
                          np.asarray(recomputed, np.float32)):
        bad = int((np.asarray(persistent, np.float32) !=
                   np.asarray(recomputed, np.float32)).sum())
        raise AssertionError(
            f"FeatureMajorKV image diverged from its recomputed form on "
            f"{bad} entries — a stale column survived an incremental "
            f"write/insert_slot (image columns must stay <= k-sparse)")


def _debug_check_fm_image(kfeat, sfa_k):
    """Assert the persistent (bh, d, n) image equals the image recomputed
    from its own columns (sparsify -> to_feature_major). Incremental
    maintenance can only corrupt the image by leaving *stale* entries
    behind, which makes a column more than k-sparse — the recomputed image
    then drops them and the equality fails. ``to_feature_major`` lives on
    as this oracle; the hot decode path never calls it."""
    tm = jnp.swapaxes(kfeat, -1, -2)                         # (bh, n, d)
    recomputed = to_feature_major(sparsify(tm, min(sfa_k, tm.shape[-1])))
    if isinstance(kfeat, jax.core.Tracer):
        jax.debug.callback(_assert_fm_image_equal, kfeat, recomputed)
    else:
        _assert_fm_image_equal(kfeat, recomputed)


class PallasFMBackend(AttentionBackend):
    """Feature-major decode: the sparse *query* selects which k of the d
    feature rows to stream (DESIGN.md §2, beyond-paper layout).

    The serving cache is the persistent ``FeatureMajorKV``: the dense
    (d, n) K image is maintained incrementally by the cache's own
    ``write``/``insert_slot`` and read here as-is — zero per-step
    re-materialization, so the kernel's O(nk) feature-row reads are the
    step's actual HBM traffic (``persistent_cache`` capability drives the
    allocator to this layout).
    """
    name = "pallas_fm"
    caps = Capabilities(full=False, decode=True, causal=True,
                        bidirectional=True, window=False, rope_protect=False,
                        mla=False, sparse=True, dense=False,
                        differentiable=False, persistent_cache=True,
                        paged=True)

    def decode(self, query: DecodeQuery, cache: FeatureMajorKV, lengths, *,
               scale, window, sfa_k, rope_protect, draft_k=None):
        if not isinstance(cache, (FeatureMajorKV, PagedFeatureMajorKV)):
            raise TypeError(
                f"pallas_fm serves the persistent FeatureMajorKV cache, got "
                f"{type(cache).__name__} — allocate caches through "
                f"init_cache/init_decode_caches so the layout follows the "
                f"selected backend")
        b, _, h, d = query.q.shape
        # speculative draft pass: the K image is dense feature-major and
        # cannot be re-thresholded after the fact, so drafting narrows the
        # QUERY side only — k' feature rows streamed instead of k
        # (DESIGN.md §6's documented layout exception)
        code = sparsify(query.q[:, 0], min(draft_k or sfa_k, d))  # (b, h, k)
        kq = code.values.shape[-1]
        qv = code.values.reshape(b * h, kq)
        qi = code.indices.reshape(b * h, kq)
        if isinstance(cache, PagedFeatureMajorKV):
            # paged persistent image: (hkv, P, d, page) pool read in place
            # through the block table; the kernel's qi index map selects the
            # k feature rows *per page*, so per-step traffic stays O(n·k)
            if _FM_DEBUG:
                g = cache.gather()                           # (s, hkv, d, n)
                s_, hkv_, d_, n_ = g.k_feat.shape
                _debug_check_fm_image(
                    g.k_feat.reshape(s_ * hkv_, d_, n_), sfa_k)
            o = flash_sfa_decode_fm_paged(
                qv, qi, cache.k_feat, cache.v, cache.block_table,
                lengths + 1, scale=scale, heads=h)
            return o.reshape(b, h, -1)
        hkv, nmax = cache.k_feat.shape[1], cache.k_feat.shape[-1]
        # zero per-step copies: both cache leaves are stored kernel-native
        # (heads-major), so the flat (b*hkv, ...) views are reshapes, and
        # GQA is served by the kernel's i // group index maps rather than a
        # materialized head repeat. The kernel accumulates and emits f32,
        # so bf16-at-rest V still matches the oracle's precision.
        kfeat = cache.k_feat.reshape(b * hkv, d, nmax)
        if _FM_DEBUG:
            _debug_check_fm_image(kfeat, sfa_k)
        vf = cache.v.reshape(b * hkv, nmax, -1)
        lens = jnp.repeat(lengths + 1, h)
        o = flash_sfa_decode_fm(qv, qi, kfeat, vf, lens, scale=scale,
                                group=h // hkv)
        return o.reshape(b, h, -1)


# --------------------------------------------------------------------------
# registry + selection
# --------------------------------------------------------------------------

_REGISTRY: dict[str, AttentionBackend] = {}


def register_backend(backend: AttentionBackend) -> AttentionBackend:
    _REGISTRY[backend.name] = backend
    return backend


def backend_names() -> tuple:
    return tuple(_REGISTRY)


def get_backend(name: str) -> AttentionBackend:
    if name not in _REGISTRY:
        raise ValueError(f"unknown attention backend {name!r}; "
                         f"registered: {backend_names()}")
    return _REGISTRY[name]


register_backend(XLABackend())
register_backend(PallasBackend())
register_backend(PallasFMBackend())

def _auto_order() -> tuple:
    """auto-selection preference: compiled Pallas kernels on TPU; the XLA
    paths everywhere else (interpret-mode Pallas is a correctness tool, not
    serving). Asked at selection time, not at import, so the answer follows
    the backend JAX actually initialized."""
    return ("pallas", "xla") if jax.default_backend() == "tpu" \
        else ("xla", "pallas")


@dataclasses.dataclass(frozen=True)
class BackendSelection:
    backend: AttentionBackend
    requested: str
    reason: Optional[str] = None     # set when the request fell back


@dataclasses.dataclass(frozen=True)
class FallbackReport:
    """Structured record of a capability-driven backend fallback."""
    requested: str
    selected: str
    reason: str
    request: AttentionRequest
    where: str = ""


_FALLBACKS: dict = {}


def fallback_reports() -> tuple:
    """All deduped fallbacks observed since the last clear (trace-time:
    one per distinct (backend, request, site), not per step)."""
    return tuple(_FALLBACKS.values())


def clear_fallback_reports() -> None:
    _FALLBACKS.clear()


def resolve_backend_name(name: str, req: AttentionRequest) -> str:
    """Pure resolution: which backend *would* serve ``req`` under ``name``.

    Same routing as ``select_backend`` but with no fallback recording or
    logging — for eligibility probes (e.g. the ``remat="codes"`` check asks
    whether the stack's forward runs through the code-tagging pallas paths
    without charging a FallbackReport to a site that never traces)."""
    if name == "auto":
        for nm in _auto_order():
            b = _REGISTRY.get(nm)
            if b is not None and b.unsupported_reason(req) is None:
                return nm
        return "xla"
    if get_backend(name).unsupported_reason(req) is None:
        return name
    return "xla"


def select_backend(name: str, req: AttentionRequest, *,
                   where: str = "") -> BackendSelection:
    """Resolve a backend name (or "auto") against a request.

    An explicitly requested backend that cannot serve the request falls
    back to the ``xla`` oracle and the reason is recorded exactly once per
    (name, request, site) — the single surfacing point for what the old
    code spread across trace-time ``warnings.warn`` calls.
    """
    if name == "auto":
        for nm in _auto_order():
            b = _REGISTRY.get(nm)
            if b is not None and b.unsupported_reason(req) is None:
                return BackendSelection(b, "auto")
        return BackendSelection(get_backend("xla"), "auto")
    backend = get_backend(name)
    reason = backend.unsupported_reason(req)
    if reason is None:
        return BackendSelection(backend, name)
    fallback = get_backend("xla")
    key = (name, req, where)
    if key not in _FALLBACKS:
        _FALLBACKS[key] = FallbackReport(requested=name, selected=fallback.name,
                                         reason=reason, request=req,
                                         where=where)
        _LOG.warning(
            "attention backend fallback: requested=%r -> %r (%s) "
            "[mode=%s%s] — %s-vs-%s comparisons on this config are void",
            name, fallback.name, reason, req.mode,
            f", at {where}" if where else "", name, fallback.name)
    return BackendSelection(fallback, name, reason)


# unified report protocol (core/reports.py): every FallbackReport is a
# not-eligible routing decision of the "backend" component. The native
# ``fallback_reports()`` accessor stays; this is a read-only view.
def _collect_backend_reports():
    return tuple(
        _reports.make_report(
            "backend", f.where, eligible=False, reason=f.reason,
            details={"requested": f.requested, "selected": f.selected,
                     "mode": f.request.mode})
        for f in fallback_reports())


_reports.register_provider("backend", _collect_backend_reports,
                           clear_fallback_reports)
