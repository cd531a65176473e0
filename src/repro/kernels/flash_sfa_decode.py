"""FlashSFA decode kernels — the memory-bound case the paper targets.

Two KV-cache layouts (DESIGN.md §2):

1. ``flash_sfa_decode`` (paper-faithful, token-major): K̃ cache stored as
   ``(n, k)`` values + indices. HBM traffic per step: ``n·k·(val+idx bytes)``
   for K instead of ``n·d`` dense — the paper's O(nk) claim, realized on TPU
   by densifying each cache tile in VMEM (one-hot) and a dense MXU matvec.
   KV-cache memory shrinks by ≈ 2d/(3k+4) on the K half (Appendix J).

2. ``flash_sfa_decode_fm`` (beyond-paper, feature-major): K cache stored
   dense ``(d, n)`` feature-major; the *query's* sparse support selects which
   k of the d feature rows to stream. Scalar-prefetched q-indices drive the
   BlockSpec index map, so only k rows ever leave HBM: O(nk) traffic AND an
   O(nk) MXU contraction (a real k/d FLOP cut with zero scatter). Trades
   cache capacity for bandwidth+FLOPs — benchmarked against layout 1 in
   EXPERIMENTS.md §Perf. The image is *persistent* in ``FeatureMajorKV``:
   ``feature_major_prefill`` below builds it once from the prefill's top-k
   codes, ``KVCache.write`` extends it one column per decoded token, and the
   kernel reads it as-is — no per-step re-materialization anywhere.

Both kernels mask by a runtime ``length`` (scalar-prefetched), support
pre-allocated over-length caches, and use online softmax across sequential
cache tiles.

Each layout also has a *paged* variant (``flash_sfa_decode_paged`` /
``flash_sfa_decode_fm_paged``) reading the shared page pools of the
``PagedKV`` caches: the block table is scalar-prefetched alongside the
lengths, and the BlockSpec index maps fetch pool page ``bt[slot, n]`` for
grid step ``n`` — block-table indirection costs zero extra HBM traffic.
The page size IS the kernel tile (``block_n``), and a slot's logical pages
are visited in token order, so the online-softmax accumulation is
bit-identical to the contiguous kernels given the same cache content
(DESIGN.md §5). Unlike the contiguous token-major path, the paged kernel
reads KV straight from the hkv-head pool via its index maps — no per-step
GQA head-repeat or unpack copy of the whole cache is ever materialized.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sparse import SparseCode, to_feature_major
from repro.kernels._compat import resolve_interpret
from repro.kernels.flash_sfa import _densify_block

NEG_INF = -1e30
LANES = 128

# Every per-query operand (q rows, q codes, outputs) travels as a
# (rows, 1, width) array with (1, 1, width) blocks: a (1, width) block of a
# (rows, width) array breaks Mosaic's (8, 128) tiling rule.


def _init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _online_update(s, vb, m_ref, l_ref, acc_ref):
    """One online-softmax step: (1, bn) scores against a (bn, dv) V tile."""
    m_prev = m_ref[:, :1]                                    # (1, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_ref[:, :1] * corr + p.sum(axis=-1, keepdims=True)
    pv = jax.lax.dot_general(p, vb, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (1, dv)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _finalize(o_ref, l_ref, acc_ref):
    o_ref[0] = (acc_ref[...] /
                jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def _state_scratch(dv):
    return [pltpu.VMEM((1, LANES), jnp.float32),
            pltpu.VMEM((1, LANES), jnp.float32),
            pltpu.VMEM((1, dv), jnp.float32)]


def _token_scores(q_ref, kv, ki, *, d, scale, start, block_n, length):
    """(1, bn) masked scores of the query row against one code tile."""
    kd = _densify_block(kv, ki.astype(jnp.int32), d)         # (bn, d)
    q = q_ref[0].astype(jnp.float32)                         # (1, d)
    s = jax.lax.dot_general(
        q, kd, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale          # (1, bn)
    pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
    return jnp.where(pos < length, s, NEG_INF)


# --------------------------------------------------------------------------
# Layout 1: token-major sparse K cache (paper-faithful)
# --------------------------------------------------------------------------

def _decode_kernel(len_ref, q_ref, kv_ref, ki_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, d: int, scale: float,
                   block_n: int):
    b = pl.program_id(0)
    nb = pl.program_id(1)
    nnb = pl.num_programs(1)
    length = len_ref[b]

    @pl.when(nb == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    @pl.when(nb * block_n < length)
    def _compute():
        s = _token_scores(q_ref, kv_ref[0], ki_ref[0], d=d, scale=scale,
                          start=nb * block_n, block_n=block_n, length=length)
        _online_update(s, v_ref[0].astype(jnp.float32), m_ref, l_ref,
                       acc_ref)

    @pl.when(nb == nnb - 1)
    def _fin():
        _finalize(o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("d", "scale", "block_n", "interpret"))
def flash_sfa_decode(q, k_vals, k_idx, v, lengths, *, d: int,
                     scale: float | None = None, block_n: int = 128,
                     interpret: bool | None = None):
    """Token-major sparse-cache decode.

    q: (bh, d) dense query (one token); k_vals/k_idx: (bh, n_max, k);
    v: (bh, n_max, dv); lengths: (bh,) int32. -> (bh, dv)
    """
    bh, nmax, kk = k_vals.shape
    dv = v.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    pad = (-nmax) % block_n
    if pad:
        k_vals = jnp.pad(k_vals, ((0, 0), (0, pad), (0, 0)))
        k_idx = jnp.pad(k_idx, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    grid = (bh, (nmax + pad) // block_n)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, d=d, scale=scale, block_n=block_n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, d), lambda b, n, L: (b, 0, 0)),
                pl.BlockSpec((1, block_n, kk), lambda b, n, L: (b, n, 0)),
                pl.BlockSpec((1, block_n, kk), lambda b, n, L: (b, n, 0)),
                pl.BlockSpec((1, block_n, dv), lambda b, n, L: (b, n, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, dv), lambda b, n, L: (b, 0, 0)),
            scratch_shapes=_state_scratch(dv),
        ),
        out_shape=jax.ShapeDtypeStruct((bh, 1, dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(lengths, jnp.int32), q[:, None], k_vals, k_idx, v)
    return out[:, 0]


def _decode_paged_kernel(bt_ref, len_ref, q_ref, kv_ref, ki_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, d: int, scale: float,
                         page: int, heads: int):
    b = pl.program_id(0)              # slot * heads + query head
    nb = pl.program_id(1)             # logical page within the slot
    nnb = pl.num_programs(1)
    length = len_ref[b // heads]

    @pl.when(nb == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    @pl.when(nb * page < length)
    def _compute():
        # kv/ki blocks are pool page bt[slot, nb] (index-map fetched);
        # indices are stored packed — unpack in VMEM, not the whole pool
        s = _token_scores(q_ref, kv_ref[0, 0], ki_ref[0, 0], d=d,
                          scale=scale, start=nb * page, block_n=page,
                          length=length)
        _online_update(s, v_ref[0, 0].astype(jnp.float32), m_ref, l_ref,
                       acc_ref)

    @pl.when(nb == nnb - 1)
    def _fin():
        _finalize(o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("d", "scale", "heads",
                                             "interpret"))
def flash_sfa_decode_paged(q, kv_pool, ki_pool, v_pool, block_tables,
                           lengths, *, d: int, scale: float | None = None,
                           heads: int = 1, interpret: bool | None = None):
    """Token-major sparse-cache decode over a paged pool.

    q: (slots*heads, d) dense query; kv_pool/ki_pool: (hkv, P, page, k)
    (indices packed at rest — unpacked per tile in VMEM); v_pool:
    (hkv, P, page, dv); block_tables: (slots, max_pages) int32;
    lengths: (slots,) incl. the just-written token. -> (slots*heads, dv) f32
    (accumulator dtype, so bf16-at-rest pools keep oracle precision with no
    whole-pool upcast). GQA is served by the ``(b % heads) // group`` index
    maps — the head repeat the contiguous path materializes never exists.
    """
    bh = q.shape[0]
    hkv, _, page, kk = kv_pool.shape
    dv = v_pool.shape[-1]
    group = heads // hkv
    mp = block_tables.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    grid = (bh, mp)
    out = pl.pallas_call(
        functools.partial(_decode_paged_kernel, d=d, scale=scale, page=page,
                          heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, d), lambda b, n, bt, L: (b, 0, 0)),
                # block-table indirection: grid step n streams pool page
                # bt[slot, n] of the slot's kv head — same tile, same order
                # as the contiguous kernel's (b, n) block
                pl.BlockSpec((1, 1, page, kk),
                             lambda b, n, bt, L: ((b % heads) // group,
                                                  bt[b // heads, n], 0, 0)),
                pl.BlockSpec((1, 1, page, kk),
                             lambda b, n, bt, L: ((b % heads) // group,
                                                  bt[b // heads, n], 0, 0)),
                pl.BlockSpec((1, 1, page, dv),
                             lambda b, n, bt, L: ((b % heads) // group,
                                                  bt[b // heads, n], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, dv),
                                   lambda b, n, bt, L: (b, 0, 0)),
            scratch_shapes=_state_scratch(dv),
        ),
        out_shape=jax.ShapeDtypeStruct((bh, 1, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
      q[:, None], kv_pool, ki_pool, v_pool)
    return out[:, 0]


def _decode_multi_kernel(len_ref, q_ref, kv_ref, ki_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, d: int, scale: float,
                         block_n: int, heads: int):
    b = pl.program_id(0)              # query position * heads + head
    nb = pl.program_id(1)
    nnb = pl.num_programs(1)
    length = len_ref[b]               # per query row: cache_len + pos + 1

    @pl.when(nb == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    @pl.when(nb * block_n < length)
    def _compute():
        s = _token_scores(q_ref, kv_ref[0], ki_ref[0], d=d, scale=scale,
                          start=nb * block_n, block_n=block_n, length=length)
        _online_update(s, v_ref[0].astype(jnp.float32), m_ref, l_ref,
                       acc_ref)

    @pl.when(nb == nnb - 1)
    def _fin():
        _finalize(o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("d", "scale", "heads", "block_n",
                                             "interpret"))
def flash_sfa_decode_multi(q, k_vals, k_idx, v, lengths, *, d: int,
                           scale: float | None = None, heads: int = 1,
                           block_n: int = 128, interpret: bool | None = None):
    """Multi-token verify over ONE slot's token-major sparse cache.

    The speculative verify pass scores C = draft_len + 1 query tokens
    against the same cache in one launch: q ``(C*heads, d)`` dense queries;
    k_vals/k_idx ``(heads, n_max, k)`` (one slot's cache, already folded to
    query heads); v ``(heads, n_max, dv)``; lengths ``(C*heads,)`` — the
    *per-query* causal lengths ``cache_len + pos + 1``, so draft position j
    sees exactly the prefix a sequential decode at that step would see.
    -> ``(C*heads, dv)`` f32.

    The cache BlockSpec index maps are ``(b % heads, n, 0)``: all C queries
    of a head stream the same tiles — the cache is fetched once per (head,
    tile), not per query, which is what makes one batched full-k pass
    cheaper than C sequential decodes. ``block_n`` should be set to the
    serving page size so the online-softmax accumulation visits tokens in
    exactly the paged decode kernel's tile order (bit-identical logits —
    the greedy acceptance rule compares argmaxes across the two paths).
    """
    bh = q.shape[0]
    _, nmax, kk = k_vals.shape
    dv = v.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    pad = (-nmax) % block_n
    if pad:
        k_vals = jnp.pad(k_vals, ((0, 0), (0, pad), (0, 0)))
        k_idx = jnp.pad(k_idx, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    grid = (bh, (nmax + pad) // block_n)
    out = pl.pallas_call(
        functools.partial(_decode_multi_kernel, d=d, scale=scale,
                          block_n=block_n, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, d), lambda b, n, L: (b, 0, 0)),
                pl.BlockSpec((1, block_n, kk), lambda b, n, L: (b % heads, n, 0)),
                pl.BlockSpec((1, block_n, kk), lambda b, n, L: (b % heads, n, 0)),
                pl.BlockSpec((1, block_n, dv), lambda b, n, L: (b % heads, n, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, dv), lambda b, n, L: (b, 0, 0)),
            scratch_shapes=_state_scratch(dv),
        ),
        out_shape=jax.ShapeDtypeStruct((bh, 1, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(lengths, jnp.int32), q[:, None], k_vals, k_idx, v)
    return out[:, 0]


# --------------------------------------------------------------------------
# Layout 2: feature-major dense K cache + sparse query (beyond-paper)
# --------------------------------------------------------------------------

def feature_major_prefill(k_vals, k_idx, d: int):
    """Prefill-write path for the persistent ``FeatureMajorKV`` image.

    Scatters the prefill's token-major top-k K codes into the dense
    feature-major layout the decode kernel streams:

        k_vals/k_idx (b, n, hkv, k) int32-indexed codes -> (b, hkv, d, n)

    Runs once per prompt (``to_feature_major`` is the shared scatter —
    DESIGN.md §2), after which ``KVCache.write`` maintains the image
    incrementally and the per-step decode performs zero layout transforms.
    """
    return to_feature_major(SparseCode(
        values=jnp.moveaxis(k_vals, 1, 2),                   # (b, hkv, n, k)
        indices=jnp.moveaxis(k_idx, 1, 2), dim=d))           # -> (b, hkv, d, n)



def _feature_rows(image):
    """(..., d, n) image -> (..., d, 1, n): one feature row per block.

    Mosaic fetches whole (8, 128) tiles, so a single row of a (d, n) image
    cannot be a block; with a unit second-minor axis it can. On the chip
    this reshape is a relayout copy of the whole image per call — the
    persistent cache should store this layout itself (ROADMAP, Speed 1).
    """
    return image[..., None, :]


def _code_value(qv_ref, t):
    """(1, 1) value of the query code's slot ``t`` (a grid index): a masked
    lane sum, since a dynamic lane index into a vector does not lower."""
    qv = qv_ref[0].astype(jnp.float32)                       # (1, k)
    lane = jax.lax.broadcasted_iota(jnp.int32, qv.shape, 1)
    return jnp.sum(jnp.where(lane == t, qv, 0.0), axis=-1, keepdims=True)


def _decode_fm_kernel(qi_ref, len_ref, qv_ref, kf_ref, v_ref, o_ref,
                      s_ref, m_ref, l_ref, acc_ref, *, scale: float,
                      block_n: int, kq: int):
    b = pl.program_id(0)
    nb = pl.program_id(1)
    t = pl.program_id(2)
    nnb = pl.num_programs(1)
    length = len_ref[b]

    @pl.when((nb == 0) & (t == 0))
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    @pl.when(t == 0)
    def _clear_scores():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(nb * block_n < length)
    def _accumulate():
        # kf_ref block is the single feature row qi[b, t] of the cache:
        # shape (1, 1, 1, block_n). Accumulate qv[t] * K_feat[row, tile].
        s_ref[...] = s_ref[...] + _code_value(qv_ref, t) * \
            kf_ref[0, 0].astype(jnp.float32)

    @pl.when((t == kq - 1) & (nb * block_n < length))
    def _softmax_update():
        s = s_ref[...] * scale                                   # (1, bn)
        pos = nb * block_n + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
        s = jnp.where(pos < length, s, NEG_INF)
        _online_update(s, v_ref[0].astype(jnp.float32), m_ref, l_ref,
                       acc_ref)

    @pl.when((nb == nnb - 1) & (t == kq - 1))
    def _fin():
        _finalize(o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("scale", "block_n", "group",
                                             "interpret"))
def flash_sfa_decode_fm(q_vals, q_idx, k_feat, v, lengths, *,
                        scale: float | None = None, block_n: int = 128,
                        group: int = 1, interpret: bool | None = None):
    """Feature-major decode: sparse query gathers k feature rows of the cache.

    q_vals/q_idx: (bh, k); k_feat: (bh // group, d, n_max);
    v: (bh // group, n_max, dv); lengths: (bh,). -> (bh, dv) in f32 (the
    accumulator dtype — bf16-at-rest caches keep oracle precision without
    an upcast copy outside the kernel). Only the k addressed rows of k_feat
    are fetched from HBM (index map driven by scalar-prefetched q_idx).
    ``group`` is the GQA group size (query heads per kv head): query row i
    reads image/V row i // group through the BlockSpec index maps, so one
    persistent image serves the whole group — no h-fold repeat is ever
    materialized.
    """
    bh, kq = q_vals.shape
    d, nmax = k_feat.shape[1], k_feat.shape[2]
    dv = v.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    pad = (-nmax) % block_n
    if pad:
        k_feat = jnp.pad(k_feat, ((0, 0), (0, 0), (0, pad)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    grid = (bh, (nmax + pad) // block_n, kq)
    out = pl.pallas_call(
        functools.partial(_decode_fm_kernel, scale=scale, block_n=block_n,
                          kq=kq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, kq), lambda b, n, t, qi, L: (b, 0, 0)),
                # the magic: fetch exactly feature row qi[b, t] of the
                # group's shared image
                pl.BlockSpec((1, 1, 1, block_n),
                             lambda b, n, t, qi, L: (b // group,
                                                     qi[b, t], 0, n)),
                pl.BlockSpec((1, block_n, dv),
                             lambda b, n, t, qi, L: (b // group, n, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, dv),
                                   lambda b, n, t, qi, L: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((1, block_n), jnp.float32),
                            *_state_scratch(dv)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, 1, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(q_idx, jnp.int32), jnp.asarray(lengths, jnp.int32),
      q_vals[:, None], _feature_rows(k_feat), v)
    return out[:, 0]


def _decode_fm_paged_kernel(qi_ref, bt_ref, len_ref, qv_ref, kf_ref, v_ref,
                            o_ref, s_ref, m_ref, l_ref, acc_ref, *,
                            scale: float, page: int, kq: int, heads: int):
    b = pl.program_id(0)
    nb = pl.program_id(1)
    t = pl.program_id(2)
    nnb = pl.num_programs(1)
    length = len_ref[b // heads]

    @pl.when((nb == 0) & (t == 0))
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    @pl.when(t == 0)
    def _clear_scores():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(nb * page < length)
    def _accumulate():
        # kf block is feature row qi[b, t] of pool page bt[slot, nb]:
        # shape (1, 1, 1, 1, page)
        s_ref[...] = s_ref[...] + _code_value(qv_ref, t) * \
            kf_ref[0, 0, 0].astype(jnp.float32)

    @pl.when((t == kq - 1) & (nb * page < length))
    def _softmax_update():
        s = s_ref[...] * scale                                # (1, page)
        pos = nb * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        s = jnp.where(pos < length, s, NEG_INF)
        _online_update(s, v_ref[0, 0].astype(jnp.float32), m_ref, l_ref,
                       acc_ref)

    @pl.when((nb == nnb - 1) & (t == kq - 1))
    def _fin():
        _finalize(o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("scale", "heads", "interpret"))
def flash_sfa_decode_fm_paged(q_vals, q_idx, kf_pool, v_pool, block_tables,
                              lengths, *, scale: float | None = None,
                              heads: int = 1, interpret: bool | None = None):
    """Feature-major decode over a paged image pool.

    q_vals/q_idx: (slots*heads, k); kf_pool: (hkv, P, d, page) — each pool
    page is a (d, page) tile of the persistent image; v_pool:
    (hkv, P, page, dv); block_tables: (slots, max_pages); lengths: (slots,).
    -> (slots*heads, dv) f32. Two levels of index-map indirection compose:
    the scalar-prefetched block table picks the pool page, the
    scalar-prefetched q-indices pick the k feature rows inside it — still
    only O(n·k) image bytes leave HBM.
    """
    bh, kq = q_vals.shape
    hkv, _, d, page = kf_pool.shape
    dv = v_pool.shape[-1]
    group = heads // hkv
    mp = block_tables.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    grid = (bh, mp, kq)
    out = pl.pallas_call(
        functools.partial(_decode_fm_paged_kernel, scale=scale, page=page,
                          kq=kq, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, kq), lambda b, n, t, qi, bt, L: (b, 0, 0)),
                pl.BlockSpec((1, 1, 1, 1, page),
                             lambda b, n, t, qi, bt, L: (
                                 (b % heads) // group,
                                 bt[b // heads, n], qi[b, t], 0, 0)),
                pl.BlockSpec((1, 1, page, dv),
                             lambda b, n, t, qi, bt, L: (
                                 (b % heads) // group,
                                 bt[b // heads, n], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, dv),
                                   lambda b, n, t, qi, bt, L: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((1, page), jnp.float32),
                            *_state_scratch(dv)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, 1, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(q_idx, jnp.int32), jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(lengths, jnp.int32), q_vals[:, None], _feature_rows(kf_pool),
      v_pool)
    return out[:, 0]
