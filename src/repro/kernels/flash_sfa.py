"""FlashSFA-TPU: IO-sparse, compute-dense tiled attention (prefill/training).

TPU adaptation of the paper's Algorithm 1 (Appendix C). The GPU kernel walks
CSR(Q)×CSC_feat(K) posting-list intersections with scatter-adds; the MXU has
no sparse path, so here each sparse tile is *densified in VMEM* with the
iota-compare one-hot idiom (k VPU passes over a (block, d) tile) and scores
come from one dense MXU matmul. HBM traffic for Q and K is O(nk) — the sparse
values+indices are all that is read — while compute runs at full MXU
throughput. Online softmax / causal masking / V streaming are identical to
FlashAttention (per-q-block running max, denominator and accumulator held in
VMEM scratch across the sequential kv-block grid axis).

``block_skip=True`` adds overlap-aware tile scheduling on top (DESIGN.md §2):
a per-tile feature-occupancy bitmap (the OR of each tile's stored indices,
masked to value-carrying entries, packed into ceil(d/32) uint32 words) is
built from the codes in one O(nk) XLA pre-pass by a bitwise-OR reduction, not
a scatter, and a (q-tile, k-tile) *level map* derived from the AND of the
words is handed to the kernel as a scalar-prefetch operand:

  * level 0 — the tile is causally dead or the q tile is fully padded:
    nothing runs, nothing is fetched.
  * level 1 — the feature intersection is empty and every (row, col) of the
    tile is unmasked: all scores are exactly 0, so the online-softmax state
    advances in closed form (m←max(m,0), l += block_k·e⁻ᵐ, acc += e⁻ᵐ·Σv)
    from a precomputed per-tile V row-sum — the K codes and the V tile are
    never read.
  * level 2 — full densify-and-MXU compute, bit-identical to the plain path.

Skipped levels also skip the HBM fetch: the K/V block index maps read a
scalar-prefetch *fetch map* that repeats the last level-2 block index, and
the TPU pipeline elides the copy when consecutive grid steps fetch the same
block. Exact softmax semantics are preserved at every level.

See DESIGN.md §2 for the napkin math on why intersection-on-VPU would lose to
densify-and-MXU at the paper's (d, k) operating points, and for the fused
forward's IO accounting.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import scopes
from repro.kernels._compat import resolve_interpret

NEG_INF = -1e30
LANES = 128
# Scalar-prefetch budget for the block-skip maps: half of a v5e core's
# 1 MiB SMEM, leaving the rest to the compiler's own scalars.
SMEM_MAP_BYTES = 512 * 1024


def lanes_to_row(x: jax.Array) -> jax.Array:
    """(b, LANES) per-row statistic broadcast over lanes -> (1, b) row.

    Per-row statistics (lse, delta, positions) travel through HBM as
    ``(bh, 1, n)`` arrays: a ``(1, block)`` block of that layout satisfies
    Mosaic's (8, 128) tiling rule, where a ``(1, block)`` block of a
    ``(bh, n)`` array does not. The relayout is one XLU transpose per tile.
    """
    return x.T[:1, :]


def row_to_column(row: jax.Array) -> jax.Array:
    """(1, b) row -> (b, 1) column: the inverse of ``lanes_to_row``."""
    b = row.shape[-1]
    return jnp.broadcast_to(row, (LANES, b)).T[:, :1]


def _densify_block(vals: jax.Array, idx: jax.Array, d: int) -> jax.Array:
    """(b, k) sparse rows -> (b, d) dense, via k iota-compare VPU passes.

    Duplicate indices SUM into their lane (each pass adds its hit), so rows
    padded with (idx=0, val=0) × k — and any fused-emit row whose duplicate
    slots carry zero values — densify to exact zeros. That duplicate-sum
    invariant is load-bearing for every padded/ragged path and is pinned by
    a hypothesis property test (tests/test_property.py).
    """
    b, k = vals.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (b, d), 1)
    out = jnp.zeros((b, d), jnp.float32)
    for t in range(k):
        hit = (iota == idx[:, t][:, None]).astype(jnp.float32)
        out = out + hit * vals[:, t][:, None].astype(jnp.float32)
    return out


def _tile_update(qv, qi, kv, ki, vb, m_ref, l_ref, acc_ref, *, d, scale,
                 causal, block_q, block_k, q_start, k_start, nk_real):
    """One (q-tile, k-tile) online-softmax step on densified codes."""
    qd = _densify_block(qv, qi, d)                         # (bq, d) f32
    kd = _densify_block(kv, ki, d)                         # (bk, d) f32
    s = jax.lax.dot_general(
        qd, kd, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # (bq, bk)
    rows = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    ok = cols < nk_real  # mask keys beyond the real sequence (padding)
    if causal:
        ok &= cols <= rows
    s = jnp.where(ok, s, NEG_INF)
    m_prev = m_ref[:, 0]                                   # (bq,)
    l_prev = l_ref[:, 0]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1)
    pv = jax.lax.dot_general(
        p, vb.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv
    m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)


def _finalize_tile(o_ref, lse_ref, m_ref, l_ref, acc_ref):
    l = l_ref[:, 0]
    o_ref[0, ...] = (acc_ref[...] /
                     jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
    if lse_ref is not None:
        # Rows that never saw a live kv tile (fully-padded q rows) finalize
        # with l=0 -> lse ~ NEG_INF. The wrapper slices them off before
        # returning, so the backward never consumes a padded-row lse
        # (asserted in tests/test_kernels.py).
        lse = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
        lse_ref[0] = lanes_to_row(lse)


def _flash_sfa_kernel(qv_ref, qi_ref, kv_ref, ki_ref, v_ref, o_ref,
                      *rest, d: int, scale: float,
                      causal: bool, block_q: int, block_k: int,
                      nq_real: int, nk_real: int, emit_lse: bool = False):
    if emit_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        lse_ref, (m_ref, l_ref, acc_ref) = None, rest
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nkb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qb * block_q
    k_start = kb * block_k
    # A tile is live unless the q tile is entirely padding (rows >= nq_real)
    # or the kv block is entirely in the causal future.
    live = q_start < nq_real
    if causal:
        live &= k_start <= q_start + block_q - 1

    @pl.when(live)
    def _compute():
        _tile_update(qv_ref[0], qi_ref[0], kv_ref[0], ki_ref[0], v_ref[0],
                     m_ref, l_ref, acc_ref, d=d, scale=scale, causal=causal,
                     block_q=block_q, block_k=block_k, q_start=q_start,
                     k_start=k_start, nk_real=nk_real)

    @pl.when(kb == nkb - 1)
    def _finalize():
        _finalize_tile(o_ref, lse_ref, m_ref, l_ref, acc_ref)


def _flash_sfa_skip_kernel(lvl_ref, ft_ref, qv_ref, qi_ref, kv_ref, ki_ref,
                           v_ref, vsum_ref, o_ref, *rest, d: int, scale: float,
                           causal: bool, block_q: int, block_k: int,
                           nk_real: int, emit_lse: bool = False):
    del ft_ref  # consumed by the K/V block index maps, not the body
    if emit_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        lse_ref, (m_ref, l_ref, acc_ref) = None, rest
    b = pl.program_id(0)
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nkb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lvl = lvl_ref[(b * pl.num_programs(1) + qb) * nkb + kb]

    @pl.when(lvl == 2)
    def _compute():
        _tile_update(qv_ref[0], qi_ref[0], kv_ref[0], ki_ref[0], v_ref[0],
                     m_ref, l_ref, acc_ref, d=d, scale=scale, causal=causal,
                     block_q=block_q, block_k=block_k, q_start=qb * block_q,
                     k_start=kb * block_k, nk_real=nk_real)

    @pl.when(lvl == 1)
    def _zero_overlap():
        # Empty feature intersection on a fully-unmasked, fully-valid tile:
        # every score is exactly 0, so the online-softmax update has the
        # closed form below — identical state to the compute path, with only
        # the (1, dv) per-tile V row-sum read instead of the K codes + V.
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, 0.0)
        corr = jnp.exp(m_prev - m_new)
        e = jnp.exp(0.0 - m_new)
        acc_ref[...] = (acc_ref[...] * corr[:, None] +
                        e[:, None] * vsum_ref[0, 0])
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(
            (l_prev * corr + block_k * e)[:, None], l_ref.shape)

    @pl.when(kb == nkb - 1)
    def _finalize():
        _finalize_tile(o_ref, lse_ref, m_ref, l_ref, acc_ref)


def _tile_occupancy(vals, idx, d: int, nblocks: int, block: int):
    """(bh, n, k) codes -> (bh, nblocks, ceil(d/32)) uint32 occupancy words.

    The d-bit OR of DESIGN.md §2b, packed: feature u of a tile is bit
    ``u & 31`` of word ``u >> 5``, set iff some entry of the tile's
    ``block · k`` stores index u with a nonzero value. Entries with value 0
    are excluded: they contribute nothing to any score, and that is exactly
    what keeps padded rows (idx=0 × k, val=0) from pinning feature 0
    occupied.

    Built as one OR reduction per word over the tile's (block, k) entries,
    never a scatter: a TPU runs an XLA scatter as a serial loop over its
    updates (~9 ns each, 1.6M of them per call at batch 4 × 4096), where
    the reductions fuse with the per-entry bit computation and read the
    codes once, with no intermediate the size of the codes.
    """
    bh, _, kq = idx.shape
    # Split n only: merging block and k into one axis is a relayout of the
    # codes (k is their minor axis), which XLA will not fuse into a reduce,
    # so the bits would be written out and read back.
    idx = idx.reshape(bh, nblocks, block, kq)
    live = vals.reshape(bh, nblocks, block, kq) != 0
    bit = jnp.where(live, jnp.left_shift(
        jnp.uint32(1), (idx & 31).astype(jnp.uint32)), jnp.uint32(0))
    word = jnp.right_shift(idx, 5)
    return jnp.stack([
        jax.lax.reduce(jnp.where(word == w, bit, jnp.uint32(0)),
                       jnp.uint32(0), jax.lax.bitwise_or, (2, 3))
        for w in range(pl.cdiv(d, 32))], axis=-1)


@jax.named_scope(scopes.BLOCK_MAPS)
def _block_maps(q_vals, q_idx, k_vals, k_idx, *, d: int, causal: bool,
                block_q: int, block_k: int, nq_real: int, nk_real: int):
    """Level map + fetch map for the block-skip kernel (padded inputs).

    level: (bh, nqb, nkb) int32 in {0: dead, 1: zero-overlap closed form,
    2: compute}. fetch: same shape; the K/V block index to DMA at each grid
    step — real index on level 2, else the last level-2 index (repeating a
    block index makes the TPU pipeline elide the copy).
    """
    bh, nqp, _ = q_idx.shape
    nkp = k_idx.shape[1]
    nqb, nkb = nqp // block_q, nkp // block_k
    occ_q = _tile_occupancy(q_vals, q_idx, d, nqb, block_q)
    occ_k = _tile_occupancy(k_vals, k_idx, d, nkb, block_k)
    overlap = jnp.any((occ_q[:, :, None, :] & occ_k[:, None, :, :]) != 0,
                      axis=-1)                     # (bh, nqb, nkb)
    qs = jnp.arange(nqb)[:, None] * block_q                # (nqb, 1)
    ks = jnp.arange(nkb)[None, :] * block_k                # (1, nkb)
    dead = jnp.broadcast_to(qs >= nq_real, (nqb, nkb))
    full = ks + block_k <= nk_real     # no padded key anywhere in the tile
    if causal:
        dead = dead | (ks > qs + block_q - 1)
        full = full & (ks + block_k - 1 <= qs)   # unmasked for every row
    level = jnp.where(dead[None], 0,
                      jnp.where(full[None] & ~overlap, 1, 2)).astype(jnp.int32)
    jidx = jnp.where(level == 2, jnp.arange(nkb)[None, None, :], -1)
    fetch = jnp.maximum(jax.lax.cummax(jidx, axis=2), 0).astype(jnp.int32)
    return level, fetch


def _pad_codes(q_vals, q_idx, k_vals, k_idx, v, block_q, block_k):
    nq = q_vals.shape[1]
    nk = k_vals.shape[1]
    pad_q = (-nq) % block_q
    pad_k = (-nk) % block_k
    if pad_q:
        q_vals = jnp.pad(q_vals, ((0, 0), (0, pad_q), (0, 0)))
        q_idx = jnp.pad(q_idx, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        # Padded keys are masked in-kernel via cols < nk_real.
        k_vals = jnp.pad(k_vals, ((0, 0), (0, pad_k), (0, 0)))
        k_idx = jnp.pad(k_idx, ((0, 0), (0, pad_k), (0, 0)))
        if v is not None:
            v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    return q_vals, q_idx, k_vals, k_idx, v, pad_q, pad_k


@functools.partial(jax.jit, static_argnames=("d", "causal", "block_q",
                                             "block_k"))
def block_skip_stats(q_vals, q_idx, k_vals, k_idx, *, d: int,
                     causal: bool = True, block_q: int = 128,
                     block_k: int = 128):
    """Tile-schedule stats for the block-skip path, on UNPADDED codes.

    Returns ``(skip_frac, overlap_frac, fetch_frac)``: the fraction of
    (q-tile, k-tile) grid steps that are dead (level 0), closed-form
    zero-overlap (level 1), and the fraction of K/V blocks actually fetched
    (level 2). Exactly the map the kernel runs from — the bench reports
    these next to the analytic byte model.
    """
    nq, nk = q_vals.shape[1], k_vals.shape[1]
    q_vals, q_idx, k_vals, k_idx, _, _, _ = _pad_codes(
        q_vals, q_idx, k_vals, k_idx, None, block_q, block_k)
    level, _ = _block_maps(q_vals, q_idx, k_vals, k_idx, d=d, causal=causal,
                           block_q=block_q, block_k=block_k, nq_real=nq,
                           nk_real=nk)
    total = level.size
    return ((level == 0).sum() / total, (level == 1).sum() / total,
            (level == 2).sum() / total)


@jax.named_scope(scopes.SFA_FWD)
@functools.partial(jax.jit, static_argnames=(
    "d", "causal", "scale", "block_q", "block_k", "interpret",
    "return_residuals", "block_skip"))
def flash_sfa(q_vals, q_idx, k_vals, k_idx, v, *, d: int, causal: bool = True,
              scale: float | None = None, block_q: int = 128,
              block_k: int = 128, interpret: bool | None = None,
              return_residuals: bool = False, block_skip: bool = False):
    """FlashSFA forward. Codes: (bh, n, k); v: (bh, n, dv) -> (bh, n, dv).

    Exactly softmax(densify(Q̃)·densify(K̃)ᵀ·scale + causal)·V, computed in
    (block_q × block_k) tiles with online softmax; no (n, n) materialization.

    With ``return_residuals`` also emits the per-row log-sum-exp
    ``lse = m + log(l)`` (bh, n) f32 — the statistic the backward kernel
    (flash_sfa_bwd.py) needs to recompute normalized P per tile. Padded-row
    lse entries are sliced off before returning, so the backward only ever
    consumes real rows.

    ``block_skip=True`` routes through the overlap-aware tile scheduler (see
    module docstring) — same outputs, causally-dead and zero-feature-overlap
    tiles skipped at both the compute and the DMA level.
    """
    bh, nq, kq = q_vals.shape
    nk = k_vals.shape[1]
    dv = v.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    interpret = resolve_interpret(interpret)
    q_vals, q_idx, k_vals, k_idx, v, pad_q, pad_k = _pad_codes(
        q_vals, q_idx, k_vals, k_idx, v, block_q, block_k)

    grid = (bh, (nq + pad_q) // block_q, (nk + pad_k) // block_k)
    out_specs = pl.BlockSpec((1, block_q, dv), lambda b, q, k, *_: (b, q, 0))
    out_shape = jax.ShapeDtypeStruct((bh, nq + pad_q, dv), v.dtype)
    if return_residuals:
        out_specs = [out_specs, pl.BlockSpec((1, 1, block_q),
                                             lambda b, q, k, *_: (b, 0, q))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((bh, 1, nq + pad_q), jnp.float32)]
    scratch_shapes = [
        pltpu.VMEM((block_q, LANES), jnp.float32),
        pltpu.VMEM((block_q, LANES), jnp.float32),
        pltpu.VMEM((block_q, dv), jnp.float32),
    ]
    if not block_skip:
        out = pl.pallas_call(
            functools.partial(_flash_sfa_kernel, d=d, scale=scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k, nq_real=nq, nk_real=nk,
                              emit_lse=return_residuals),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, kq), lambda b, q, k: (b, q, 0)),
                pl.BlockSpec((1, block_q, kq), lambda b, q, k: (b, q, 0)),
                pl.BlockSpec((1, block_k, k_vals.shape[-1]),
                             lambda b, q, k: (b, k, 0)),
                pl.BlockSpec((1, block_k, k_idx.shape[-1]),
                             lambda b, q, k: (b, k, 0)),
                pl.BlockSpec((1, block_k, dv), lambda b, q, k: (b, k, 0)),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(q_vals, q_idx, k_vals, k_idx, v)
    else:
        level, fetch = _block_maps(q_vals, q_idx, k_vals, k_idx, d=d,
                                   causal=causal, block_q=block_q,
                                   block_k=block_k, nq_real=nq, nk_real=nk)
        vsum = v.astype(jnp.float32).reshape(
            bh, grid[2], block_k, dv).sum(axis=2, keepdims=True)  # (bh, nkb, 1, dv)

        # SMEM pads the minor dim of a 2-D+ array to 128 words, so the maps
        # travel flat: (bh, nqb, nkb) would cost 128/nkb x their size.
        map_bytes = 2 * level.size * 4
        if not interpret and map_bytes > SMEM_MAP_BYTES:
            raise ValueError(
                f"block_skip: the level/fetch maps need {map_bytes} bytes of "
                f"SMEM at bh={bh}, {grid[1]}x{grid[2]} tiles; the budget is "
                f"{SMEM_MAP_BYTES}. Call with block_skip=False, or split the "
                f"batch*heads axis")
        level, fetch = level.reshape(-1), fetch.reshape(-1)

        def _kv_map(b, q, k, lvl, ft):
            del lvl
            return (b, ft[(b * grid[1] + q) * grid[2] + k], 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, kq),
                             lambda b, q, k, *_: (b, q, 0)),
                pl.BlockSpec((1, block_q, kq),
                             lambda b, q, k, *_: (b, q, 0)),
                pl.BlockSpec((1, block_k, k_vals.shape[-1]), _kv_map),
                pl.BlockSpec((1, block_k, k_idx.shape[-1]), _kv_map),
                pl.BlockSpec((1, block_k, dv), _kv_map),
                pl.BlockSpec((1, 1, 1, dv),
                             lambda b, q, k, *_: (b, k, 0, 0)),
            ],
            out_specs=out_specs,
            scratch_shapes=scratch_shapes,
        )
        out = pl.pallas_call(
            functools.partial(_flash_sfa_skip_kernel, d=d, scale=scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k, nk_real=nk,
                              emit_lse=return_residuals),
            grid_spec=grid_spec,
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(level, fetch, q_vals, q_idx, k_vals, k_idx, v, vsum)
    if return_residuals:
        o, lse = out
        return o[:, :nq], lse[:, 0, :nq]
    return out[:, :nq]
