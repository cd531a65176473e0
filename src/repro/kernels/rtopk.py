"""RTopK-TPU: row-wise top-|k| selection as a Pallas kernel.

The paper uses the GPU RTopK kernel (Xie et al., 2024): per-warp binary search
on a magnitude threshold. The TPU adaptation (DESIGN.md §2) replaces warp
shuffles with VPU-wide vector ops and makes the search *exact* in a fixed 31
iterations by bisecting on IEEE-754 bit patterns: for non-negative floats the
int32 bit pattern is order-isomorphic to the float value, so integer bisection
finds the k-th largest magnitude's exact bit pattern — no dynamic-range or
ulp-convergence caveat (an improvement over the float-threshold search used on
GPU).

Selection then needs no sort network: entries strictly above the threshold are
all kept; ties at the threshold are kept in ascending-index order until k slots
fill. Slot positions come from a cumulative sum computed as a lower-triangular
matmul (MXU-friendly prefix sum). Output contract matches
``repro.core.sparse.sparsify``: values + ascending int32 indices.

NaN handling: the bit-pattern order isomorphism holds for *ordered* floats
only — NaN payloads bitcast above the ``0x7F800001`` bisection bound, which
breaks the ``cnt_geq(hi) < k`` invariant and can leave rows with NaNs holding
fewer than k real selections. ``_topk_select`` therefore canonicalizes NaNs
to +0.0 before the search, so the documented contract becomes parity with
``jax.lax.top_k(|nan_to_zero(x)|)``: NaN entries lose (tie with true zeros at
magnitude 0) and are emitted as 0.0 if a zero-tie slot picks them. ±Inf,
subnormals, and ±0 all order correctly through the bit patterns and are moved
bit-exactly.

``proj_rtopk`` is the fused projection entry (DESIGN.md §2): per (batch,
head, row-tile) grid step it computes the head projection ``x_tile @ w_h``
(+ optional RoPE) in VMEM and runs the same top-k selection *in-tile*, so the
dense (n, d) activation never exists in HBM — only the (n, k) codes are
written.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import scopes
from repro.kernels._compat import resolve_interpret
from repro.kernels.flash_sfa import row_to_column


def _cumsum_rows(x: jax.Array) -> jax.Array:
    """Inclusive prefix-sum along the last axis via triangular matmul.

    (r, d) @ (d, d) lower-triangular-ones — runs on the MXU, avoiding
    jnp.cumsum (which lowers to a serial scan on the TPU minor axis).
    """
    d = x.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
    tri = (row <= col).astype(x.dtype)  # tri[i,j] = 1 if i<=j  -> inclusive
    return jax.lax.dot(x, tri, preferred_element_type=jnp.float32)


def _topk_select(x: jax.Array, k: int, *, bits: int = 31):
    """In-tile top-|k|: x (br, d) f32 -> (vals (br, k) f32, idx (br, k) i32).

    Shared by the standalone rtopk kernel and the fused projection kernel
    (``proj_rtopk``). Values are moved as int32 bit patterns so the
    compaction is bit-exact even for subnormals (TPU/XLA float adds
    flush-to-zero). NaNs are canonicalized to +0.0 up front — see module
    docstring.
    """
    br, d = x.shape
    x = jnp.where(jnp.isnan(x), 0.0, x)
    ax = jnp.abs(x)
    # --- exact integer bisection on IEEE-754 bit patterns ---------------
    axb = jax.lax.bitcast_convert_type(ax, jnp.int32)  # >=0 floats: monotonic
    lo = jnp.zeros((br, 1), jnp.int32)                 # cnt_geq(0) = d >= k
    hi = jnp.full((br, 1), jnp.int32(0x7F800001))      # above +inf: cnt_geq = 0
    for _ in range(bits + 1):
        mid = lo + (hi - lo) // 2
        cnt = (axb >= mid).astype(jnp.float32).sum(axis=-1, keepdims=True)
        take_lo = cnt >= k                              # invariant: cnt_geq(lo) >= k
        lo = jnp.where(take_lo, mid, lo)
        hi = jnp.where(take_lo, hi, mid)
    theta = lo                                          # exact k-th |x| bit pattern
    # --- tie-aware selection in ascending index order --------------------
    sel_hi = axb > theta                                # strictly greater: < k of them
    sel_tie = axb == theta
    n_hi = sel_hi.astype(jnp.float32).sum(axis=-1, keepdims=True)
    rank_tie = _cumsum_rows(sel_tie.astype(jnp.float32))   # 1-based among ties
    sel = sel_hi | (sel_tie & (rank_tie <= (k - n_hi)))
    pos = _cumsum_rows(sel.astype(jnp.float32)) - 1.0      # 0-based output slot
    pos = jnp.where(sel, pos, -1.0)
    # --- compaction: k masked reductions (VPU) ---------------------------
    iota_d = jax.lax.broadcasted_iota(jnp.int32, (br, d), 1)
    xb = jax.lax.bitcast_convert_type(x, jnp.int32)
    vals_out = []
    idx_out = []
    for j in range(k):
        at_j = (pos == float(j))
        vals_out.append(jnp.sum(jnp.where(at_j, xb, 0), axis=-1))
        idx_out.append(jnp.sum(jnp.where(at_j, iota_d, 0), axis=-1))
    vals_bits = jnp.stack(vals_out, axis=-1)
    vals = jax.lax.bitcast_convert_type(vals_bits, jnp.float32)
    idx = jnp.stack(idx_out, axis=-1).astype(jnp.int32)
    return vals, idx


def _rtopk_kernel(x_ref, vals_ref, idx_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)          # (br, d)
    vals, idx = _topk_select(x, k)
    vals_ref[...] = vals.astype(vals_ref.dtype)
    idx_ref[...] = idx


@jax.named_scope(scopes.PROJ_RTOPK)
@functools.partial(jax.jit, static_argnames=("k", "block_rows", "interpret"))
def rtopk(x: jax.Array, k: int, *, block_rows: int = 256,
          interpret: bool | None = None):
    """Row-wise top-k by magnitude. x: (..., d) -> (values (...,k), idx (...,k)).

    Indices ascending per row; exact match with jax.lax.top_k(|x|) + index sort
    for NaN-free rows (ties keep lowest indices — both contracts agree;
    asserted in tests). Rows containing NaNs follow the canonicalized contract
    ``jax.lax.top_k(|nan_to_zero(x)|)`` — see module docstring.
    """
    interpret = resolve_interpret(interpret)
    orig_shape = x.shape
    d = orig_shape[-1]
    assert k <= d, (k, d)
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    pad = (-rows) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    nblocks = x2.shape[0] // block_rows
    vals, idx = pl.pallas_call(
        functools.partial(_rtopk_kernel, k=k),
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((x2.shape[0], k), x.dtype),
            jax.ShapeDtypeStruct((x2.shape[0], k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2)
    vals = vals[:rows].reshape(*orig_shape[:-1], k)
    idx = idx[:rows].reshape(*orig_shape[:-1], k)
    return vals, idx


def _rope_tile(y: jax.Array, pos: jax.Array, theta: float, rot: int,
               dt) -> jax.Array:
    """RoPE on one (br, d) projection tile — same arithmetic as
    ``models.layers.rope`` (elementwise, so the fused forward stays parity-
    exact with the unfused projection -> rope -> rtopk composition).

    pos: (br, 1) f32 positions. The interleaved pairs (2i, 2i+1) are
    rotated lane-wise, with no lane reshape: each lane reads its partner
    through a lane roll, and a parity mask picks the sign of the sin term.
    """
    br, d = y.shape
    yf = y.astype(dt).astype(jnp.float32)          # unfused path ropes dt acts
    # iota, not jnp.arange: arange would be a captured trace-time constant,
    # which pallas kernels reject.
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)
    freqs = theta ** (-(2.0 * (lane // 2).astype(jnp.float32)) / rot)
    ang = pos * freqs                                       # (br, d)
    cos = jnp.cos(ang)
    sin = jnp.sin(ang)
    even = lane % 2 == 0
    nxt = pltpu.roll(yf, d - 1, 1)                          # lane j <- j+1
    prv = pltpu.roll(yf, 1, 1)                              # lane j <- j-1
    rotated = jnp.where(even, yf * cos - nxt * sin, yf * cos + prv * sin)
    if rot < d:
        rotated = jnp.where(lane < rot, rotated, yf)
    return rotated.astype(dt)


def _proj_rtopk_kernel(x_ref, w_ref, *rest, k: int, rope_spec):
    if rope_spec is None:
        pos_ref = None
        vals_ref, idx_ref = rest
    else:
        pos_ref, vals_ref, idx_ref = rest
    dt = vals_ref.dtype
    xt = x_ref[0].astype(jnp.float32)              # (bn, m)
    wt = w_ref[0].astype(jnp.float32)              # (m, d)
    y = jax.lax.dot_general(xt, wt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y.astype(dt)                               # quantize like `x @ w`
    if rope_spec is not None:
        theta, rot = rope_spec
        pos = row_to_column(pos_ref[0].astype(jnp.float32))   # (bn, 1)
        y = _rope_tile(y, pos, theta, rot, dt)
    vals, idx = _topk_select(y.astype(jnp.float32), k)
    vals_ref[0, 0] = vals.astype(dt)
    idx_ref[0, 0] = idx


@jax.named_scope(scopes.PROJ_RTOPK)
@functools.partial(jax.jit, static_argnames=("k", "rope_spec", "block_n",
                                             "interpret"))
def proj_rtopk(x: jax.Array, w_heads: jax.Array, positions=None, *, k: int,
               rope_spec=None, block_n: int = 128,
               interpret: bool | None = None):
    """Fused head projection -> [RoPE] -> top-k: codes only, no dense HBM y.

    x: (b, n, m) activations; w_heads: (H, m, d) per-head projection blocks;
    positions: (b, n) int32 (required when ``rope_spec=(theta, rot_dim)`` is
    set). Per grid step one (block_n, d) projection tile is built and
    sparsified entirely in VMEM; HBM sees only the (b, H, n, k) values +
    indices — the fused-forward seam's write contract (DESIGN.md §2).

    Returns (vals (b, H, n, k) in x.dtype, idx (b, H, n, k) int32), matching
    ``rtopk(rope(x @ w_h))`` row-for-row.
    """
    interpret = resolve_interpret(interpret)
    b, n, m = x.shape
    nh, m2, d = w_heads.shape
    assert m2 == m, (w_heads.shape, x.shape)
    assert k <= d, (k, d)
    pad = (-n) % block_n
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    np_ = n + pad
    grid = (b, nh, np_ // block_n)
    in_specs = [
        pl.BlockSpec((1, block_n, m), lambda bb, hh, ii: (bb, ii, 0)),
        pl.BlockSpec((1, m, d), lambda bb, hh, ii: (hh, 0, 0)),
    ]
    operands = [x, w_heads]
    if rope_spec is not None:
        assert positions is not None, "rope_spec needs positions"
        pos = jnp.broadcast_to(positions, (b, n)).astype(jnp.int32)
        if pad:
            pos = jnp.pad(pos, ((0, 0), (0, pad)))
        # (b, 1, n) rows: see flash_sfa.lanes_to_row
        pos = pos[:, None, :]
        in_specs.append(pl.BlockSpec((1, 1, block_n),
                                     lambda bb, hh, ii: (bb, 0, ii)))
        operands.append(pos)
    vals, idx = pl.pallas_call(
        functools.partial(_proj_rtopk_kernel, k=k, rope_spec=rope_spec),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_n, k),
                         lambda bb, hh, ii: (bb, hh, ii, 0)),
            pl.BlockSpec((1, 1, block_n, k),
                         lambda bb, hh, ii: (bb, hh, ii, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, np_, k), x.dtype),
            jax.ShapeDtypeStruct((b, nh, np_, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(*operands)
    return vals[:, :, :n], idx[:, :, :n]
