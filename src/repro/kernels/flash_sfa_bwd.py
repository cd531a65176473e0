"""FlashSFA-TPU backward: recompute-in-tile gradients for the sparse codes.

FlashAttention-2-style backward, adapted to the paper's sparse feature codes
(DESIGN.md §3). The forward saves only O and the per-row log-sum-exp
LSE = m + log(l); the backward re-densifies the Q̃/K̃ code tiles in VMEM with
the same iota-compare idiom as the forward, recomputes per-tile normalized
probabilities P = exp(S − LSE) from the saved statistics — never
materializing the (n, n) score matrix — and accumulates

    dV_j  = Σ_i P_ijᵀ dO_i
    dS_ij = P_ij (dP_ij − D_i) · scale,  dP = dO Vᵀ,  D_i = Σ(dO_i ∘ O_i)
    dQ_i  = Σ_j dS_ij K̃_j,   dK_j = Σ_i dS_ijᵀ Q̃_i

in VMEM scratch across the sequential grid axis. Straight-through (paper
Eq. 6) gradients land exactly on the k stored coordinates of each row's code,
scatter-free, in one of two emit layouts (``emit=``):

  * ``"dense"``    — the accumulator is masked to the rebuilt support and
                     written as (block, d) rows: dQ/dK come out dense (n, d).
  * ``"compact"``  — the accumulator is *gathered* down to (block, k) on the
                     stored indices before the single HBM write: dQ̃/dK̃ come
                     out as (n, k) value-gradients aligned to the (n, k) int32
                     index tensors the forward already stores. Backward write
                     traffic for dQ+dK drops from 2·n·d·2 to 2·n·k·2 bytes
                     (8× at d=64, k=8 — DESIGN.md §3); kernels/code_grad.py
                     consumes the codes downstream without ever re-scattering.
  * ``"compact2"`` — the RoPE pair-widened form (DESIGN.md §3): the gathered
                     (block, k) values are laid out on the *pair closure* of
                     the stored indices — for each stored index i the closure
                     holds both members of i's RoPE rotation pair
                     (2⌊i/2⌋, 2⌊i/2⌋+1) — as two concatenated k-wide halves
                     (even members first, odd members second; see
                     ``pair_closure_indices``). A k-sparse post-rope cotangent
                     is exactly 2k-sparse pre-rope *on these known indices*,
                     so the rope vjp (``models/layers.py::rope_code_vjp``)
                     rotates the (n, 2k) codes in place and the projection
                     seam still never sees a dense dQ/dK. Write traffic is
                     2·n·2k·2 for dQ+dK — still d/2k ≈ 4× below dense at
                     d=64, k=8. ``rot_dim < d`` (partial rotation) keeps
                     unrotated trailing dims unwidened: their closure entry
                     is (i, i) with the whole value in the first half.

Two kernels, as in the standard TPU flash backward: a dQ kernel whose grid
parallelizes over q blocks and scans kv blocks, and a dK/dV kernel whose grid
parallelizes over kv blocks and scans q blocks — each output block is owned
by exactly one program, so no cross-program accumulation is needed.

Both kernels are parametrized by ``sparse``: the dense-baseline variant
(``flash_attention_bwd``, used by the custom_vjp in flash_attention.py so
the paper's Dense_* rows are also measured fwd+bwd) is identity-densify with
no support mask — same tile/grid bookkeeping, one code path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._compat import resolve_interpret
from repro.kernels.flash_sfa import _densify_block, row_to_column

NEG_INF = -1e30


def _support_mask(idx: jax.Array, d: int) -> jax.Array:
    """(b, k) int32 indices -> (b, d) {0,1} support mask (k VPU passes)."""
    b, k = idx.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (b, d), 1)
    m = jnp.zeros((b, d), jnp.float32)
    for t in range(k):
        m = jnp.maximum(m, (iota == idx[:, t][:, None]).astype(jnp.float32))
    return m


def _gather_support(acc: jax.Array, idx: jax.Array) -> jax.Array:
    """(b, d) dense accumulator -> (b, k) values at the stored coordinates.

    The inverse of ``_densify_block``: k iota-compare passes, each a masked
    row-reduction over the (b, d) tile — no gather op, no scatter, and the
    dense accumulator never leaves VMEM."""
    b, d = acc.shape
    k = idx.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (b, d), 1)
    cols = []
    for t in range(k):
        hit = (iota == idx[:, t][:, None]).astype(jnp.float32)
        cols.append(jnp.sum(acc * hit, axis=1, keepdims=True))
    return jnp.concatenate(cols, axis=1)


def pair_closure_indices(idx: jax.Array, rot_dim: int) -> jax.Array:
    """(…, k) stored indices -> (…, 2k) RoPE pair-closure indices.

    Layout matches ``emit="compact2"``: two concatenated k-wide halves —
    ``out[…, t]`` is the even member 2⌊i_t/2⌋ of stored index i_t's rotation
    pair, ``out[…, k+t]`` the odd member 2⌊i_t/2⌋+1. Indices at or beyond
    ``rot_dim`` (partial rotation: MLA rope heads, rot_dim < head_dim models)
    have no pair partner and pass through *unwidened*: both their closure
    slots are i_t itself, with the second half's value pinned to zero by the
    emit, so the duplicate contributes nothing when scattered.

    The closure is NOT deduped: when both members of a pair are stored, the
    pair appears twice, each occurrence carrying only its own index's
    cotangent share — every consumer (the XLA oracle and the code_grad
    VMEM rebuild alike) *sums* duplicate indices, so the semantics are
    exact and every shape stays static."""
    rotated = idx < rot_dim
    even = jnp.where(rotated, (idx // 2) * 2, idx)
    odd = jnp.where(rotated, even + 1, idx)
    return jnp.concatenate([even, odd], axis=-1)


def _pair_closure_gather(acc: jax.Array, idx: jax.Array,
                         rot_dim: int) -> jax.Array:
    """(b, d) dense accumulator -> (b, 2k) pair-closure code values.

    The straight-through gradient lives only on the k *stored* coordinates,
    so each closure slot carries the stored value iff the stored index IS
    that slot's pair member: the even half takes rows whose stored index is
    even (or unrotated), the odd half rows whose stored index is odd. The
    partner slots are zero here — they only become nonzero once the rope
    vjp mixes each pair (models/layers.py::rope_code_vjp)."""
    g = _gather_support(acc, idx)                         # (b, k) f32
    rotated = idx < rot_dim
    is_odd = rotated & (idx % 2 == 1)
    odd_f = is_odd.astype(jnp.float32)
    return jnp.concatenate([g * (1.0 - odd_f), g * odd_f], axis=1)


def _tile_p_ds(qd, kd, do, vb, lse, delta, *, scale, rows, cols, nk_real,
               causal):
    """Shared backward tile math: normalized P and dS for one (bq, bk) tile.

    ``lse``/``delta`` arrive as (1, bq) rows (their HBM layout) and are
    turned into (bq, 1) columns here."""
    s = jax.lax.dot_general(qd, kd, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    ok = cols < nk_real
    if causal:
        ok &= cols <= rows
    s = jnp.where(ok, s, NEG_INF)
    p = jnp.exp(s - row_to_column(lse))
    p = jnp.where(ok, p, 0.0)
    dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (bq, bk)
    ds = p * (dp - row_to_column(delta)) * scale
    return p, ds


def _unpack(refs, d, sparse, emit, rot_dim):
    """Split kernel refs into (load_q, load_k, q_emit_fn, k_emit_fn, rest).

    sparse: refs = (qv, qi, kv, ki, *rest) — densify in VMEM (lazily, only
    for live tiles); the emit fns turn the dense (block, d) accumulator into
    the written form — support-masked dense rows (``emit="dense"``), the
    (block, k) gathered code values (``emit="compact"``), or the (block, 2k)
    pair-closure values (``emit="compact2"``, rot_dim-aware).
    dense: refs = (q, k, *rest) — identity load, identity emit.
    """
    if sparse:
        qv_ref, qi_ref, kv_ref, ki_ref, *rest = refs
        load_q = lambda: _densify_block(qv_ref[0], qi_ref[0], d)
        load_k = lambda: _densify_block(kv_ref[0], ki_ref[0], d)
        if emit == "compact":
            q_emit = lambda x: _gather_support(x, qi_ref[0])
            k_emit = lambda x: _gather_support(x, ki_ref[0])
        elif emit == "compact2":
            q_emit = lambda x: _pair_closure_gather(x, qi_ref[0], rot_dim)
            k_emit = lambda x: _pair_closure_gather(x, ki_ref[0], rot_dim)
        else:
            q_emit = lambda x: x * _support_mask(qi_ref[0], d)
            k_emit = lambda x: x * _support_mask(ki_ref[0], d)
    else:
        q_ref, k_ref, *rest = refs
        load_q = lambda: q_ref[0].astype(jnp.float32)
        load_k = lambda: k_ref[0].astype(jnp.float32)
        q_emit = k_emit = lambda x: x
    return load_q, load_k, q_emit, k_emit, rest


def _bwd_dq_kernel(*refs, d: int, scale: float, causal: bool, block_q: int,
                   block_k: int, nk_real: int, sparse: bool, emit: str,
                   rot_dim: int):
    qb, kb = pl.program_id(1), pl.program_id(2)
    nkb = pl.num_programs(2)
    load_q, load_k, q_emit, _, rest = _unpack(refs, d, sparse, emit, rot_dim)
    v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc = rest

    @pl.when(kb == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start = qb * block_q
    k_start = kb * block_k
    live = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(live)
    def _compute():
        qd, kd = load_q(), load_k()
        rows = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        _, ds = _tile_p_ds(qd, kd, do_ref[0].astype(jnp.float32),
                           v_ref[0].astype(jnp.float32), lse_ref[0],
                           delta_ref[0], scale=scale, rows=rows, cols=cols,
                           nk_real=nk_real, causal=causal)
        dq_acc[...] += jax.lax.dot_general(
            ds, kd, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == nkb - 1)
    def _finalize():
        # Scatter-free straight-through: grads only on the stored coords —
        # masked dense rows, or the gathered (block, k) code values.
        dq_ref[0, ...] = q_emit(dq_acc[...]).astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, d: int, scale: float, causal: bool, block_q: int,
                    block_k: int, nk_real: int, sparse: bool, emit: str,
                    rot_dim: int):
    kb, qb = pl.program_id(1), pl.program_id(2)
    nqb = pl.num_programs(2)
    load_q, load_k, _, k_emit, rest = _unpack(refs, d, sparse, emit, rot_dim)
    v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest

    @pl.when(qb == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = qb * block_q
    k_start = kb * block_k
    live = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(live)
    def _compute():
        qd, kd = load_q(), load_k()
        do = do_ref[0].astype(jnp.float32)
        rows = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        p, ds = _tile_p_ds(qd, kd, do, v_ref[0].astype(jnp.float32),
                           lse_ref[0], delta_ref[0], scale=scale, rows=rows,
                           cols=cols, nk_real=nk_real, causal=causal)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                    # (bk, dv)
        dk_acc[...] += jax.lax.dot_general(
            ds, qd, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                    # (bk, d)

    @pl.when(qb == nqb - 1)
    def _finalize():
        dk_ref[0, ...] = k_emit(dk_acc[...]).astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_impl(q_ops, k_ops, v, o, lse, g, *, d, causal, scale, block_q,
              block_k, interpret, sparse, emit="dense", rot_dim=None):
    """Shared scaffolding for both backwards.

    q_ops/k_ops: (vals, idx) code pairs when sparse, (dense,) when not —
    per-side operand lists whose BlockSpecs follow the q/k tiling.
    """
    nq = q_ops[0].shape[1]
    nk = k_ops[0].shape[1]
    bh = v.shape[0]
    dv_dim = v.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    pad_q = (-nq) % block_q
    pad_k = (-nk) % block_k
    if pad_q:
        q_ops = [jnp.pad(x, ((0, 0), (0, pad_q), (0, 0))) for x in q_ops]
        g = jnp.pad(g, ((0, 0), (0, pad_q), (0, 0)))
        lse = jnp.pad(lse, ((0, 0), (0, pad_q)))
        delta = jnp.pad(delta, ((0, 0), (0, pad_q)))
    if pad_k:
        k_ops = [jnp.pad(x, ((0, 0), (0, pad_k), (0, 0))) for x in k_ops]
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    nqp, nkp = nq + pad_q, nk + pad_k

    def specs(qmap, kmap):
        """Input BlockSpecs in kernel order for the given q/k index maps."""
        def row_map(*a):
            b, i, _ = qmap(*a)
            return b, 0, i
        return ([pl.BlockSpec((1, block_q, x.shape[-1]), qmap)
                 for x in q_ops] +
                [pl.BlockSpec((1, block_k, x.shape[-1]), kmap)
                 for x in k_ops] +
                [pl.BlockSpec((1, block_k, dv_dim), kmap),      # v
                 pl.BlockSpec((1, block_q, dv_dim), qmap),      # do
                 pl.BlockSpec((1, 1, block_q), row_map),          # lse
                 pl.BlockSpec((1, 1, block_q), row_map)])         # delta

    kw = dict(d=d, scale=scale, causal=causal, block_q=block_q,
              block_k=block_k, nk_real=nk, sparse=sparse, emit=emit,
              rot_dim=d if rot_dim is None else rot_dim)
    cparams = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    # per-row statistics as (bh, 1, n) rows: see flash_sfa.lanes_to_row
    operands = (*q_ops, *k_ops, v, g, lse[:, None, :], delta[:, None, :])
    # compact emits shrink the dQ/dK output rows from d to the code width
    # (k for "compact", 2k for the pair-closure "compact2")
    code_w = {"compact": 1, "compact2": 2}.get(emit)
    dq_w = code_w * q_ops[0].shape[-1] if code_w else d
    dk_w = code_w * k_ops[0].shape[-1] if code_w else d

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kw),
        grid=(bh, nqp // block_q, nkp // block_k),
        in_specs=specs(lambda b, i, j: (b, i, 0), lambda b, i, j: (b, j, 0)),
        out_specs=pl.BlockSpec((1, block_q, dq_w), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, nqp, dq_w), q_ops[0].dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=cparams, interpret=resolve_interpret(interpret),
    )(*operands)

    dk, dvout = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **kw),
        grid=(bh, nkp // block_k, nqp // block_q),
        in_specs=specs(lambda b, j, i: (b, i, 0), lambda b, j, i: (b, j, 0)),
        out_specs=[
            pl.BlockSpec((1, block_k, dk_w), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, dv_dim), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, nkp, dk_w), k_ops[0].dtype),
            jax.ShapeDtypeStruct((bh, nkp, dv_dim), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv_dim), jnp.float32),
        ],
        compiler_params=cparams, interpret=resolve_interpret(interpret),
    )(*operands)
    return dq[:, :nq], dk[:, :nk], dvout[:, :nk]


@functools.partial(jax.jit, static_argnames=(
    "d", "causal", "scale", "block_q", "block_k", "interpret", "emit",
    "rot_dim"))
def flash_sfa_bwd(q_vals, q_idx, k_vals, k_idx, v, o, lse, g, *, d: int,
                  causal: bool = True, scale: float | None = None,
                  block_q: int = 128, block_k: int = 128,
                  interpret: bool | None = None, emit: str = "dense",
                  rot_dim: int | None = None):
    """FlashSFA backward. Codes: (bh, n, k); v/o/g: (bh, n, dv); lse: (bh, n).

    Returns (dq, dk, dv), all supported only on each row's k stored
    coordinates (paper Eq. 6 straight-through — i.e. the gradient w.r.t. the
    pre-Topk dense Q/K); dv is dense (bh, n, dv). The dQ/dK layout follows
    ``emit``:

      * ``"dense"``    — (bh, n, d) rows, zeros off-support (the oracle form).
      * ``"compact"``  — (bh, n, k) value-gradients aligned index-for-index
                         with ``q_idx``/``k_idx``; O(n·k) HBM write traffic.
                         ``kernels.code_grad.scatter_code_grads`` is the
                         exact inverse back to the dense form.
      * ``"compact2"`` — (bh, n, 2k) value-gradients on the RoPE pair
                         closure ``pair_closure_indices(idx, rot_dim)``
                         (concatenated even/odd halves). Same scatter
                         inverse, with the closure indices; the layout
                         exists so ``rope_code_vjp`` can rotate the
                         cotangent to its pre-rope form without leaving the
                         compact domain. ``rot_dim`` (default d: fully
                         rotated) bounds the pairing — stored indices at or
                         beyond it emit unwidened (second slot zero).
    """
    if emit not in ("dense", "compact", "compact2"):
        raise ValueError(
            f"emit={emit!r}; expected 'dense', 'compact' or 'compact2'")
    return _bwd_impl([q_vals, q_idx], [k_vals, k_idx], v, o, lse, g, d=d,
                     causal=causal, scale=scale, block_q=block_q,
                     block_k=block_k, interpret=resolve_interpret(interpret), sparse=True,
                     emit=emit, rot_dim=rot_dim)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret"))
def flash_attention_bwd(q, k, v, o, lse, g, *, causal: bool = True,
                        scale: float | None = None, block_q: int = 128,
                        block_k: int = 128, interpret: bool | None = None):
    """Dense FlashAttention backward. q/k/v/o/g: (bh, n, d); lse: (bh, n)."""
    return _bwd_impl([q], [k], v, o, lse, g, d=q.shape[-1], causal=causal,
                     scale=scale, block_q=block_q, block_k=block_k,
                     interpret=resolve_interpret(interpret), sparse=False)
