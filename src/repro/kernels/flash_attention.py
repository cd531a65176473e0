"""Dense FlashAttention baseline kernel (the paper's comparison point).

Same tiling/online-softmax skeleton as flash_sfa.py but with dense (n, d)
Q/K — used to benchmark SFA's IO savings against an equal-quality dense
implementation (paper Figure 4 / Table 9 "Dense_*" rows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._compat import resolve_interpret
from repro.kernels.flash_sfa import _finalize_tile

NEG_INF = -1e30
LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  nk_real: int, emit_lse: bool = False):
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
    live = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        ok = cols < nk_real
        if causal:
            ok &= cols <= rows
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, 0] * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(p, v_ref[0].astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(kb == nkb - 1)
    def _finalize():
        _finalize_tile(o_ref, lse_ref, m_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "return_residuals"))
def _flash_fwd(q, k, v, *, causal: bool = True, scale: float | None = None,
               block_q: int = 128, block_k: int = 128,
               interpret: bool | None = None,
               return_residuals: bool = False):
    interpret = resolve_interpret(interpret)
    bh, nq, d = q.shape
    nk = k.shape[1]
    dv = v.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    pad_q = (-nq) % block_q
    pad_k = (-nk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    grid = (bh, (nq + pad_q) // block_q, (nk + pad_k) // block_k)
    out_specs = pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0))
    out_shape = jax.ShapeDtypeStruct((bh, nq + pad_q, dv), v.dtype)
    if return_residuals:
        out_specs = [out_specs,
                     pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((bh, 1, nq + pad_q), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk_real=nk,
                          emit_lse=return_residuals),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    if return_residuals:
        o, lse = out
        return o[:, :nq], lse[:, 0, :nq]
    return out[:, :nq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_diff(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_fwd(q, k, v, causal=causal, scale=scale, block_q=block_q,
                      block_k=block_k, interpret=interpret)


def _flash_diff_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    o, lse = _flash_fwd(q, k, v, causal=causal, scale=scale, block_q=block_q,
                        block_k=block_k, interpret=interpret,
                        return_residuals=True)
    return o, (q, k, v, o, lse)


def _flash_diff_bwd(causal, scale, block_q, block_k, interpret, res, g):
    # deferred import: flash_sfa_bwd shares tile helpers with flash_sfa
    from repro.kernels.flash_sfa_bwd import flash_attention_bwd
    q, k, v, o, lse = res
    dq, dk, dv = flash_attention_bwd(
        q, k, v, o, lse, g, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=interpret)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None,
                    return_residuals: bool = False):
    """Dense flash attention. q/k/v: (bh, n, d) -> (bh, n, dv).

    Differentiable: ``jax.grad`` executes the Pallas backward kernels in
    kernels/flash_sfa_bwd.py (recompute-in-tile, FA2-style) — no XLA forward
    re-execution. ``return_residuals`` additionally returns the per-row
    log-sum-exp (same contract as flash_sfa; that path is forward-only).
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if return_residuals:
        return _flash_fwd(q, k, v, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret, return_residuals=True)
    return _flash_diff(q, k, v, causal, scale, block_q, block_k, interpret)
