"""Jitted public wrappers around the Pallas kernels (kernel-level dispatch).

Production code selects execution paths through the typed attention-backend
registry (repro/models/backends.py) — the registry's ``pallas`` backend is
the only production caller passing ``impl``/``bwd_impl`` here; tests use
them directly to pin kernel-vs-oracle parity.

``sfa_attention_op`` is the full fused pipeline (rtopk sparsify -> FlashSFA)
on (batch, seq, heads, head_dim) activations, matching the signature of
``repro.core.attention.sfa_attention``. ``impl`` selects:

  * ``"xla"``     — pure-JAX chunked online-softmax (always available; what
                    the pjit/dry-run path lowers; differentiable).
  * ``"pallas"``  — Pallas kernels, interpret-mode on CPU (correctness;
                    see ``repro.kernels._compat.resolve_interpret``)
                    or compiled on a real TPU. Differentiable end-to-end:
                    the backward is the FlashSFA backward kernel
                    (kernels/flash_sfa_bwd.py) — per-tile score recompute
                    from the saved (O, lse) residuals, straight-through
                    gradients on the stored top-k coordinates (paper Eq. 6).

``bwd_impl`` independently selects the backward for ``impl="pallas"``:
``"pallas"`` (default, the kernel) or ``"xla"`` (full XLA re-execution of
the forward via ``jax.vjp`` — kept as the gradient oracle for parity tests
and as a fallback on backends without a Pallas lowering).

``bwd_emit`` selects the Pallas backward's dQ/dK emit layout (DESIGN.md §3):
``"dense"`` (n, d) rows, ``"compact"`` (n, k) value-gradients, or
``"compact2"`` (n, 2k) RoPE pair-closure value-gradients — the compact forms
the kernel writes in O(n·k) bytes and this wrapper scatters back to the
dense cotangents the generic custom_vjp contract requires (for
``"compact2"`` with the pair-closure indices, pinning that the widened emit
is lossless). The scatter-free end-to-end consumer — the fused projection
seam that feeds the compact codes straight into ``kernels/code_grad.py``
(and, on rope'd layers, through ``rope_code_vjp`` first) — lives in
``repro/models/attention.py``; this op-level mode is the generic
correctness-preserving form (and what parity tests pin).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import attention as att
from repro.core import scopes
from repro.core.remat import tag_codes, tag_k_codes, tag_lse, tag_q_codes
from repro.distributed.shard import (
    run_tp, tp_flash_sfa, tp_flash_sfa_bwd, tp_proj_rtopk,
)
from repro.kernels.code_grad import scatter_code_grads
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_sfa_bwd import pair_closure_indices
from repro.kernels.rtopk import rtopk


def fold_heads(x):
    """(b, n, h, d) -> (b*h, n, d), h innermost — the kernels' batch layout."""
    b, n, h, d = x.shape
    return jnp.einsum("bnhd->bhnd", x).reshape(b * h, n, d)


def unfold_heads(x, b, h):
    """Inverse of ``fold_heads``."""
    bh, n, d = x.shape
    return jnp.einsum("bhnd->bnhd", x.reshape(b, h, n, d))


def fused_qk_codes(x, w, positions, *, h, hkv, hd, sfa_k, rope_spec=None):
    """Fused q/k code computation: dense q/k never round-trip HBM.

    x: (b, n, m) activations; w: (m, (h + 2·hkv)·hd) packed qkv projection
    (same layout the unfused seam splits). Each head's projection tile is
    built, rope'd and top-k-sparsified inside ``proj_rtopk``'s VMEM — the
    only q/k arrays this function ever touches in HBM are the (n, sfa_k)
    codes. GQA key codes are computed once at hkv heads and repeated across
    the group, so group members carry *identical* indices (the invariant the
    compact backward's dk group-reduction relies on), matching the unfused
    repeat-KV -> rtopk composition row-for-row.

    Returns (q_vals, q_idx, k_vals, k_idx), each (b·h, n, sfa_k) in the
    kernels' b-major/h-inner folded layout.

    NOTE tests/test_fused_forward.py greps this function's source to enforce
    the no-dense-write contract: no rope / head-fold / matmul ops may appear
    here — only slicing, axis moves and repeats of (n, k)-sized arrays.
    """
    b, n, m = x.shape
    with jax.named_scope(scopes.PROJ_RTOPK):
        w = w.astype(x.dtype)           # unfused path projects in x.dtype
        wq = jnp.moveaxis(w[:, :h * hd].reshape(m, h, hd), 1, 0)
        wk = jnp.moveaxis(w[:, h * hd:(h + hkv) * hd].reshape(m, hkv, hd),
                          1, 0)
        qv, qi = tp_proj_rtopk(x, wq, positions, k=sfa_k,
                               rope_spec=rope_spec)
        kv_, ki = tp_proj_rtopk(x, wk, positions, k=sfa_k,
                                rope_spec=rope_spec)
        # name the codes as remat="codes" saveables (identity otherwise):
        # the checkpoint policy saves these (n, k) tensors so the backward
        # never re-runs the fused projection->rope->top-k pass
        # (core/remat.py). The k codes are tagged BEFORE the GQA group
        # repeat — the policy stores them at hkv width and the backward
        # recomputes the repeat for free.
        qv, qi = tag_q_codes(qv, qi)
        kv_, ki = tag_k_codes(kv_, ki)
        if hkv != h:
            kv_ = jnp.repeat(kv_, h // hkv, axis=1)
            ki = jnp.repeat(ki, h // hkv, axis=1)
        return (qv.reshape(b * h, n, sfa_k), qi.reshape(b * h, n, sfa_k),
                kv_.reshape(b * h, n, sfa_k), ki.reshape(b * h, n, sfa_k))


def _sfa_pallas_fwd(q, k, v, sfa_k, causal, scale, return_residuals=False):
    """Shared primal body: fold -> rtopk -> flash_sfa (-> residuals)."""
    b, n, h, d = q.shape
    qf, kf, vf = fold_heads(q), fold_heads(k), fold_heads(v)
    qv, qi = run_tp(lambda xx: rtopk(xx, sfa_k), (qf,), (0,), (0, 0))
    kv_, ki = run_tp(lambda xx: rtopk(xx, sfa_k), (kf,), (0,), (0, 0))
    # remat="codes" saveable names (identity tags otherwise, core/remat.py):
    # under the codes checkpoint policy only these (n, k) tensors (+ lse)
    # survive the forward — dense q/k/v and out are rebuilt in the backward.
    qv, qi, kv_, ki = tag_codes(qv, qi, kv_, ki)
    if not return_residuals:
        out = tp_flash_sfa(qv, qi, kv_, ki, vf, d=d, causal=causal,
                           scale=scale)
        return unfold_heads(out, b, h)
    out, lse = tp_flash_sfa(qv, qi, kv_, ki, vf, d=d, causal=causal,
                            scale=scale, return_residuals=True)
    lse = tag_lse(lse)
    # The kernel backward needs only the codes + folded v + (out, lse); the
    # dense q/k/v are NOT saved (shapes/dtypes are recoverable from g and
    # the codes), keeping residual memory at the FA2 contract.
    return unfold_heads(out, b, h), (qv, qi, kv_, ki, vf, out, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _sfa_pallas(q, k, v, sfa_k, causal, scale, bwd, emit):
    return _sfa_pallas_fwd(q, k, v, sfa_k, causal, scale)


def _sfa_xla(q, k, v, sfa_k, causal, scale):
    return att.sfa_attention(q, k, v, sfa_k=sfa_k, causal=causal, scale=scale)


def _sfa_fwd(q, k, v, sfa_k, causal, scale, bwd, emit):
    if bwd == "xla":
        return _sfa_pallas_fwd(q, k, v, sfa_k, causal, scale), (q, k, v)
    out, res = _sfa_pallas_fwd(q, k, v, sfa_k, causal, scale,
                               return_residuals=True)
    # Zero-size dtype carriers: the cotangents must come back in the
    # ORIGINAL q/k/v dtypes, not the code-value dtypes (which would silently
    # diverge if rtopk ever changed its output dtype).
    protos = tuple(jnp.zeros((), x.dtype) for x in (q, k, v))
    return out, res + (protos,)


def _sfa_bwd(sfa_k, causal, scale, bwd, emit, res, g):
    if bwd == "xla":
        # Oracle/fallback: straight-through backward via full XLA
        # re-execution of the forward (paper Eq. 6 semantics).
        q, k, v = res
        _, vjp = jax.vjp(lambda q, k, v: _sfa_xla(q, k, v, sfa_k, causal,
                                                  scale), q, k, v)
        return vjp(g)
    qv, qi, kv_, ki, vf, out, lse, (qp, kp, vp) = res
    b, n, h, d = g.shape
    gf = fold_heads(g)
    if emit in ("compact", "compact2"):
        # The kernel writes O(n·k) code-gradients; the custom_vjp contract
        # still owes dense (b, n, h, d) cotangents, so scatter here via the
        # XLA oracle ("compact2" scatters on the pair-closure indices — at
        # the op level the widening is a lossless relayout, since rope sits
        # outside the op and its vjp runs through XLA autodiff). The train
        # path that never pays this scatter is the fused projection seam in
        # repro/models/attention.py.
        dqc, dkc, dvf = tp_flash_sfa_bwd(qv, qi, kv_, ki, vf, out, lse, gf,
                                         d=d, causal=causal, scale=scale,
                                         emit=emit)
        qi_s = pair_closure_indices(qi, d) if emit == "compact2" else qi
        ki_s = pair_closure_indices(ki, d) if emit == "compact2" else ki
        dqf = scatter_code_grads(dqc, qi_s, d)
        dkf = scatter_code_grads(dkc, ki_s, d)
    else:
        dqf, dkf, dvf = tp_flash_sfa_bwd(qv, qi, kv_, ki, vf, out, lse, gf,
                                         d=d, causal=causal, scale=scale)
    return (unfold_heads(dqf, b, h).astype(qp.dtype),
            unfold_heads(dkf, b, h).astype(kp.dtype),
            unfold_heads(dvf, b, h).astype(vp.dtype))


_sfa_pallas.defvjp(_sfa_fwd, _sfa_bwd)


def _check_impl(name, value, allowed=("xla", "pallas")):
    if value not in allowed:
        raise ValueError(f"{name}={value!r}; expected one of {allowed}")


def sfa_attention_op(q, k, v, *, sfa_k: int, causal: bool = True,
                     scale: float | None = None, impl: str = "xla",
                     bwd_impl: str = "pallas", bwd_emit: str = "dense"):
    """SFA attention on (b, n, h, d) activations. See module docstring."""
    _check_impl("impl", impl)
    _check_impl("bwd_impl", bwd_impl)
    _check_impl("bwd_emit", bwd_emit, ("dense", "compact", "compact2"))
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    if impl == "pallas":
        return _sfa_pallas(q, k, v, sfa_k, causal, scale, bwd_impl, bwd_emit)
    return _sfa_xla(q, k, v, sfa_k, causal, scale)


def dense_attention_op(q, k, v, *, causal: bool = True,
                       scale: float | None = None, impl: str = "xla"):
    """Dense attention on (b, n, h, d); the pallas impl is differentiable via
    the dense FlashAttention backward kernel (flash_sfa_bwd.py)."""
    _check_impl("impl", impl)
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    if impl == "pallas":
        b, n, h, _ = q.shape
        out = flash_attention(fold_heads(q), fold_heads(k), fold_heads(v),
                              causal=causal, scale=scale)
        return unfold_heads(out, b, h)
    return att.chunked_attention(q, k, v, causal=causal, scale=scale)
