"""Compact code-gradient consumers: sparse-grad × dense matmul kernels.

The FlashSFA backward with ``emit="compact"`` (flash_sfa_bwd.py) writes
dQ̃/dK̃ as (n, k) value-gradients aligned to the stored (n, k) int32 indices —
O(n·k) HBM write traffic instead of O(n·d). This module makes that win real
*end-to-end through the train step*: the Q/K input-projection backward

    dW = xᵀ · scatter(dQ̃)          (d_model, d)   — contraction over tokens
    dx = scatter(dQ̃) · Wᵀ          (n, d_model)   — contraction over features

consumes the compact codes directly. Each Pallas kernel densifies one
(block_n, d) code tile in VMEM with the iota-compare idiom (DESIGN.md §2 —
the same densify-and-MXU trade the forward makes) and feeds the MXU; the
dense gradient tile lives and dies in VMEM, so a dense dQ/dK never
round-trips through HBM anywhere on the ``bwd_emit="compact"`` train path.

Both kernels are generic over the *static code width*: the last axis of
``vals``/``idx`` may be the forward's k (``emit="compact"``) or the RoPE
pair-closure's 2k (``emit="compact2"`` widened through ``rope_code_vjp``) —
the width is read from the operand shapes and only sizes the VPU densify
loop. Duplicate indices within a row (pair closures where both members of a
RoPE pair were stored, or unwidened partial-rotation entries) *sum*, in the
VMEM densify and in the XLA oracle alike — exactly the scatter-add
semantics the closure layout relies on.

Both kernels carry a leading per-head axis H (attention projections are
head-blocked: W = [W_1 | ... | W_H] with per-head codes over d = head_dim)
as a *sequential* grid axis with a VMEM accumulator, so the head sum in dx
never materializes H partial products either.

``scatter_code_grads`` is the XLA oracle: the exact (n, k) -> (n, d)
inverse of the kernel's in-tile gather, used for parity pins and as the
generic densify step for callers that do need dense-layout gradients.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import scopes
from repro.kernels._compat import resolve_interpret
from repro.kernels.flash_sfa import _densify_block


@jax.named_scope(scopes.CODE_GRAD)
def scatter_code_grads(vals: jax.Array, idx: jax.Array, d: int) -> jax.Array:
    """XLA oracle: scatter (..., k) value-grads to their dense (..., d) form.

    One-hot contraction (TPU-friendly, no lax.scatter). Duplicate indices
    within a row SUM — a guarantee, not an accident: rtopk/sparsify codes
    are duplicate-free, but ``pair_closure_indices`` closures repeat an
    index when both pair members are stored (each occurrence carrying its
    own share) and the summing contraction is what makes that exact.
    """
    onehot = jax.nn.one_hot(idx, d, dtype=vals.dtype)       # (..., k, d)
    return jnp.einsum("...k,...kd->...d", vals, onehot)


def _dx_kernel(vals_ref, idx_ref, w_ref, out_ref, acc_ref, *, d: int,
               nheads: int):
    h = pl.program_id(2)

    @pl.when(h == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = _densify_block(vals_ref[0], idx_ref[0], d)           # (bn, d) f32
    acc_ref[...] += jax.lax.dot_general(
        s, w_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                  # (bn, bm)

    @pl.when(h == nheads - 1)
    def _finalize():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@jax.named_scope(scopes.CODE_GRAD)
@functools.partial(jax.jit, static_argnames=("d", "block_n", "block_m",
                                             "interpret"))
def code_grad_dx(vals, idx, w, *, d: int, block_n: int = 128,
                 block_m: int = 128, interpret: bool | None = None):
    """dx = Σ_h scatter(vals_h, idx_h) @ w_hᵀ without densifying in HBM.

    vals/idx: (H, n, w) compact code-grads at any static code width w (k,
    or 2k for pair-closure codes); w: (H, m, d) per-head weight blocks
    (m = d_model). Returns (n, m) f32. The head axis is a sequential
    grid axis accumulated in VMEM — per (n, m) tile the HBM reads are the
    O(nk) codes plus the weight tiles; the densified (block_n, d) gradient
    tile exists only in VMEM.
    """
    nh, n, kk = vals.shape
    m = w.shape[1]
    pad_n = (-n) % block_n
    pad_m = (-m) % block_m
    if pad_n:                       # zero vals ⇒ zero contribution
        vals = jnp.pad(vals, ((0, 0), (0, pad_n), (0, 0)))
        idx = jnp.pad(idx, ((0, 0), (0, pad_n), (0, 0)))
    if pad_m:
        w = jnp.pad(w, ((0, 0), (0, pad_m), (0, 0)))
    np_, mp = n + pad_n, m + pad_m
    out = pl.pallas_call(
        functools.partial(_dx_kernel, d=d, nheads=nh),
        grid=(np_ // block_n, mp // block_m, nh),
        in_specs=[
            pl.BlockSpec((1, block_n, kk), lambda i, j, h: (h, i, 0)),
            pl.BlockSpec((1, block_n, kk), lambda i, j, h: (h, i, 0)),
            pl.BlockSpec((1, block_m, d), lambda i, j, h: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, block_m), lambda i, j, h: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, mp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_n, block_m), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(vals, idx, w)
    return out[:n, :m]


def _dw_kernel(x_ref, vals_ref, idx_ref, out_ref, acc_ref, *, d: int,
               nblocks_n: int):
    nb = pl.program_id(2)

    @pl.when(nb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = _densify_block(vals_ref[0], idx_ref[0], d)           # (bn, d) f32
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), s, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # (bm, d)

    @pl.when(nb == nblocks_n - 1)
    def _finalize():
        out_ref[0, ...] = acc_ref[...].astype(out_ref.dtype)


@jax.named_scope(scopes.CODE_GRAD)
@functools.partial(jax.jit, static_argnames=("d", "block_n", "block_m",
                                             "interpret"))
def code_grad_dw(x, vals, idx, *, d: int, block_n: int = 128,
                 block_m: int = 128, interpret: bool | None = None):
    """dW_h = xᵀ @ scatter(vals_h, idx_h) without densifying in HBM.

    x: (n, m) projection input (m = d_model, tokens flattened over batch);
    vals/idx: (H, n, w) compact code-grads at any static code width w (k or
    the pair-closure 2k). Returns (H, m, d) f32 per-head
    weight-gradient blocks. The token axis is the sequential grid axis with
    a (block_m, d) VMEM accumulator; like ``code_grad_dx`` the densified
    gradient tile never touches HBM.
    """
    nh, n, kk = vals.shape
    m = x.shape[1]
    pad_n = (-n) % block_n
    pad_m = (-m) % block_m
    if pad_n:                       # zero x rows / zero vals ⇒ no-op rows
        x = jnp.pad(x, ((0, pad_n), (0, 0)))
        vals = jnp.pad(vals, ((0, 0), (0, pad_n), (0, 0)))
        idx = jnp.pad(idx, ((0, 0), (0, pad_n), (0, 0)))
    if pad_m:
        x = jnp.pad(x, ((0, 0), (0, pad_m)))
    np_, mp = n + pad_n, m + pad_m
    out = pl.pallas_call(
        functools.partial(_dw_kernel, d=d, nblocks_n=np_ // block_n),
        grid=(nh, mp // block_m, np_ // block_n),
        in_specs=[
            pl.BlockSpec((block_n, block_m), lambda h, j, i: (i, j)),
            pl.BlockSpec((1, block_n, kk), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, block_n, kk), lambda h, j, i: (h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_m, d), lambda h, j, i: (h, j, 0)),
        out_shape=jax.ShapeDtypeStruct((nh, mp, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(x, vals, idx)
    return out[:, :m]
