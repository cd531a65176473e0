"""Ring-SFA: causal ring attention over the ``seq`` mesh axis with
code-payload hops.

Classic ring attention rotates dense (n/P, d) K blocks (plus V) around the
device ring. SFA's top-k feature codes shrink the K payload to (n/P, k)
values + indices — a per-hop K-byte ratio of

    dense/code = d·val_bytes / (k·(val_bytes + idx_bytes)) ≈ d / (2k)

at matched value/index widths (V rides along identically in both worlds,
so the ratio is quoted K-payload-only; ``ring_bytes_per_hop`` gives the
absolute total). At the paper's operating points (d=128, k=8..16) that is
a 4-8x cut of the rotating K traffic.

Mechanics (validated against the single-device FlashSFA kernels):

  * Each device owns one contiguous sequence shard of the folded (b·h, n, *)
    arrays. The hop payload ``(k_vals, k_idx, v)`` rotates device i -> i+1
    with ``jax.lax.ppermute``; after hop t, device ``idx`` holds the shard
    of rank ``src = (idx - t) % P``.
  * Per hop the local FlashSFA kernel runs on the (q-shard, k-shard) tile —
    ``causal=True`` on the diagonal hop, ``causal=False`` on fully-past
    hops — and the per-hop ``(o_t, lse_t)`` partials fold into the running
    output with the standard online-softmax merge. The backward ring runs
    the compact-emit FlashSFA backward per hop; dK/dV accumulators *travel
    with the payload* so each contribution is produced on the device that
    computes it and lands home with ONE extra return hop (P permutes
    total backward, P-1 forward).
  * Hop skipping, exactly: a future shard (``src > idx``) contributes
    nothing (causal early-exit: rank i's queries are complete after i+1
    hops — the remaining hops run the zero-cost skip branch). A fully-past
    hop whose K-shard feature occupancy is DISJOINT from the local Q-shard
    occupancy has all-zero scores, so its softmax contribution has the
    closed form ``o_t = mean_j(v_j)``, ``lse_t = log(n_local)`` (uniform
    attention), and its backward is ``dq = dk = 0``,
    ``dv_t[j] = Σ_i e^{-lse_i} g_i`` — no kernel launch either way.
    Occupancy is a d-bit OR over the whole shard, so the skip is
    conservative (any overlapping row disables it) and exact.

The public entry points fall back to the single-device kernel composition
outside a mesh context (or when the ``seq`` axis is absent/1, or the
sequence does not divide the ring degree), so the same model code runs
everywhere. ``ring_sfa`` is the code-level op (codes in, code-grads out);
``ring_sfa_op`` is the dense folded-level op models/attention.py calls
(rtopk runs inside the shard_map region — row-wise, so sharding the
sequence is free; the backward scatters the code grads to dense dQ/dK
locally per shard).

NOTE tests/test_ring.py greps the hop-loop bodies (``_ring_fwd_local`` /
``_ring_bwd_local``) to pin that no dense (n, d) K tensor is ever built
inside a hop: no ``scatter_code_grads`` / ``densify`` / ``one_hot`` /
``.at[`` may appear there — the K payload stays (n/P, k) codes end to end.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_mesh

# kernel imports are lazy inside the bodies (kernels/ops.py ->
# distributed/shard.py import-cycle precedent; ring.py is imported by
# models/attention.py which the kernels' public wrappers also reach)


def ring_degree(axis_name: str = "seq") -> int:
    """Size of the ring mesh axis under the active rules context (1 if
    none)."""
    mesh = current_mesh()
    return 1 if mesh is None else mesh.shape.get(axis_name, 1)


# --------------------------------------------------------------------------
# analytic comms-byte model (asserted against realized collective bytes by
# benchmarks/bench_attention.py + benchmarks/check_trajectory.py)
# --------------------------------------------------------------------------

def ring_bytes_per_hop(bh: int, n_local: int, k: int, dv: int, *,
                       val_bytes: int = 4, idx_bytes: int = 4,
                       v_bytes: int = 4) -> int:
    """Per-device payload bytes of ONE code-ring hop: (n/P, k) K-code
    values + indices plus the (n/P, dv) V block."""
    return bh * n_local * (k * (val_bytes + idx_bytes) + dv * v_bytes)


def ring_dense_bytes_per_hop(bh: int, n_local: int, d: int, dv: int, *,
                             val_bytes: int = 4, v_bytes: int = 4) -> int:
    """Per-device payload bytes of one DENSE ring hop (the baseline ring
    attention rotates the full (n/P, d) K block)."""
    return bh * n_local * (d * val_bytes + dv * v_bytes)


def ring_byte_ratio(d: int, k: int, *, val_bytes: int = 4,
                    idx_bytes: int = 4) -> float:
    """Dense-K / code-K payload ratio per hop. V rides identically in both
    worlds, so the ratio is K-payload-only: d·val / (k·(val+idx)) — at
    matched widths exactly d/(2k)."""
    return (d * val_bytes) / (k * (val_bytes + idx_bytes))


def ring_fwd_wire_bytes(nshards: int, bh: int, n_local: int, k: int,
                        dv: int, *, val_bytes: int = 4, idx_bytes: int = 4,
                        v_bytes: int = 4) -> int:
    """Total per-device wire bytes of the forward ring: P-1 hops of the
    (K-codes + V) payload (collective-permute wire = operand bytes)."""
    return (nshards - 1) * ring_bytes_per_hop(
        bh, n_local, k, dv, val_bytes=val_bytes, idx_bytes=idx_bytes,
        v_bytes=v_bytes)


def ring_bwd_wire_bytes(nshards: int, bh: int, n_local: int, k: int,
                        dv: int, *, val_bytes: int = 4, idx_bytes: int = 4,
                        v_bytes: int = 4, grad_bytes: int = 4) -> int:
    """Total per-device wire bytes of the backward ring: P-1 payload hops
    (K codes + V + traveling dK-code/dV accumulators) plus the single
    return hop of the accumulators."""
    payload = ring_bytes_per_hop(bh, n_local, k, dv, val_bytes=val_bytes,
                                 idx_bytes=idx_bytes, v_bytes=v_bytes)
    acc = bh * n_local * (k + dv) * grad_bytes
    return (nshards - 1) * (payload + acc) + acc


def ring_hop_stats(q_idx, k_idx, nshards: int, *, d: int) -> dict:
    """Static hop-occupancy accounting for a GLOBAL pair of code-index
    arrays (bh, n, k): which of the P x P (q-shard, k-shard) hops actually
    launch a kernel. Returns python ints (call on concrete arrays).

    ``causal_skipped`` counts the future hops every ring run skips by
    construction (P(P-1)/2); ``overlap_skipped`` counts fully-past hops
    whose shard-level feature occupancies are disjoint (the closed-form
    uniform branch); ``computed`` is the rest (diagonal hops always
    compute)."""
    n = q_idx.shape[1]
    nl = n // nshards
    occ = np.zeros((2, nshards, d), dtype=bool)
    for which, idx in enumerate((q_idx, k_idx)):
        arr = np.asarray(idx)
        for s in range(nshards):
            occ[which, s, np.unique(arr[:, s * nl:(s + 1) * nl])] = True
    causal_skipped = nshards * (nshards - 1) // 2
    overlap_skipped = 0
    for r in range(nshards):
        for s in range(r):                       # fully-past hops only
            if not np.any(occ[0, r] & occ[1, s]):
                overlap_skipped += 1
    total = nshards * nshards
    return {
        "total_hops": total,
        "causal_skipped": causal_skipped,
        "overlap_skipped": overlap_skipped,
        "computed": total - causal_skipped - overlap_skipped,
    }


# --------------------------------------------------------------------------
# hop-loop bodies (run INSIDE shard_map; local (bh, n/P, ...) shapes)
# --------------------------------------------------------------------------

def _merge(o, lse, o_t, lse_t):
    """Online-softmax merge of two (o, lse) partials; f32 arithmetic."""
    m = jnp.maximum(lse, lse_t)
    wa = jnp.exp(lse - m)
    wb = jnp.exp(lse_t - m)
    return ((o * wa[..., None] + o_t * wb[..., None]) / (wa + wb)[..., None],
            m + jnp.log(wa + wb))


def _occupancy(idx, d):
    """d-bit feature-occupancy bitmap of a code-index shard (any row)."""
    return jnp.zeros((d,), jnp.bool_).at[idx.reshape(-1)].set(True)


def _ring_fwd_local(qv, qi, kv, ki, v, *, d, scale, nshards, axis_name,
                    interpret, block_q, block_k):
    """One device's forward ring. NO dense K anywhere: the traveling
    payload is (k_vals, k_idx, v) and every hop feeds the codes straight
    into FlashSFA (grep-banned contract, see module docstring)."""
    from repro.kernels.flash_sfa import flash_sfa

    bh, nl, dv = v.shape
    idx = jax.lax.axis_index(axis_name)
    o = jnp.zeros((bh, nl, dv), jnp.float32)
    lse = jnp.full((bh, nl), -1e30, jnp.float32)
    q_occ = _occupancy(qi, d)
    kernel_kw = dict(d=d, scale=scale, interpret=interpret,
                     block_q=min(block_q, nl), block_k=min(block_k, nl),
                     return_residuals=True)
    payload = (kv, ki, v)
    for t in range(nshards):
        src = (idx - t) % nshards
        pkv, pki, pv = payload

        def diag_hop(op):
            o_t, lse_t = flash_sfa(qv, qi, *op, causal=True, **kernel_kw)
            return o_t.astype(jnp.float32), lse_t

        def full_hop(op):
            o_t, lse_t = flash_sfa(qv, qi, *op, causal=False, **kernel_kw)
            return o_t.astype(jnp.float32), lse_t

        def uniform_hop(op):
            # disjoint feature occupancy -> all scores 0 -> closed form
            _, _, pv = op
            o_t = jnp.broadcast_to(
                pv.astype(jnp.float32).mean(axis=1, keepdims=True),
                (bh, nl, dv))
            return o_t, jnp.full((bh, nl), math.log(nl), jnp.float32)

        def skip_hop(op):
            return (jnp.zeros((bh, nl, dv), jnp.float32),
                    jnp.full((bh, nl), -1e30, jnp.float32))

        overlap = jnp.any(q_occ & _occupancy(pki, d))
        branch = jnp.where(
            src == idx, 0,
            jnp.where(src < idx, jnp.where(overlap, 1, 2), 3))
        o_t, lse_t = jax.lax.switch(
            branch, (diag_hop, full_hop, uniform_hop, skip_hop),
            (pkv, pki, pv))
        o, lse = _merge(o, lse, o_t, lse_t)
        if t < nshards - 1:
            perm = [(i, (i + 1) % nshards) for i in range(nshards)]
            payload = tuple(jax.lax.ppermute(x, axis_name, perm)
                            for x in payload)
    return o, lse


def _ring_bwd_local(qv, qi, kv, ki, v, o, lse, g, *, d, scale, nshards,
                    axis_name, interpret, block_q, block_k):
    """One device's backward ring (compact emit: dQ/dK as code-value grads
    aligned to the stored indices). dQ accumulates locally; the dK-code and
    dV accumulators TRAVEL with the payload and come home with one final
    return hop — P permutes total vs the forward's P-1."""
    from repro.kernels.flash_sfa_bwd import flash_sfa_bwd

    bh, nl, dv = v.shape
    k = ki.shape[-1]
    idx = jax.lax.axis_index(axis_name)
    dqc = jnp.zeros((bh, nl, k), jnp.float32)
    q_occ = _occupancy(qi, d)
    g32 = g.astype(jnp.float32)
    kernel_kw = dict(d=d, scale=scale, emit="compact", interpret=interpret,
                     block_q=min(block_q, nl), block_k=min(block_k, nl))
    payload = (kv, ki, v,
               jnp.zeros((bh, nl, k), jnp.float32),
               jnp.zeros((bh, nl, dv), jnp.float32))
    for t in range(nshards):
        src = (idx - t) % nshards
        pkv, pki, pv, dkc_acc, dv_acc = payload

        def mk_hop(causal_flag):
            def hop(op):
                dq_t, dkc_t, dv_t = flash_sfa_bwd(qv, qi, *op, o, lse, g,
                                                  causal=causal_flag,
                                                  **kernel_kw)
                # f32 accumulator dtype regardless of the code dtype, so
                # the closed-form branches agree with the kernel branches
                return (dq_t.astype(jnp.float32), dkc_t.astype(jnp.float32),
                        dv_t.astype(jnp.float32))
            return hop

        def uniform_hop(op):
            # zero scores: code grads gather at disjoint coords -> 0; the
            # uniform attention still carries dV = sum_i e^{-lse_i} g_i
            coef = jnp.exp(-lse)                               # (bh, nl_q)
            dv_t = jnp.broadcast_to(
                jnp.einsum("bi,bid->bd", coef, g32)[:, None, :],
                (bh, nl, dv))
            return (jnp.zeros((bh, nl, k), jnp.float32),
                    jnp.zeros((bh, nl, k), jnp.float32), dv_t)

        def skip_hop(op):
            return (jnp.zeros((bh, nl, k), jnp.float32),
                    jnp.zeros((bh, nl, k), jnp.float32),
                    jnp.zeros((bh, nl, dv), jnp.float32))

        overlap = jnp.any(q_occ & _occupancy(pki, d))
        branch = jnp.where(
            src == idx, 0,
            jnp.where(src < idx, jnp.where(overlap, 1, 2), 3))
        dq_t, dkc_t, dv_t = jax.lax.switch(
            branch, (mk_hop(True), mk_hop(False), uniform_hop, skip_hop),
            (pkv, pki, pv))
        dqc = dqc + dq_t
        payload = (pkv, pki, pv, dkc_acc + dkc_t, dv_acc + dv_t)
        if t < nshards - 1:
            perm = [(i, (i + 1) % nshards) for i in range(nshards)]
            payload = tuple(jax.lax.ppermute(x, axis_name, perm)
                            for x in payload)
    # after P-1 rotations shard j's accumulators sit on device j-1: one
    # return hop brings them home
    perm = [(i, (i + 1) % nshards) for i in range(nshards)]
    dkc_acc = jax.lax.ppermute(payload[3], axis_name, perm)
    dv_acc = jax.lax.ppermute(payload[4], axis_name, perm)
    return dqc, dkc_acc, dv_acc


# --------------------------------------------------------------------------
# code-level op: codes in, code-grads out
# --------------------------------------------------------------------------

def _seq_spec(ndim, axis_name):
    return P(*[None, axis_name] + [None] * (ndim - 2))


def _ring_eligible(n, axis_name):
    mesh = current_mesh()
    if mesh is None:
        return None
    nshards = mesh.shape.get(axis_name, 1)
    if nshards <= 1 or n % nshards:
        return None
    return mesh, nshards


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _ring_sfa(qv, qi, kv, ki, v, d, scale, axis_name, interpret, block_q,
              block_k):
    out, _ = _ring_sfa_fwd(qv, qi, kv, ki, v, d, scale, axis_name,
                           interpret, block_q, block_k)
    return out


def _ring_sfa_fwd(qv, qi, kv, ki, v, d, scale, axis_name, interpret,
                  block_q, block_k):
    mesh, nshards = _ring_eligible(qv.shape[1], axis_name)
    body = functools.partial(_ring_fwd_local, d=d, scale=scale,
                             nshards=nshards, axis_name=axis_name,
                             interpret=interpret, block_q=block_q,
                             block_k=block_k)
    spec = _seq_spec(3, axis_name)
    o, lse = jax.shard_map(body, mesh=mesh,
                       in_specs=(spec,) * 5,
                       out_specs=(spec, _seq_spec(2, axis_name)),
                       check_vma=False)(qv, qi, kv, ki, v)
    return o.astype(v.dtype), (qv, qi, kv, ki, v, o, lse)


def _ring_sfa_bwd(d, scale, axis_name, interpret, block_q, block_k, res, g):
    qv, qi, kv, ki, v, o, lse = res
    mesh, nshards = _ring_eligible(qv.shape[1], axis_name)
    body = functools.partial(_ring_bwd_local, d=d, scale=scale,
                             nshards=nshards, axis_name=axis_name,
                             interpret=interpret, block_q=block_q,
                             block_k=block_k)
    spec3 = _seq_spec(3, axis_name)
    spec2 = _seq_spec(2, axis_name)
    dqc, dkc, dv = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec3,) * 6 + (spec2, spec3),
        out_specs=(spec3, spec3, spec3),
        check_vma=False)(qv, qi, kv, ki, v, o, lse, g)
    zero_i = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (dqc.astype(qv.dtype), zero_i(qi), dkc.astype(kv.dtype),
            zero_i(ki), dv.astype(v.dtype))


_ring_sfa.defvjp(_ring_sfa_fwd, _ring_sfa_bwd)


def ring_sfa(q_vals, q_idx, k_vals, k_idx, v, *, d: int, causal: bool = True,
             scale: float | None = None, axis_name: str = "seq",
             interpret: bool | None = None, block_q: int = 128,
             block_k: int = 128):
    """Code-level Ring-SFA on global (b·h, n, *) arrays sharded over the
    ``seq`` mesh axis. Differentiable: the backward emits compact code-value
    gradients aligned to the stored indices (the same contract as
    ``flash_sfa_bwd(emit="compact")``). Falls back to the single-device
    ``flash_sfa`` outside a mesh / when the ring is inapplicable."""
    if not causal:
        raise NotImplementedError(
            "ring_sfa is causal-only: the hop skip schedule (rank i "
            "finishes after i+1 hops) is the causal triangle")
    scale = d ** -0.5 if scale is None else scale
    if _ring_eligible(q_vals.shape[1], axis_name) is None:
        from repro.kernels.flash_sfa import flash_sfa
        return flash_sfa(q_vals, q_idx, k_vals, k_idx, v, d=d, causal=True,
                         scale=scale, interpret=interpret)
    return _ring_sfa(q_vals, q_idx, k_vals, k_idx, v, d, scale, axis_name,
                     interpret, block_q, block_k)


# --------------------------------------------------------------------------
# dense folded-level op (what models/attention.py calls): rtopk inside the
# region, scatter-to-dense grads per shard in the backward
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_sfa_op(q, k, v, sfa_k, d, scale, axis_name, interpret, blocks):
    out, _ = _ring_op_fwd(q, k, v, sfa_k, d, scale, axis_name, interpret,
                          blocks)
    return out


def _ring_op_fwd(q, k, v, sfa_k, d, scale, axis_name, interpret, blocks):
    mesh, nshards = _ring_eligible(q.shape[1], axis_name)
    block_q, block_k = blocks

    def body(qf, kf, vf):
        from repro.kernels.rtopk import rtopk
        qv, qi = rtopk(qf, sfa_k, interpret=interpret)
        kv, ki = rtopk(kf, sfa_k, interpret=interpret)
        o, lse = _ring_fwd_local(qv, qi, kv, ki, vf, d=d, scale=scale,
                                 nshards=nshards, axis_name=axis_name,
                                 interpret=interpret, block_q=block_q,
                                 block_k=block_k)
        return o, lse, qv, qi, kv, ki

    spec3 = _seq_spec(3, axis_name)
    o, lse, qv, qi, kv, ki = jax.shard_map(
        body, mesh=mesh, in_specs=(spec3,) * 3,
        out_specs=(spec3, _seq_spec(2, axis_name)) + (spec3,) * 4,
        check_vma=False)(q, k, v)
    return o.astype(v.dtype), (qv, qi, kv, ki, v, o, lse)


def _ring_op_bwd(sfa_k, d, scale, axis_name, interpret, blocks, res, g):
    qv, qi, kv, ki, v, o, lse = res
    mesh, nshards = _ring_eligible(qv.shape[1], axis_name)
    block_q, block_k = blocks

    def body(qv, qi, kv, ki, vf, o, lse, gf):
        from repro.kernels.code_grad import scatter_code_grads
        dqc, dkc, dv = _ring_bwd_local(qv, qi, kv, ki, vf, o, lse, gf, d=d,
                                       scale=scale, nshards=nshards,
                                       axis_name=axis_name,
                                       interpret=interpret, block_q=block_q,
                                       block_k=block_k)
        # the dense (n/P, d) dQ/dK exist only HERE, per shard, as the
        # custom_vjp contract requires — never inside a hop (top-k is
        # straight-through on the stored coordinates, paper Eq. 6)
        return scatter_code_grads(dqc, qi, d), scatter_code_grads(dkc, ki, d), dv

    spec3 = _seq_spec(3, axis_name)
    spec2 = _seq_spec(2, axis_name)
    dq, dk, dv = jax.shard_map(
        body, mesh=mesh, in_specs=(spec3,) * 6 + (spec2, spec3),
        out_specs=(spec3,) * 3, check_vma=False)(qv, qi, kv, ki, v, o, lse, g)
    dt = v.dtype
    return dq.astype(dt), dk.astype(dt), dv.astype(dt)


_ring_sfa_op.defvjp(_ring_op_fwd, _ring_op_bwd)


def ring_sfa_op(q, k, v, *, sfa_k: int, causal: bool = True,
                scale: float | None = None, axis_name: str = "seq",
                interpret: bool | None = None, block_q: int = 128,
                block_k: int = 128):
    """Dense folded-level Ring-SFA: (b·h, n, d) q/k and (b·h, n, dv) v,
    sequence sharded over the ``seq`` mesh axis. rtopk runs inside the
    shard_map region (row-wise, so the shard boundary is free); gradients
    come back dense via a per-shard local scatter. Falls back to the
    single-device rtopk -> flash_sfa composition when the ring is
    inapplicable."""
    if not causal:
        raise NotImplementedError("ring_sfa_op is causal-only")
    d = q.shape[-1]
    scale = d ** -0.5 if scale is None else scale
    if _ring_eligible(q.shape[1], axis_name) is None:
        from repro.kernels.flash_sfa import flash_sfa
        from repro.kernels.rtopk import rtopk
        qv, qi = rtopk(q, sfa_k, interpret=interpret)
        kv, ki = rtopk(k, sfa_k, interpret=interpret)
        return flash_sfa(qv, qi, kv, ki, v, d=d, causal=True, scale=scale,
                         interpret=interpret)
    return _ring_sfa_op(q, k, v, sfa_k, d, scale, axis_name, interpret,
                        (block_q, block_k))
