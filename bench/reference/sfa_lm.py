"""Plain reference of a decoder-only language model with SFA attention.

Straightforward ``jax.numpy`` in float32 with ``Precision.HIGHEST`` on every
matrix product, no kernel, no cache, no batching across requests. It follows
the paper's SFA (arXiv 2603.22300, Eq. 3-6): per head, the top-k features of
q and of k by magnitude (lowest index wins a tie) are kept, the rest set to
zero, and softmax(q~ k~^T / sqrt(d)) V is taken over all causal keys; the
backward passes through the kept coordinates only (straight-through).

The block is the configuration file's ``model`` block, of the GPT-2 kind:
LayerNorm, a GELU (tanh) MLP, learned positions, as many K/V heads as query
heads; ``check_block`` refuses any other. Weights come in the layout the
benchmark makes them (``benchlib/weights.py``): a stacked per-layer dict.

``mode="int8"``, ``"fp8"`` and ``"fp8_hybrid"`` are the controls, the
precision below bfloat16: every matrix product takes its operands rounded
to int8 (absmax scale per row of the left operand and per column of the
right one) or to float8 (one absmax scale per tensor), in the forward and
in the backward. ``fp8`` rounds everything to e4m3; ``fp8_hybrid`` is the
usual FP8 training recipe, e4m3 for weights and activations and e5m2 for
the gradients that flow back.

This module imports nothing of the system under test.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30


# ---------------------------------------------------------------- products

def _int8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.round(x / s).clip(-127, 127) * s


def _float8(dtype):
    top = float(jnp.finfo(dtype).max)

    def round_(x, axis):
        del axis                  # one scale for the tensor
        s = jnp.max(jnp.abs(x)) / top
        s = jnp.where(s > 0, s, 1.0)
        return (x / s).astype(dtype).astype(jnp.float32) * s

    return round_


def _lowered(round_, round_grad=None):
    """A matrix product on rounded operands; the backward's products round
    the incoming gradient by ``round_grad`` and the saved operands by
    ``round_``."""
    round_grad = round_grad or round_

    def prod(a, b, ra, rb):
        return jnp.matmul(ra(a, -1), rb(b, -2), precision=HIGHEST)

    @jax.custom_vjp
    def mm_(a, b):
        return prod(a, b, round_, round_)

    def fwd(a, b):
        return mm_(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        da = prod(g, jnp.swapaxes(b, -1, -2), round_grad, round_)
        db = prod(jnp.swapaxes(a, -1, -2), g, round_, round_grad)
        while db.ndim > b.ndim:       # b was broadcast over a's leading axes
            db = db.sum(0)
        return da, db

    mm_.defvjp(fwd, bwd)
    return mm_


E4M3, E5M2 = _float8(jnp.float8_e4m3fn), _float8(jnp.float8_e5m2)
LOWER = {"int8": _lowered(_int8), "fp8": _lowered(E4M3),
         "fp8_hybrid": _lowered(E4M3, E5M2)}


def mm(a, b, mode):
    """``a (..., M, K) @ b (..., K, N)`` in float32 or a control's
    precision."""
    if mode in LOWER:
        return LOWER[mode](a, b)
    return jnp.matmul(a, b, precision=HIGHEST)


# ------------------------------------------------------------------ pieces

BLOCK = {"norm": "layernorm", "act": "gelu", "glu": False, "rope": False,
         "qk_norm": False, "pos_embedding": "learned"}


def check_block(m):
    """The reference follows the GPT-2 kind of block only."""
    odd = {k: m[k] for k, v in BLOCK.items() if m[k] != v}
    if odd or m["num_kv_heads"] != m["num_heads"]:
        raise ValueError(f"the reference does not follow {odd or m}")


def norm(m, p, x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + m["norm_eps"]) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def topk_keep(x, k):
    """x with all but its k largest-magnitude features zeroed (lowest index
    wins a tie); the gradient passes through the kept features only."""
    a = jnp.abs(x)
    kth = jax.lax.top_k(a, k)[0][..., -1:]
    above = a > kth
    tie = a == kth
    room = k - above.sum(-1, keepdims=True)
    keep = above | (tie & (jnp.cumsum(tie, -1) <= room))
    return x * jax.lax.stop_gradient(keep.astype(x.dtype))


def attention(m, p, x, mode, q_block):
    """Causal SFA attention of one sequence x (n, d_model)."""
    n = x.shape[0]
    h, hd = m["num_heads"], m["head_dim"]
    qkv = mm(x, p["w_qkv"]["w"], mode).reshape(n, 3, h, hd)
    q, k, v = (qkv[:, i].transpose(1, 0, 2) for i in range(3))
    q = topk_keep(q, m["sfa_k"])
    k = topk_keep(k, m["sfa_k"])
    scale = hd ** -0.5
    outs = []
    for s in range(0, n, q_block):
        e = min(n, s + q_block)
        sc = mm(q[:, s:e], jnp.swapaxes(k[:, :e], 1, 2), mode) * scale
        causal = jnp.arange(e)[None, :] <= jnp.arange(s, e)[:, None]
        sc = jnp.where(causal[None], sc, NEG)
        pr = jax.nn.softmax(sc, axis=-1)
        outs.append(mm(pr, v[:, :e], mode))
    o = jnp.concatenate(outs, axis=1).transpose(1, 0, 2).reshape(n, h * hd)
    return mm(o, p["w_o"]["w"], mode)


def mlp(p, x, mode):
    return mm(gelu_tanh(mm(x, p["up"]["w"], mode)), p["down"]["w"], mode)


def hidden(params, m, tokens, mode, q_block, remat):
    """Final-norm hidden states (n, d_model) of one sequence."""
    x = params["embed"]["w"][tokens] + params["pos"]["w"][:tokens.shape[0]]

    def block(x, p):
        x = x + attention(m, p["attn"], norm(m, p["ln1"], x), mode, q_block)
        x = x + mlp(p["mlp"], norm(m, p["ln2"], x), mode)
        return x, None

    if remat:
        block = jax.checkpoint(block)
    x, _ = jax.lax.scan(block, x, params["segments"][0])
    return norm(m, params["final_norm"], x)


# ------------------------------------------------------------- entry points

@functools.partial(jax.jit, static_argnames=("m_items", "mode", "q_block"))
def _row_loss_grad(params, tokens, labels, m_items, mode, q_block):
    m = dict(m_items)

    def loss_sum(p):
        hs = hidden(p, m, tokens, mode, q_block, remat=True)
        logits = mm(hs, p["embed"]["w"].T, mode)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)

    return jax.value_and_grad(loss_sum)(params)


def loss_and_grads(params, m, tokens, labels, *, mode="f32", q_block=1024):
    """Mean next-token cross entropy over all rows, and its gradient,
    taken one row at a time."""
    items = tuple(sorted(m.items()))
    total, grads = 0.0, None
    for r in range(tokens.shape[0]):
        ls, g = _row_loss_grad(params, tokens[r], labels[r], items, mode,
                               q_block)
        total = total + ls
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    count = tokens.shape[0] * tokens.shape[1]
    return total / count, jax.tree.map(lambda g: g / count, grads)


def adamw(params, grads, state, step, opt):
    """AdamW with global-norm clipping and decoupled decay on every array
    stored with two or more dimensions; ``step`` counts from 1."""
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(
        lambda g: g * jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-9)),
        grads)
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, state["m"], grads)
    nu = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, state["v"],
                      grads)
    lr = opt["lr"] * min(step / max(opt["warmup_steps"], 1), 1.0)

    def upd(p, m_, v_):
        u = (m_ / (1 - b1 ** step)) / (jnp.sqrt(v_ / (1 - b2 ** step))
                                       + opt["eps"])
        if p.ndim >= 2:
            u = u + opt["weight_decay"] * p
        return p - lr * u

    return (jax.tree.map(upd, params, mu, nu), {"m": mu, "v": nu}, grads)


def train_steps(params, m, batches, opt, *, mode="f32", q_block=1024):
    """Follow ``len(batches)`` AdamW steps from ``params``. Returns each
    step's loss, the first step's clipped gradient and the final params."""
    check_block(m)
    state = {"m": jax.tree.map(jnp.zeros_like, params),
             "v": jax.tree.map(jnp.zeros_like, params)}
    losses, first = [], None
    p = params
    for i, (tokens, labels) in enumerate(batches, start=1):
        loss, g = loss_and_grads(p, m, tokens, labels, mode=mode,
                                 q_block=q_block)
        p, state, g = adamw(p, g, state, i, opt)
        losses.append(float(loss))
        if first is None:
            first = g
    return losses, first, p
