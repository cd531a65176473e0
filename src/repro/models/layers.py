"""Shared model layers: norms, MLPs, RoPE, embeddings — pure-JAX, pytree params.

Params are nested dicts of jnp arrays. Every layer is a pair of functions:
``<layer>_init(rng, ...) -> params`` and ``<layer>(params, x, ...) -> y``.
Compute runs in the activation dtype (bf16 by default); params are fp32 and
cast at use ("param_dtype=fp32, compute bf16" mixed precision).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import scopes
from repro.distributed.shard import run_tp
from repro.kernels.code_grad import code_grad_dw, code_grad_dx


def _split(rng, n):
    return jax.random.split(rng, n)


def dense_init(rng, in_dim: int, out_dim: int, *, scale: float | None = None):
    scale = scale if scale is not None else in_dim ** -0.5
    return {"w": jax.random.normal(rng, (in_dim, out_dim), jnp.float32) * scale}


def dense(params, x, dtype=None):
    w = params["w"]
    if dtype is not None:
        w = w.astype(dtype)
    return x @ w


@jax.named_scope(scopes.CODE_GRAD)
def sparse_proj_bwd(x, w_heads, g_vals, g_idx, *, d: int,
                    interpret: bool | None = None):
    """Backward of a head-blocked projection ``y_h = x @ w_h`` whose upstream
    cotangent arrives as compact (n, k) code-gradients (DESIGN.md §3).

    This is the projection-side half of the ``bwd_emit="compact"`` train
    path: the FlashSFA backward kernel emits dQ̃/dK̃ as code values aligned to
    the stored indices, and this seam consumes them directly —

        dx = Σ_h scatter(g_h) @ w_hᵀ        (kernels/code_grad.py, Pallas)
        dw_h = xᵀ @ scatter(g_h)

    with the scatter living only in VMEM tiles, so the dense (n, d)
    gradient never round-trips through HBM.

    x: (n, m) projection input; w_heads: (H, m, d) per-head weight blocks;
    g_vals/g_idx: (H, n, k). Returns (dx (n, m), dw (H, m, d)), both f32.

    Under tensor parallelism the head axis splits over the model mesh axis
    (``distributed/shard.py``): dW stays local to each head shard
    (column-parallel), and dx — the ONE cross-device reduction of the
    compact seam backward — psums its per-shard partials inside the
    shard_map region, the classic column-parallel dL/dx all-reduce.
    """
    def fn(xx, ww, gv, gi):
        dx = code_grad_dx(gv, gi, ww, d=d, interpret=interpret)
        dw = code_grad_dw(xx, gv, gi, d=d, interpret=interpret)
        return dx, dw

    return run_tp(fn, (x, w_heads, g_vals, g_idx),
                  in_axes=(None, 0, 0, 0), out_axes=(None, 0),
                  reduce_out=(0,))


def norm_init(dim: int, kind: str = "rmsnorm"):
    p = {"scale": jnp.ones((dim,), jnp.float32)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((dim,), jnp.float32)
    return p


@jax.named_scope(scopes.NORM)
def apply_norm(params, x, kind: str = "rmsnorm", eps: float = 1e-6):
    """Norms with f32 *statistics* but activation-dtype elementwise math.

    Keeping the tensor-sized ops in bf16 keeps their backward cotangents
    bf16 too — halving the cross-device bytes of every sharding transition
    that crosses a norm (EXPERIMENTS.md §Perf i4). Reductions stay f32.
    """
    dt = x.dtype
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        out = x * r.astype(dt) * params["scale"].astype(dt)
    else:
        mu = xf.mean(-1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
        r = jax.lax.rsqrt(var + eps)
        out = (x - mu.astype(dt)) * r.astype(dt) * \
            params["scale"].astype(dt) + params["bias"].astype(dt)
    return out


_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
         "relu": jax.nn.relu, "relu2": lambda x: jnp.square(jax.nn.relu(x))}


def mlp_init(rng, d_model: int, d_ff: int, *, glu: bool = True):
    r1, r2, _ = _split(rng, 3)
    if glu:
        # fused up+gate (§Perf i7): one column-parallel matmul -> one
        # backward dL/dx all-reduce instead of two
        return {"up_gate": dense_init(r1, d_model, 2 * d_ff),
                "down": dense_init(r2, d_ff, d_model)}
    return {"up": dense_init(r1, d_model, d_ff),
            "down": dense_init(r2, d_ff, d_model)}


@jax.named_scope(scopes.MLP)
def mlp(params, x, *, act: str = "silu", glu: bool = True):
    dt = x.dtype
    if glu:
        ug = dense(params["up_gate"], x, dt)
        h, g = jnp.split(ug, 2, axis=-1)
        h = h * _ACTS[act](g)
    else:
        h = _ACTS[act](dense(params["up"], x, dt))
    return dense(params["down"], h, dt)


def embed_init(rng, vocab: int, d_model: int):
    return {"w": jax.random.normal(rng, (vocab, d_model), jnp.float32) * 0.02}


@jax.named_scope(scopes.EMBED)
def embed(params, tokens, dtype=jnp.bfloat16):
    return params["w"].astype(dtype)[tokens]


def rope(x: jax.Array, positions: jax.Array, *, theta: float = 10_000.0,
         rot_dim: int | None = None) -> jax.Array:
    """Rotary embedding on (..., seq, heads, head_dim); positions (..., seq).

    If rot_dim < head_dim, only the leading rot_dim dims rotate (MLA rope
    head, or partial-rotary models)."""
    d = x.shape[-1]
    rot = rot_dim or d
    freqs = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = positions[..., None].astype(jnp.float32) * freqs          # (..., s, rot/2)
    cos = jnp.cos(ang)[..., None, :]                                # (..., s, 1, rot/2)
    sin = jnp.sin(ang)[..., None, :]
    x1 = x[..., 0:rot:2].astype(jnp.float32)
    x2 = x[..., 1:rot:2].astype(jnp.float32)
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    rotated = jnp.stack([r1, r2], axis=-1).reshape(*x.shape[:-1], rot)
    if rot < d:
        rotated = jnp.concatenate([rotated, x[..., rot:].astype(jnp.float32)], -1)
    return rotated.astype(x.dtype)


def rope_code_vjp(vals: jax.Array, idx: jax.Array, positions: jax.Array, *,
                  theta: float = 10_000.0, rot_dim: int) -> jax.Array:
    """Rope's vjp applied directly on (…, 2k) pair-closure code cotangents.

    RoPE rotates head dims in (2j, 2j+1) pairs, so a k-sparse post-rope
    cotangent is exactly 2k-sparse pre-rope on the *known* pair closure of
    the stored indices (DESIGN.md §3). ``vals``/``idx`` follow the
    ``emit="compact2"`` layout (``kernels.flash_sfa_bwd.pair_closure_indices``):
    two concatenated k-wide halves holding each stored index's even and odd
    pair member. Per closure entry t the inverse rotation Rᵀ of the pair's
    angle mixes the two halves in place:

        dpre_even = cos·ge + sin·go      dpre_odd = −sin·ge + cos·go

    Entries whose base index is at or beyond ``rot_dim`` (partial rotation)
    never rotated, so their cotangent passes through untouched — the closure
    left them unwidened (odd half pinned to zero), and the identity branch
    here keeps it that way. O(n·k) elementwise work on the code values; no
    scatter, no dense rebuild, no (n, d) tensor anywhere.

    vals/idx: (…, 2k); positions: broadcastable to vals.shape[:-1].
    Returns the pre-rope code cotangents, same shape/indices/dtype.
    """
    kw = vals.shape[-1] // 2
    ge = vals[..., :kw].astype(jnp.float32)
    go = vals[..., kw:].astype(jnp.float32)
    base = idx[..., :kw]
    rotated = base < rot_dim
    # pair j's frequency: theta^(-2j/rot_dim), exactly rope()'s table
    freqs = theta ** (-(base // 2 * 2).astype(jnp.float32) / rot_dim)
    ang = positions[..., None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    de = jnp.where(rotated, c * ge + s * go, ge)
    do = jnp.where(rotated, c * go - s * ge, go)
    return jnp.concatenate([de, do], axis=-1).astype(vals.dtype)


@jax.named_scope(scopes.LOSS)
def chunked_cross_entropy(hidden: jax.Array, emb_w: jax.Array,
                          labels: jax.Array, *, chunk: int = 512,
                          mask: jax.Array | None = None):
    """Vocab-parallel, sequence-chunked CE loss.

    hidden: (b, n, d); emb_w: (vocab, d) (the tied LM head); labels: (b, n).
    Logits are only ever materialized per chunk — with vocab sharded over the
    model axis, the per-device transient is (b_local, chunk, vocab_local),
    which is what lets 262k-vocab × 1M-token batches fit the dry-run.
    Returns (mean loss, token count).
    """
    b, n, d = hidden.shape
    nchunks = -(-n // chunk)
    pad = nchunks * chunk - n
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
        if mask is not None:
            mask = jnp.pad(mask, ((0, 0), (0, pad)))
    if mask is None:
        mask = labels >= 0
    hidden = hidden.reshape(b, nchunks, chunk, d)
    labels = labels.reshape(b, nchunks, chunk)
    mask = mask.reshape(b, nchunks, chunk)

    def body(carry, xs):
        h, y, m = xs                                  # (b, chunk, d) ...
        logits = (h.astype(jnp.float32) @ emb_w.T.astype(jnp.float32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(y, 0)[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * m
        loss_sum, cnt = carry
        return (loss_sum + nll.sum(), cnt + m.sum()), None

    (loss_sum, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (jnp.moveaxis(hidden, 1, 0), jnp.moveaxis(labels, 1, 0),
         jnp.moveaxis(mask, 1, 0)))
    return loss_sum / jnp.maximum(cnt, 1.0), cnt
