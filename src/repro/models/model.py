"""Model assembly: every assigned arch as (init, forward, prefill, decode).

Layer stacks lower as ``jax.lax.scan`` over stacked per-layer params so the
HLO is O(1) in depth (compile tractability for 60-layer/236B dry-runs).
Heterogeneous depth patterns become *segments* of scan-compatible blocks:

  dense/vlm/audio  -> [("block", L)]           (gemma3 gets a per-layer
                                                window array as scanned xs)
  moe              -> [("block", first_dense), ("block+moe", L - first_dense)]
  hybrid (jamba)   -> [("jamba", L/period)]    (8-sublayer super-block)
  ssm (rwkv6)      -> [("rwkv", L)]

Modes: "train" (loss), "prefill" (logits + caches), "decode" (one token).
Decode caches are typed ``KVCache`` pytrees (repro/core/kv_cache.py); the
attention execution path per mode is resolved through the backend registry
(repro/models/backends.py) from ``cfg.attention.backend`` /
``cfg.attention.decode_backend``.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import scopes
from repro.core.remat import checkpoint_policy, normalize_remat, record_remat
from repro.distributed.sharding import constrain
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import mamba as mb
from repro.models import moe as moe_lib
from repro.models import rwkv as rk

GLOBAL_WINDOW = 1 << 30  # "window" value meaning unrestricted (global layer)


# ==========================================================================
# segments
# ==========================================================================

def segments(cfg: ModelConfig):
    if cfg.family == "hybrid":
        return [("jamba", cfg.num_layers // cfg.hybrid_period)]
    if cfg.family == "ssm":
        return [("rwkv", cfg.num_layers)]
    if cfg.moe is not None:
        fd = cfg.moe.first_dense
        segs = []
        if fd:
            segs.append(("block_dense", fd))
        segs.append(("block_moe", cfg.num_layers - fd))
        return segs
    return [("block_dense", cfg.num_layers)]


def _window_array(cfg: ModelConfig, count: int, offset: int = 0):
    """Per-layer effective window (gemma3 local/global interleave)."""
    a = cfg.attention
    if a is None or a.window is None:
        return None
    pat = a.local_global_pattern
    out = []
    for i in range(offset, offset + count):
        if pat is not None and (i % (pat + 1)) == pat:
            out.append(GLOBAL_WINDOW)   # every (pat+1)-th layer is global
        else:
            out.append(a.window)
    return jnp.asarray(out, jnp.int32)


# ==========================================================================
# per-block init
# ==========================================================================

def _block_init(rng, cfg: ModelConfig, kind: str):
    rs = jax.random.split(rng, 8)
    if kind == "rwkv":
        return {
            "ln1": L.norm_init(cfg.d_model, "layernorm"),
            "tm": rk.rwkv_tm_init(rs[0], cfg.d_model, cfg.rwkv),
            "ln2": L.norm_init(cfg.d_model, "layernorm"),
            "cm": rk.rwkv_cm_init(rs[1], cfg.d_model, cfg.d_ff),
        }
    if kind == "jamba":
        period = cfg.hybrid_period
        subs = []
        for i in range(period):
            sub = {"ln1": L.norm_init(cfg.d_model, cfg.norm),
                   "ln2": L.norm_init(cfg.d_model, cfg.norm)}
            if i == cfg.hybrid_attn_index:
                sub["attn"] = attn.attention_init(rs[i % 8], cfg)
            else:
                sub["mamba"] = mb.mamba_init(jax.random.fold_in(rs[i % 8], 1),
                                             cfg.d_model, cfg.ssm)
            if i % cfg.moe.every == cfg.moe.every - 1:
                sub["moe"] = moe_lib.moe_init(jax.random.fold_in(rs[i % 8], 2),
                                              cfg.d_model, cfg.moe, glu=cfg.glu)
            else:
                sub["mlp"] = L.mlp_init(jax.random.fold_in(rs[i % 8], 3),
                                        cfg.d_model, cfg.d_ff, glu=cfg.glu)
            subs.append(sub)
        return {"subs": subs}
    p = {
        "ln1": L.norm_init(cfg.d_model, cfg.norm),
        "attn": attn.attention_init(rs[0], cfg),
        "ln2": L.norm_init(cfg.d_model, cfg.norm),
    }
    if kind == "block_moe":
        p["moe"] = moe_lib.moe_init(rs[1], cfg.d_model, cfg.moe, glu=cfg.glu)
    else:
        ff = cfg.d_ff
        if cfg.moe is not None:    # dense layer inside an MoE model
            ff = max(cfg.d_ff, cfg.moe.expert_dim * cfg.moe.top_k)
        p["mlp"] = L.mlp_init(rs[1], cfg.d_model, ff, glu=cfg.glu)
    return p


def init(rng, cfg: ModelConfig):
    rs = jax.random.split(rng, 4 + len(segments(cfg)))
    params: dict[str, Any] = {}
    if cfg.frontend is None or cfg.frontend.kind == "patch":
        params["embed"] = L.embed_init(rs[0], cfg.vocab_size, cfg.d_model)
    if cfg.frontend is not None:
        params["frontend"] = L.dense_init(rs[1], cfg.frontend.input_dim,
                                          cfg.d_model)
    if cfg.pos_embedding == "learned":
        params["pos"] = {"w": jax.random.normal(
            jax.random.fold_in(rs[1], 3), (cfg.max_seq_len if cfg.max_seq_len
                                           <= 65536 else 65536, cfg.d_model)) * 0.01}
    params["final_norm"] = L.norm_init(cfg.d_model, cfg.norm)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(rs[2], cfg.d_model, cfg.vocab_size)
    segs = []
    for si, (kind, count) in enumerate(segments(cfg)):
        krng = jax.random.split(rs[3 + si], count)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                               *[_block_init(krng[i], cfg, kind)
                                 for i in range(count)])
        segs.append(stacked)
    params["segments"] = segs
    return params


# ==========================================================================
# block apply (single layer; scanned)
# ==========================================================================

MOE_AUX_WEIGHT = 0.01


def _tx_block(p, x, cfg: ModelConfig, kind: str, *, window=None, positions=None,
              mode="train", cache=None, cache_len=None, slot=None):
    h = L.apply_norm(p["ln1"], x, cfg.norm)
    ao = attn.attention_apply(p["attn"], h, cfg=cfg, positions=positions,
                              window=window, mode=mode, cache=cache,
                              cache_len=cache_len, slot=slot)
    with jax.named_scope(scopes.ATTN_PROJ):
        x = x + ao.out
    h = L.apply_norm(p["ln2"], x, cfg.norm)
    if kind == "block_moe":
        mo, aux = moe_lib.moe_apply(p["moe"], h, cfg.moe, act=cfg.act,
                                    glu=cfg.glu)
        aux = MOE_AUX_WEIGHT * aux
    else:
        mo = L.mlp(p["mlp"], h, act=cfg.act, glu=cfg.glu)
        aux = jnp.zeros((), jnp.float32)
    aux = aux + cfg.sfa_distill * ao.distill          # paper Eq. 8 term
    with jax.named_scope(scopes.MLP):
        x = constrain(x + mo, ("batch", None, "embed"))
    return x, ao.cache, aux


def _rwkv_block(p, x, cfg: ModelConfig, *, mode="train", state=None):
    st_tm = state["tm"] if state is not None else None
    st_cm = state["cm"] if state is not None else None
    h = L.apply_norm(p["ln1"], x, "layernorm")
    o, st_tm = rk.rwkv_time_mix(p["tm"], h, cfg.rwkv, mode=mode, state=st_tm)
    x = x + o
    h = L.apply_norm(p["ln2"], x, "layernorm")
    o, st_cm = rk.rwkv_channel_mix(p["cm"], h, mode=mode, state=st_cm)
    x = x + o
    new_state = {"tm": st_tm, "cm": st_cm} if st_tm is not None else None
    return x, new_state


def _jamba_super(p, x, cfg: ModelConfig, *, positions=None, mode="train",
                 cache=None, cache_len=None):
    """One 8-sublayer jamba super-block. cache: {'attn':…, 'mamba': [7×state]}"""
    new_cache: dict[str, Any] = {"mamba": []}
    aux_total = jnp.zeros((), jnp.float32)
    mi = 0
    for i, sub in enumerate(p["subs"]):
        h = L.apply_norm(sub["ln1"], x, cfg.norm)
        if i == cfg.hybrid_attn_index:
            ao = attn.attention_apply(
                sub["attn"], h, cfg=cfg, positions=positions, mode=mode,
                cache=None if cache is None else cache["attn"],
                cache_len=cache_len)
            x = x + ao.out
            new_cache["attn"] = ao.cache
        else:
            st = None if cache is None else cache["mamba"][mi]
            o, st = mb.mamba_apply(sub["mamba"], h, cfg.ssm, mode=mode, state=st)
            x = x + o
            new_cache["mamba"].append(st)
            mi += 1
        h = L.apply_norm(sub["ln2"], x, cfg.norm)
        if "moe" in sub:
            mo, aux = moe_lib.moe_apply(sub["moe"], h, cfg.moe, act=cfg.act,
                                        glu=cfg.glu)
            aux_total = aux_total + MOE_AUX_WEIGHT * aux
        else:
            mo = L.mlp(sub["mlp"], h, act=cfg.act, glu=cfg.glu)
        x = constrain(x + mo, ("batch", None, "embed"))
    if mode == "train":
        new_cache = None
    return x, new_cache, aux_total


# ==========================================================================
# stack scan
# ==========================================================================

def _scan_segment(seg_params, x, cfg: ModelConfig, kind: str, count: int,
                  offset: int, *, positions, mode, caches, cache_len,
                  slot=None):
    """Scan one segment. caches: stacked (count, ...) pytree or None."""
    windows = _window_array(cfg, count, offset) if kind.startswith("block") else None

    def body(carry, xs):
        x, aux = carry
        if kind == "rwkv":
            p, cache = xs if caches is not None else (xs, None)
            x, new_cache = _rwkv_block(p, x, cfg, mode=mode, state=cache)
            aux_i = jnp.zeros((), jnp.float32)
        elif kind == "jamba":
            p, cache = xs if caches is not None else (xs, None)
            x, new_cache, aux_i = _jamba_super(
                p, x, cfg, positions=positions, mode=mode, cache=cache,
                cache_len=cache_len)
        else:
            if windows is not None:
                if caches is not None:
                    p, w, cache = xs
                else:
                    (p, w), cache = xs, None
            else:
                w = None
                p, cache = xs if caches is not None else (xs, None)
            x, new_cache, aux_i = _tx_block(
                p, x, cfg, kind, window=w, positions=positions, mode=mode,
                cache=cache, cache_len=cache_len, slot=slot)
        return (x, aux + aux_i), new_cache

    # Remat applies to gradient-free "eval" forwards too (long-context
    # scoring is activation-memory-bound the same way training is); the
    # cache-carrying serving modes never checkpoint.
    rm = normalize_remat(cfg.remat)
    if rm != "none" and mode in ("train", "eval"):
        applied = rm
        if rm == "codes":
            reason = attn.remat_codes_ineligible_reason(cfg)
            if reason is not None:
                # nothing in this stack tags the code saveables: a named
                # policy would silently save nothing. Degrade to "full"
                # explicitly and say why (reports component "remat").
                applied = "full"
            record_remat(f"{cfg.name}/scan[{kind}]", rm, applied, reason)
        pol = checkpoint_policy(applied)
        body = (jax.checkpoint(body, policy=pol) if pol is not None
                else jax.checkpoint(body))

    if windows is not None:
        xs = (seg_params, windows, caches) if caches is not None \
            else (seg_params, windows)
    else:
        xs = (seg_params, caches) if caches is not None else seg_params
    (x, aux), new_caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    return x, aux, (new_caches if mode != "train" else None)


def _apply_stack(params, x, cfg: ModelConfig, *, positions, mode,
                 caches=None, cache_len=None, slot=None):
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = []
    offset = 0
    for si, (kind, count) in enumerate(segments(cfg)):
        seg_cache = caches[si] if caches is not None else None
        x, aux, nc = _scan_segment(params["segments"][si], x, cfg, kind, count,
                                   offset, positions=positions, mode=mode,
                                   caches=seg_cache, cache_len=cache_len,
                                   slot=slot)
        aux_total = aux_total + aux
        new_caches.append(nc)
        offset += count
    return x, aux_total, (new_caches if mode != "train" else None)


# ==========================================================================
# embedding / head
# ==========================================================================

@jax.named_scope(scopes.EMBED)
def _embed_inputs(params, batch, cfg: ModelConfig, dtype):
    """Returns (hidden (b, n, d), label_mask or None)."""
    if cfg.family == "audio":
        h = L.dense(params["frontend"], batch["frames"].astype(dtype), dtype)
        return h, None
    toks = batch["tokens"]
    h = L.embed(params["embed"], toks, dtype) * (cfg.d_model ** 0.5
                                                 if cfg.norm == "rmsnorm" else 1.0)
    if cfg.family == "vlm" and "patches" in batch:
        pre = L.dense(params["frontend"], batch["patches"].astype(dtype), dtype)
        h = jnp.concatenate([pre, h], axis=1)
    if cfg.pos_embedding == "learned":
        n = h.shape[1]
        h = h + params["pos"]["w"][:n].astype(dtype)[None]
    return h, None


def _head_weights(params, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return params["embed"]["w"]
    return params["lm_head"]["w"].T      # (vocab, d)


@jax.named_scope(scopes.LOSS)
def _logits(h, params, cfg: ModelConfig):
    """f32 logits of the final hidden states ``h (..., d)``."""
    return h.astype(jnp.float32) @ _head_weights(params, cfg).T.astype(jnp.float32)


@jax.named_scope(scopes.EMBED)
def _embed_at(params, tokens, positions, cfg: ModelConfig, dtype):
    """Embeddings of ``tokens (b, c)`` at ``positions (b, c)`` for the
    serving steps. A position past the learned table reads its last row
    (``mode="clip"``), so a decode step and a chunk or verify pass that
    reach the same position read the same row."""
    h = L.embed(params["embed"], tokens, dtype) * (
        cfg.d_model ** 0.5 if cfg.norm == "rmsnorm" else 1.0)
    if cfg.pos_embedding == "learned":
        h = h + jnp.take(params["pos"]["w"], positions, axis=0,
                         mode="clip").astype(dtype)
    return h


# ==========================================================================
# public API
# ==========================================================================

class ForwardOut(NamedTuple):
    loss: Optional[jax.Array]
    logits: Optional[jax.Array]
    caches: Optional[list]
    aux_loss: Optional[jax.Array]


def loss_fn(params, batch, cfg: ModelConfig, *, aux_weight: float = 1.0):
    """Training loss: chunked vocab-parallel CE + pre-weighted aux terms
    (MoE load-balance ×0.01, SFA distillation ×cfg.sfa_distill)."""
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    h, _ = _embed_inputs(params, batch, cfg, dtype)
    h = constrain(h, ("batch", None, "embed"))
    n = h.shape[1]
    positions = jnp.arange(n)[None, :]
    h, aux, _ = _apply_stack(params, h, cfg, positions=positions, mode="train")
    h = L.apply_norm(params["final_norm"], h, cfg.norm)
    labels = batch["labels"]
    if labels.shape[1] < h.shape[1]:     # vlm: no labels on the patch prefix
        pad = h.shape[1] - labels.shape[1]
        labels = jnp.pad(labels, ((0, 0), (pad, 0)), constant_values=-1)
    loss, cnt = L.chunked_cross_entropy(h, _head_weights(params, cfg), labels,
                                        chunk=cfg.loss_chunk)
    return loss + aux_weight * aux, {"ce": loss, "aux": aux, "tokens": cnt}


def forward_logits(params, batch, cfg: ModelConfig, *, mode="train"):
    """Full-sequence logits (small models / eval / NIAH scoring)."""
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    h, _ = _embed_inputs(params, batch, cfg, dtype)
    n = h.shape[1]
    positions = jnp.arange(n)[None, :]
    h, aux, caches = _apply_stack(params, h, cfg, positions=positions,
                                  mode=mode)
    h = L.apply_norm(params["final_norm"], h, cfg.norm)
    return ForwardOut(None, _logits(h, params, cfg), caches, aux)


def prefill(params, batch, cfg: ModelConfig):
    """Prefill: last-position logits + caches for the decode engine."""
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    h, _ = _embed_inputs(params, batch, cfg, dtype)
    n = h.shape[1]
    positions = jnp.arange(n)[None, :]
    h, _, caches = _apply_stack(params, h, cfg, positions=positions,
                                mode="prefill")
    h = L.apply_norm(params["final_norm"], h[:, -1:], cfg.norm)
    return _logits(h[:, 0], params, cfg), caches


def decode_step(params, token, caches, cache_len, cfg: ModelConfig):
    """One decode step. token: (b,) int32; cache_len: (b,) int32 — number of
    tokens already in the cache. Returns (logits (b, vocab), new caches)."""
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    positions = cache_len[:, None]
    h = _embed_at(params, token[:, None], positions, cfg, dtype)
    h, _, new_caches = _apply_stack(params, h, cfg, positions=positions,
                                    mode="decode", caches=caches,
                                    cache_len=cache_len)
    h = L.apply_norm(params["final_norm"], h, cfg.norm)
    return _logits(h[:, 0], params, cfg), new_caches


def prefill_chunk(params, tokens, caches, offset, valid, slot, cfg: ModelConfig):
    """One chunk of a paged prefill: land ``tokens (1, C)`` of ``slot`` at
    positions ``offset..offset+C-1`` into the paged caches and return the
    logits at the last *valid* chunk position (``valid <= C``; trailing pad
    tokens are written but always masked/overwritten before any read).

    Each chunk query is scored as a single-token decode at its own prefix
    length, through the decode backend's multi-token verify entry (see
    ``attention_apply`` mode="chunk"), so interleaving chunks with decode
    steps never changes which cache prefix a query sees."""
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    positions = offset + jnp.arange(tokens.shape[1])[None, :]
    h = _embed_at(params, tokens, positions, cfg, dtype)
    h, _, new_caches = _apply_stack(params, h, cfg, positions=positions,
                                    mode="chunk", caches=caches,
                                    cache_len=offset, slot=slot)
    h = L.apply_norm(params["final_norm"], h, cfg.norm)
    hv = jax.lax.dynamic_index_in_dim(h[0], valid - 1, 0, keepdims=False)
    return _logits(hv, params, cfg), new_caches


def verify_step(params, tokens, caches, offset, slot, cfg: ModelConfig):
    """Speculative verify: score ``tokens (1, C)`` of ``slot`` (the pending
    token + C-1 drafted tokens) at positions ``offset..offset+C-1`` in one
    batched full-k pass, returning the logits at EVERY position
    ``(C, vocab)`` plus the updated caches.

    Structurally ``prefill_chunk`` with two differences: attention runs in
    mode="verify" (the backend's multi-token verify kernel, each query at
    its own causal length), and all C positions' logits come back — the
    greedy acceptance rule compares drafted token j+1 against
    ``argmax(logits[j])``. The chunk write lands FULL-k codes at all C
    positions, overwriting whatever the low-k' draft pass wrote there."""
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    positions = offset + jnp.arange(tokens.shape[1])[None, :]
    h = _embed_at(params, tokens, positions, cfg, dtype)
    h, _, new_caches = _apply_stack(params, h, cfg, positions=positions,
                                    mode="verify", caches=caches,
                                    cache_len=offset, slot=slot)
    h = L.apply_norm(params["final_norm"], h, cfg.norm)
    return _logits(h[0], params, cfg), new_caches


def init_decode_caches(cfg: ModelConfig, batch: int, max_len: int,
                       dtype=jnp.bfloat16):
    """Stacked (per segment) decode caches matching _apply_stack layout."""
    def stack(trees):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
    out = []
    for kind, count in segments(cfg):
        if kind == "rwkv":
            one = rk.rwkv_init_state(batch, cfg.d_model, cfg.rwkv, dtype)
        elif kind == "jamba":
            one = {"attn": attn.init_cache(cfg, batch, max_len, dtype),
                   "mamba": [mb.mamba_init_state(batch, cfg.d_model, cfg.ssm,
                                                 dtype)
                             for _ in range(cfg.hybrid_period - 1)]}
        else:
            one = attn.init_cache(cfg, batch, max_len, dtype)
        out.append(stack([one] * count))
    return out


def init_paged_decode_caches(cfg: ModelConfig, *, slots: int, num_pages: int,
                             page_size: int, max_pages: int,
                             dtype=jnp.bfloat16):
    """Stacked paged decode caches (one shared pool per layer, block table
    replicated per layer inside the pytree so the scanned step functions
    keep their signatures — the engine swaps every replica at once)."""
    segs = segments(cfg)
    if any(kind in ("rwkv", "jamba") for kind, _ in segs):
        raise NotImplementedError(
            f"paged decode caches cover attention KV caches only; "
            f"family={cfg.family!r} carries recurrent state")

    def stack(trees):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

    out = []
    for kind, count in segs:
        one = attn.init_paged_cache(cfg, slots=slots, num_pages=num_pages,
                                    page_size=page_size, max_pages=max_pages,
                                    dtype=dtype)
        out.append(stack([one] * count))
    return out
