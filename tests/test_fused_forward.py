"""ISSUE 8: fused projection -> top-k -> FlashSFA forward + block skipping.

Pins, in order: the fused ``proj_rtopk`` kernel against the unfused
projection -> rope -> rtopk composition; the no-dense-q/k-write grep ban on
the fused seam path (same idiom as the ``code_grad`` no-scatter ban in
tests/test_code_grad.py); the forward pad-edge matrix (ragged nq/nk ×
causal × residuals × block_skip); the block-skip scheduler's exactness on
structured-sparsity data that actually exercises the zero-overlap closed
form; the seam-level fused == unfused parity (outputs AND gradients — the
residual tuple is identical by construction); and the ``CompactSeamReport``
``fused_fwd`` field.
"""
import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AttentionConfig, ModelConfig
from repro.kernels import ops, ref as REF
from repro.kernels.flash_sfa import block_skip_stats, flash_sfa
from repro.kernels.rtopk import proj_rtopk, rtopk
from repro.models import attention as attn
from repro.models.layers import rope

ATOL = 1e-4


# --------------------------------------------------------------------------
# proj_rtopk: fused projection -> [rope] -> top-k
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n,rope_on", [(128, False), (200, False),
                                       (200, True), (64, True)])
def test_proj_rtopk_matches_unfused_composition(rng, n, rope_on):
    b, m, nh, d, k = 2, 48, 3, 64, 8
    x = jax.random.normal(rng, (b, n, m))
    w = jax.random.normal(jax.random.fold_in(rng, 1), (nh, m, d)) * 0.1
    pos = jnp.broadcast_to(jnp.arange(n), (b, n))
    spec = (10_000.0, d) if rope_on else None
    vf, idf = proj_rtopk(x, w, pos if rope_on else None, k=k,
                         rope_spec=spec, block_n=128)
    y = jnp.einsum("bnm,hmd->bhnd", x, w)
    if rope_on:
        y = rope(y.transpose(0, 2, 1, 3), pos).transpose(0, 2, 1, 3)
    vu, iu = rtopk(y.reshape(b * nh, n, d), k)
    np.testing.assert_array_equal(np.asarray(idf).reshape(b * nh, n, k),
                                  np.asarray(iu))
    np.testing.assert_allclose(np.asarray(vf).reshape(b * nh, n, k),
                               np.asarray(vu), atol=1e-5)


def test_fused_qk_codes_matches_and_repeats_gqa(rng):
    """GQA: key codes computed at hkv heads then repeated — group members
    must carry IDENTICAL indices (the backward's dk group-sum invariant)."""
    b, n, m, h, hkv, hd, k = 2, 96, 48, 4, 2, 64, 8
    w = jax.random.normal(rng, (m, (h + 2 * hkv) * hd)) * 0.1
    x = jax.random.normal(jax.random.fold_in(rng, 1), (b, n, m))
    pos = jnp.broadcast_to(jnp.arange(n), (b, n))
    qv, qi, kv_, ki = ops.fused_qk_codes(x, w, pos, h=h, hkv=hkv, hd=hd,
                                         sfa_k=k, rope_spec=(10_000.0, hd))
    group = h // hkv
    ki4 = np.asarray(ki).reshape(b, hkv, group, n, k)
    np.testing.assert_array_equal(ki4[:, :, 0], ki4[:, :, 1])
    # parity with the unfused seam's q/k construction
    dt = x.dtype
    qkv = x @ w.astype(dt)
    q, kk, _ = jnp.split(qkv, [h * hd, (h + hkv) * hd], axis=-1)
    q = rope(q.reshape(b, n, h, hd), pos)
    kk = rope(kk.reshape(b, n, hkv, hd), pos)
    kk = jnp.repeat(kk, group, axis=2)
    qv_r, qi_r = rtopk(ops.fold_heads(q), k)
    kv_r, ki_r = rtopk(ops.fold_heads(kk), k)
    np.testing.assert_array_equal(np.asarray(qi), np.asarray(qi_r))
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ki_r))
    np.testing.assert_allclose(np.asarray(qv), np.asarray(qv_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(kv_), np.asarray(kv_r), atol=1e-5)


def test_fused_path_has_no_dense_qk_hbm_write():
    """Grep-able regression ban (same idiom as the code_grad no-scatter
    ban): the fused seam's q/k code producer must never materialize a dense
    (n, d) q/k — no rope/expand/fold/matmul op may appear in its source.
    All of that runs inside ``proj_rtopk``'s VMEM tile."""
    src = inspect.getsource(ops.fused_qk_codes)
    for banned in ("rope(", "expand_kv", "fold_heads", "einsum", "@",
                   "dot_general", "jnp.matmul"):
        assert banned not in src, (
            f"fused_qk_codes contains {banned!r} — a dense q/k HBM "
            f"round-trip snuck back into the fused forward")


def test_proj_rtopk_emits_canonical_padded_rows(rng):
    """Fused-emit invariant shared with ``_densify_block``: any row whose
    selection ties out at zero magnitude emits (idx ascending, val=0.0)
    slots — exactly the padded-row pattern that must densify to zeros."""
    b, n, m, nh, d, k = 1, 64, 16, 1, 32, 8
    x = jnp.zeros((b, n, m))                    # all-zero projection rows
    w = jax.random.normal(rng, (nh, m, d))
    vals, idx = proj_rtopk(x, w, k=k, block_n=64)
    np.testing.assert_array_equal(np.asarray(vals), 0.0)
    np.testing.assert_array_equal(
        np.asarray(idx), np.broadcast_to(np.arange(k), (b, nh, n, k)))


# --------------------------------------------------------------------------
# forward pad-edge matrix (satellite: ragged nq/nk × causal × residuals)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("nq,nk", [(100, 160), (96, 70)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("residuals", [True, False])
@pytest.mark.parametrize("block_skip", [False, True])
def test_forward_pad_edge_matrix(rng, nq, nk, causal, residuals, block_skip):
    bh, d, k, dv = 2, 64, 8, 64
    q = jax.random.normal(jax.random.fold_in(rng, 1), (bh, nq, d))
    kk = jax.random.normal(jax.random.fold_in(rng, 2), (bh, nk, d))
    v = jax.random.normal(jax.random.fold_in(rng, 3), (bh, nk, dv))
    qv, qi = REF.rtopk_ref(q, k)
    kv_, ki = REF.rtopk_ref(kk, k)
    out = flash_sfa(qv, qi, kv_, ki, v, d=d, causal=causal, block_q=64,
                    block_k=64, return_residuals=residuals,
                    block_skip=block_skip)
    if residuals:
        out, lse = out
        assert lse.shape == (bh, nq)
        # the padded-row guard: every returned lse row is a REAL row that
        # saw at least one live key tile — a padded/garbage row would sit
        # at ~NEG_INF and poison the backward's per-row rescale
        assert np.asarray(lse).min() > -1e29
    ref = REF.flash_sfa_ref(qv, qi, kv_, ki, v, d=d, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=ATOL)


def test_ragged_backward_never_consumes_padded_lse(rng):
    """Satellite-2 pin from the other side: gradients through the pallas
    custom_vjp at a ragged n (fully-padded q tiles exist in the kernel grid)
    match the XLA straight-through oracle — garbage padded-row lse leaking
    into the backward would break this."""
    b, n, h, d, k = 1, 100, 2, 64, 8
    q = jax.random.normal(jax.random.fold_in(rng, 1), (b, n, h, d))
    kk = jax.random.normal(jax.random.fold_in(rng, 2), (b, n, h, d))
    v = jax.random.normal(jax.random.fold_in(rng, 3), (b, n, h, d))

    def loss(bwd_impl):
        def f(q, kk, v):
            o = ops.sfa_attention_op(q, kk, v, sfa_k=k, impl="pallas",
                                     bwd_impl=bwd_impl)
            return jnp.sum(o * jnp.cos(jnp.arange(o.size,
                                                  dtype=o.dtype)
                                       .reshape(o.shape)))
        return f

    gp = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, kk, v)
    gx = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, kk, v)
    for a, b_ in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-3)


# --------------------------------------------------------------------------
# block skipping: exactness where it actually skips
# --------------------------------------------------------------------------

def _disjoint_codes(rng, bh, n, d, k):
    """Structured sparsity: q lives on the low feature half, k on the high
    half — every (q-tile, k-tile) has an empty intersection, forcing the
    level-1 closed-form path (random data saturates occupancy instead)."""
    half = d // 2
    xq = jnp.zeros((bh, n, d)).at[..., :half].set(
        jax.random.normal(jax.random.fold_in(rng, 1), (bh, n, half)))
    xk = jnp.zeros((bh, n, d)).at[..., half:].set(
        jax.random.normal(jax.random.fold_in(rng, 2), (bh, n, half)))
    qv, qi = REF.rtopk_ref(xq, k)
    kv_, ki = REF.rtopk_ref(xk, k)
    return qv, qi, kv_, ki


@pytest.mark.parametrize("causal", [True, False])
def test_block_skip_zero_overlap_closed_form(rng, causal):
    bh, n, d, k, dv = 2, 192, 64, 8, 64
    qv, qi, kv_, ki = _disjoint_codes(rng, bh, n, d, k)
    v = jax.random.normal(jax.random.fold_in(rng, 3), (bh, n, dv))
    s0, s1, s2 = block_skip_stats(qv, qi, kv_, ki, d=d, causal=causal,
                                  block_q=64, block_k=64)
    assert float(s1) > 0, "disjoint features must hit the level-1 path"
    if causal:
        assert float(s0) > 0, "causal grids must skip dead tiles"
    assert abs(float(s0) + float(s1) + float(s2) - 1.0) < 1e-6
    out = flash_sfa(qv, qi, kv_, ki, v, d=d, causal=causal, block_q=64,
                    block_k=64, block_skip=True)
    ref = REF.flash_sfa_ref(qv, qi, kv_, ki, v, d=d, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=ATOL)


def test_block_skip_occupancy_ignores_value_zero_entries(rng):
    """Padded code rows carry idx=0 × k with val=0 — they must NOT pin
    feature 0 occupied (they contribute exactly 0 to every score), or the
    zero-overlap skip would silently die on any padded/ragged input."""
    bh, n, d, k, dv = 1, 128, 64, 8, 64
    qv, qi, kv_, ki = _disjoint_codes(rng, bh, n, d, k)
    # forge fully-padded rows in the middle of a tile
    qv = qv.at[:, 10:20].set(0.0)
    qi = qi.at[:, 10:20].set(0)
    _, s1, _ = block_skip_stats(qv, qi, kv_, ki, d=d, causal=False,
                                block_q=64, block_k=64)
    assert float(s1) == 1.0, (
        "value-zero entries leaked into the occupancy bitmap")
    v = jax.random.normal(jax.random.fold_in(rng, 3), (bh, n, dv))
    out = flash_sfa(qv, qi, kv_, ki, v, d=d, causal=False, block_q=64,
                    block_k=64, block_skip=True)
    ref = REF.flash_sfa_ref(qv, qi, kv_, ki, v, d=d, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=ATOL)


# --------------------------------------------------------------------------
# block-skip pre-pass: packed OR words == the scatter-max + einsum formula
# --------------------------------------------------------------------------

def _scatter_block_maps(q_vals, q_idx, k_vals, k_idx, *, d, causal, block_q,
                        block_k, nq_real, nk_real):
    """Oracle: the unpacked f32 occupancy built by a scatter-max and
    intersected by an einsum, with the level/fetch rule of ``_block_maps``."""
    def occupancy(vals, idx, block):
        bh, n, kq = idx.shape
        nb = n // block
        live = (vals.reshape(bh, nb, block * kq) != 0).astype(jnp.float32)
        return jnp.zeros((bh, nb, d), jnp.float32).at[
            jnp.arange(bh)[:, None, None], jnp.arange(nb)[None, :, None],
            idx.reshape(bh, nb, block * kq)].max(live, mode="drop")

    occ_q = occupancy(q_vals, q_idx, block_q)
    occ_k = occupancy(k_vals, k_idx, block_k)
    overlap = jnp.einsum("bqd,bkd->bqk", occ_q, occ_k) > 0.5
    nqb, nkb = occ_q.shape[1], occ_k.shape[1]
    qs = jnp.arange(nqb)[:, None] * block_q
    ks = jnp.arange(nkb)[None, :] * block_k
    dead = jnp.broadcast_to(qs >= nq_real, (nqb, nkb))
    full = ks + block_k <= nk_real
    if causal:
        dead = dead | (ks > qs + block_q - 1)
        full = full & (ks + block_k - 1 <= qs)
    level = jnp.where(dead[None], 0,
                      jnp.where(full[None] & ~overlap, 1, 2)).astype(jnp.int32)
    jidx = jnp.where(level == 2, jnp.arange(nkb)[None, None, :], -1)
    fetch = jnp.maximum(jax.lax.cummax(jidx, axis=2), 0).astype(jnp.int32)
    return level, fetch


def _edge_codes(seed, bh, n, d, k, block):
    """Clustered codes: every row of a tile stores the tile's two features,
    one from the word edges (0, 31, 32, 63, ...) and one uniform over d,
    with nonzero values; the other k-2 slots hold random indices with value
    0, which the occupancy must ignore. Two tiles overlap iff they share a
    feature, so a lost, misplaced or aliased bit flips a level."""
    r = np.random.default_rng(seed)
    edges = np.array([e for e in (0, 31, 32, 63, 64, 95, 96, 127) if e < d])
    nb = -(-n // block)
    feats = np.stack([r.choice(edges, size=(bh, nb)),
                      r.integers(0, d, size=(bh, nb))], axis=-1)
    idx = r.integers(0, d, size=(bh, n, k)).astype(np.int32)
    idx[..., :2] = np.repeat(feats, block, axis=1)[:, :n]
    vals = np.zeros((bh, n, k), np.float32)
    vals[..., :2] = r.choice([-1.0, 1.0], size=(bh, n, 2))
    return jnp.asarray(vals), jnp.asarray(idx)


@pytest.mark.parametrize("codes", ["random", "disjoint", "edges"])
@pytest.mark.parametrize("nq,nk", [(256, 256), (200, 136)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("d", [64, 128])
def test_block_maps_match_scatter_oracle(rng, codes, nq, nk, causal, d):
    """Level and fetch maps equal the scatter-max formula's, integer for
    integer: ragged nq/nk padded as ``flash_sfa`` pads them, value-0 rows
    in mid-tile, and codes where level 1 fires."""
    from repro.kernels.flash_sfa import _block_maps, _pad_codes
    bh, k, block = 3, d // 8, 64
    key = jax.random.fold_in(rng, 7 * d + nq)
    if codes == "random":
        qv, qi = REF.rtopk_ref(jax.random.normal(key, (bh, nq, d)), k)
        kv_, ki = REF.rtopk_ref(
            jax.random.normal(jax.random.fold_in(key, 1), (bh, nk, d)), k)
    elif codes == "disjoint":
        qv, qi, _, _ = _disjoint_codes(key, bh, nq, d, k)
        _, _, kv_, ki = _disjoint_codes(key, bh, nk, d, k)
    else:
        qv, qi = _edge_codes(nq + d, bh, nq, d, k, block)
        kv_, ki = _edge_codes(nk + d + 1, bh, nk, d, k, block)
    # canonical padded rows (idx=0 × k, val=0) in the middle of a tile
    qv, qi = qv.at[:, 10:20].set(0), qi.at[:, 10:20].set(0)
    kv_, ki = kv_.at[:, 70:90].set(0), ki.at[:, 70:90].set(0)
    padded = _pad_codes(qv, qi, kv_, ki, None, block, block)[:4]
    kw = dict(d=d, causal=causal, block_q=block, block_k=block, nq_real=nq,
              nk_real=nk)
    level, fetch = _block_maps(*padded, **kw)
    want_level, want_fetch = _scatter_block_maps(*padded, **kw)
    np.testing.assert_array_equal(np.asarray(level), np.asarray(want_level))
    np.testing.assert_array_equal(np.asarray(fetch), np.asarray(want_fetch))
    if codes != "random":
        assert (np.asarray(level) == 1).any(), "level 1 never fired"
        assert (np.asarray(level) == 2).any() or codes == "disjoint"


def test_block_maps_never_scatter():
    """Grep-able regression (same idiom as the code_grad no-scatter ban):
    the pre-pass, lowered at gpt2s-train-4k's shape (bh 48, n 4096, k 8,
    d 64, block 128), holds no scatter, which a TPU runs as a serial loop
    over its 1.6M updates."""
    from repro.kernels.flash_sfa import _block_maps
    codes = [jax.ShapeDtypeStruct((48, 4096, 8), t)
             for t in (jnp.bfloat16, jnp.int32, jnp.bfloat16, jnp.int32)]
    maps = functools.partial(_block_maps, d=64, causal=True, block_q=128,
                             block_k=128, nq_real=4096, nk_real=4096)
    text = jax.jit(maps).lower(*codes).as_text()
    assert "reduce" in text
    assert "scatter" not in text, "the block-skip pre-pass scatters again"


# --------------------------------------------------------------------------
# seam level: fused forward == unfused forward, gradients included
# --------------------------------------------------------------------------

@pytest.mark.parametrize("hkv", [4, 2])
@pytest.mark.parametrize("rope_on", [True, False])
@pytest.mark.parametrize("causal", [True, False])
def test_seam_fused_forward_parity(rng, hkv, rope_on, causal):
    b, n, m, h, hd, k = 2, 120, 48, 4, 64, 8
    w = jax.random.normal(rng, (m, (h + 2 * hkv) * hd)) * 0.05
    x = jax.random.normal(jax.random.fold_in(rng, 1), (b, n, m))
    pos = jnp.broadcast_to(jnp.arange(n), (b, n))
    spec = (10_000.0, hd) if rope_on else None
    o0, r0 = attn._sfa_proj_attend_fwd_impl(w, x, pos, h, hkv, hd, k,
                                            causal, hd ** -0.5, spec, False)
    o1, r1 = attn._sfa_proj_attend_fwd_impl(w, x, pos, h, hkv, hd, k,
                                            causal, hd ** -0.5, spec, True)
    np.testing.assert_allclose(np.asarray(o0), np.asarray(o1), atol=ATOL)
    # identical residual tuple (codes bit-matched) => identical backward
    for a, b_ in zip(r0[3:8], r1[3:8]):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b_, np.float32), atol=1e-5)


def test_seam_fused_gradients_match_unfused(rng):
    b, n, m, h, hkv, hd, k = 2, 96, 48, 4, 2, 64, 8
    w = jax.random.normal(rng, (m, (h + 2 * hkv) * hd)) * 0.05
    x = jax.random.normal(jax.random.fold_in(rng, 1), (b, n, m))
    pos = jnp.broadcast_to(jnp.arange(n), (b, n))

    def loss(fuse):
        def f(w, x):
            o = attn._sfa_proj_attend_compact(w, x, pos, h, hkv, hd, k,
                                              True, hd ** -0.5,
                                              (10_000.0, hd), "compact2",
                                              fuse)
            return jnp.sum(o * jnp.sin(jnp.arange(o.size, dtype=o.dtype)
                                       .reshape(o.shape)))
        return f

    gw0, gx0 = jax.grad(loss(False), argnums=(0, 1))(w, x)
    gw1, gx1 = jax.grad(loss(True), argnums=(0, 1))(w, x)
    np.testing.assert_allclose(np.asarray(gw0), np.asarray(gw1), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gx0), np.asarray(gx1), atol=1e-4)


def _seam_cfg(fwd_fuse: bool) -> ModelConfig:
    a = AttentionConfig(num_heads=2, num_kv_heads=2, head_dim=32, sfa_k=4,
                        rope=True, backend="pallas", bwd_emit="compact",
                        fwd_fuse=fwd_fuse)
    return ModelConfig(name=f"fused-fwd-{fwd_fuse}", family="dense",
                       num_layers=1, d_model=48, d_ff=64, vocab_size=64,
                       attention=a)


def test_seam_report_records_fused_fwd(rng):
    attn.clear_compact_seam_reports()
    for fuse in (True, False):
        cfg = _seam_cfg(fuse)
        assert attn.compact_train_eligible(cfg)
        params = attn.attention_init(jax.random.fold_in(rng, int(fuse)), cfg)
        x = jax.random.normal(rng, (1, 64, cfg.d_model))
        attn.attention_apply(params, x, cfg=cfg, mode="train")
    reports = {r.fused_fwd for r in attn.compact_seam_reports() if r.taken}
    assert reports == {True, False}
    attn.clear_compact_seam_reports()


def test_fused_fwd_config_output_parity(rng):
    cfg_f, cfg_u = _seam_cfg(True), _seam_cfg(False)
    params = attn.attention_init(rng, cfg_f)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 80, cfg_f.d_model))
    of = attn.attention_apply(params, x, cfg=cfg_f, mode="train").out
    ou = attn.attention_apply(params, x, cfg=cfg_u, mode="train").out
    np.testing.assert_allclose(np.asarray(of), np.asarray(ou), atol=ATOL)
