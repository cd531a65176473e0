"""Pair-widened (n, 2k) compact backward through the RoPE vjp (ISSUE 5).

Four layers of pinning:
  * pair-closure map — ``pair_closure_indices`` covers every stored index's
    rotation pair, keeps unrotated trailing dims (rot_dim < head_dim)
    unwidened, and its duplicates carry complementary value shares;
  * kernel emit — ``flash_sfa_bwd(emit="compact2")`` scattered on the
    closure indices reproduces the dense emit exactly (full AND partial
    rotation);
  * rope vjp on codes — ``rope_code_vjp`` equals XLA autodiff of ``rope``
    fed the scattered cotangent, without ever leaving the (n, 2k) domain;
  * train path — a RoPE'd config with llama3.2-3b head geometry and
    ``bwd_emit="compact"`` takes the fused seam and matches the XLA
    straight-through oracle gradients to <= 1e-4 (the ISSUE 5 acceptance
    bar), and the rope × qk-norm × MLA × window eligibility matrix routes
    exactly as documented, observable via the structured reports.
"""
import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import AttentionConfig, MLAConfig, ModelConfig
from repro.kernels import ref as REF
from repro.kernels.code_grad import scatter_code_grads
from repro.kernels.flash_sfa import flash_sfa
from repro.kernels.flash_sfa_bwd import flash_sfa_bwd, pair_closure_indices
from repro.core import reports as U
from repro.models import attention as attn
from repro.models.layers import rope, rope_code_vjp

ATOL = 1e-4


def _rand_codes(rng, shape, d, k):
    vals = jax.random.normal(jax.random.fold_in(rng, 1), shape + (k,))
    perm = jax.random.permutation(
        jax.random.fold_in(rng, 2),
        jnp.broadcast_to(jnp.arange(d), shape + (d,)), axis=-1,
        independent=True)
    idx = jnp.sort(perm[..., :k], axis=-1).astype(jnp.int32)
    return vals, idx


# --------------------------------------------------------------------------
# pair-closure map
# --------------------------------------------------------------------------

def test_pair_closure_covers_rotation_pairs():
    idx = jnp.array([[0, 3, 6, 7]], jnp.int32)
    c = np.asarray(pair_closure_indices(idx, 8))
    # concatenated halves: even members first, odd members second
    np.testing.assert_array_equal(c, [[0, 2, 6, 6, 1, 3, 7, 7]])
    for i in (0, 3, 6, 7):
        pair = {(i // 2) * 2, (i // 2) * 2 + 1}
        assert pair <= set(c[0]), f"pair of {i} not covered"


def test_pair_closure_partial_rotation_unwidened():
    """ISSUE 5 bugfix audit: with rot_dim < head_dim, stored indices in the
    unrotated tail must NOT be unioned with a bogus partner — both closure
    slots are the index itself, and the emit pins the duplicate's second
    share to zero so scatter-sum semantics stay exact."""
    rot = 4
    idx = jnp.array([[1, 4, 5, 7]], jnp.int32)     # 1 rotated; 4,5,7 not
    c = np.asarray(pair_closure_indices(idx, rot))
    np.testing.assert_array_equal(c, [[0, 4, 5, 7, 1, 4, 5, 7]])
    assert not (set(c[0]) - {0, 1, 4, 5, 7}), "bogus partner leaked in"


# --------------------------------------------------------------------------
# kernel emit (compact2) vs dense emit
# --------------------------------------------------------------------------

@pytest.mark.parametrize("d,k,rot", [(32, 4, 32), (32, 4, 16), (64, 8, 64)])
def test_flash_sfa_bwd_compact2_matches_dense_emit(rng, d, k, rot):
    """Scattering the (n, 2k) pair-closure emit on its closure indices
    reproduces the dense emit bit-for-bit in support and <= 1e-5 in value;
    dV is untouched. Ragged n exercises padded tiles."""
    bh, n = 2, 176
    q = jax.random.normal(jax.random.fold_in(rng, 1), (bh, n, d))
    kk = jax.random.normal(jax.random.fold_in(rng, 2), (bh, n, d))
    v = jax.random.normal(jax.random.fold_in(rng, 3), (bh, n, d))
    g = jax.random.normal(jax.random.fold_in(rng, 4), (bh, n, d))
    qv, qi = REF.rtopk_ref(q, k)
    kv_, ki = REF.rtopk_ref(kk, k)
    o, lse = flash_sfa(qv, qi, kv_, ki, v, d=d, return_residuals=True)
    dq, dk, dv = flash_sfa_bwd(qv, qi, kv_, ki, v, o, lse, g, d=d)
    dq2, dk2, dv2 = flash_sfa_bwd(qv, qi, kv_, ki, v, o, lse, g, d=d,
                                  emit="compact2", rot_dim=rot)
    assert dq2.shape == (bh, n, 2 * k) and dk2.shape == (bh, n, 2 * k)
    qi2, ki2 = pair_closure_indices(qi, rot), pair_closure_indices(ki, rot)
    np.testing.assert_allclose(np.asarray(scatter_code_grads(dq2, qi2, d)),
                               np.asarray(dq), atol=1e-5)
    np.testing.assert_allclose(np.asarray(scatter_code_grads(dk2, ki2, d)),
                               np.asarray(dk), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(dv2), np.asarray(dv))


# --------------------------------------------------------------------------
# rope vjp on codes vs XLA autodiff oracle
# --------------------------------------------------------------------------

@pytest.mark.parametrize("rot", [32, 16])       # full and partial rotation
def test_rope_code_vjp_matches_rope_autodiff(rng, rot):
    n, h, d, k = 24, 2, 32, 4
    theta = 500_000.0
    x = jax.random.normal(jax.random.fold_in(rng, 1), (1, n, h, d))
    pos = jnp.arange(n)[None, :]
    vals, idx = _rand_codes(rng, (1, n, h), d, k)
    g_dense = scatter_code_grads(vals, idx, d)          # post-rope cotangent
    _, vjp = jax.vjp(lambda x: rope(x, pos, theta=theta, rot_dim=rot), x)
    (dpre_ref,) = vjp(g_dense)
    idx2 = pair_closure_indices(idx, rot)
    is_odd = (idx < rot) & (idx % 2 == 1)
    vals2 = jnp.concatenate([vals * ~is_odd, vals * is_odd], -1)
    pre2 = rope_code_vjp(vals2, idx2, pos[..., None], theta=theta,
                         rot_dim=rot)
    np.testing.assert_allclose(np.asarray(scatter_code_grads(pre2, idx2, d)),
                               np.asarray(dpre_ref), atol=ATOL)


def test_rope_code_vjp_partial_rotation_is_identity_on_tail(rng):
    """Unrotated tail entries must pass through untouched — the pair-partner
    audit of the ISSUE 5 bugfix, value side."""
    rot, d, k = 4, 16, 4
    idx = jnp.array([[[6, 8, 10, 12]]], jnp.int32)      # all in the tail
    vals = jax.random.normal(rng, (1, 1, k))
    idx2 = pair_closure_indices(idx, rot)
    vals2 = jnp.concatenate([vals, jnp.zeros_like(vals)], -1)
    out = rope_code_vjp(vals2, idx2, jnp.full((1, 1), 7), theta=1e4,
                        rot_dim=rot)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(vals2))


# --------------------------------------------------------------------------
# train path: the ISSUE 5 acceptance bar
# --------------------------------------------------------------------------

def _rope_cfg(h, hkv, hd=32, k=4, theta=500_000.0, bwd_emit="compact",
              backend="pallas", **kw):
    a = AttentionConfig(num_heads=h, num_kv_heads=hkv, head_dim=hd, sfa_k=k,
                        rope=True, rope_theta=theta, backend=backend,
                        bwd_emit=bwd_emit, **kw)
    return ModelConfig(name="rope-seam-test", family="dense", num_layers=1,
                       d_model=48, d_ff=64, vocab_size=64, attention=a)


def _attn_grads(rng, cfg, params=None, b=2, n=96):
    if params is None:
        params = attn.attention_init(rng, cfg)
    x = jax.random.normal(jax.random.fold_in(rng, 9), (b, n, cfg.d_model))

    def loss(p, x):
        o = attn.attention_apply(p, x, cfg=cfg, mode="train").out
        w = jnp.arange(o.size, dtype=o.dtype).reshape(o.shape) / o.size
        return jnp.sum(o * w + 0.5 * o * o)

    return params, jax.grad(loss, argnums=(0, 1))(params, x)


def test_rope_seam_grad_parity_llama_geometry(rng):
    """Acceptance: a RoPE'd config with llama3.2-3b head geometry (reduced:
    GQA 24/8 -> 4/2 heads, theta=500k) and ``bwd_emit="compact"`` takes the
    fused seam and matches the dense-emit pallas path AND the XLA
    straight-through oracle to <= 1e-4."""
    base = get_config("llama3.2-3b").reduced().attention
    assert base.rope
    cfg_c = _rope_cfg(4, 2, hd=base.head_dim, k=base.sfa_k,
                      theta=base.rope_theta)
    assert attn.compact_train_eligible(cfg_c)
    params, (gp_c, gx_c) = _attn_grads(rng, cfg_c)
    for ref_cfg in (_rope_cfg(4, 2, hd=base.head_dim, k=base.sfa_k,
                              theta=base.rope_theta, bwd_emit="dense"),
                    _rope_cfg(4, 2, hd=base.head_dim, k=base.sfa_k,
                              theta=base.rope_theta, bwd_emit="dense",
                              backend="xla")):
        _, (gp_r, gx_r) = _attn_grads(rng, ref_cfg, params=params)
        np.testing.assert_allclose(
            np.asarray(gx_c), np.asarray(gx_r), atol=ATOL,
            err_msg=f"dx vs {ref_cfg.attention.backend}")
        for key in ("w_qkv", "w_o"):
            np.testing.assert_allclose(
                np.asarray(gp_c[key]["w"]), np.asarray(gp_r[key]["w"]),
                atol=ATOL, err_msg=f"d{key} vs {ref_cfg.attention.backend}")


def test_forced_compact2_on_ropefree_seam(rng):
    """bwd_emit="compact2" on a rope-free eligible layer must honor the
    launch-flag contract — the seam runs the pair-widened kernel emit (a
    lossless relayout without the rotation) and grads still match."""
    def cfg_for(emit):
        a = AttentionConfig(num_heads=2, num_kv_heads=2, head_dim=32,
                            sfa_k=4, rope=False, backend="pallas",
                            bwd_emit=emit)
        return ModelConfig(name=f"c2-force-{emit}", family="dense",
                           num_layers=1, d_model=48, d_ff=64, vocab_size=64,
                           attention=a)

    cfg2 = cfg_for("compact2")
    assert attn.compact_train_eligible(cfg2)
    params, (gp2, gx2) = _attn_grads(rng, cfg2)
    _, (gp1, gx1) = _attn_grads(rng, cfg_for("compact"), params=params)
    np.testing.assert_allclose(np.asarray(gx2), np.asarray(gx1), atol=ATOL)
    np.testing.assert_allclose(np.asarray(gp2["w_qkv"]["w"]),
                               np.asarray(gp1["w_qkv"]["w"]), atol=ATOL)


def test_rope_seam_op_level_compact2_parity(rng):
    """Op-level: bwd_emit="compact2" (pair-widened emit scattered back for
    the generic vjp) matches the XLA oracle — pins that the widened kernel
    emit is lossless outside the seam too."""
    from repro.kernels import sfa_attention_op

    def grads(impl, bwd_emit="dense"):
        def loss(q, k, v):
            o = sfa_attention_op(q, k, v, sfa_k=4, causal=True, impl=impl,
                                 bwd_emit=bwd_emit)
            return jnp.sum(o * o)
        return jax.grad(loss, argnums=(0, 1, 2))(q, kk, v)

    q = jax.random.normal(jax.random.fold_in(rng, 1), (2, 96, 2, 32))
    kk = jax.random.normal(jax.random.fold_in(rng, 2), (2, 96, 2, 32))
    v = jax.random.normal(jax.random.fold_in(rng, 3), (2, 96, 2, 32))
    g1 = grads("pallas", bwd_emit="compact2")
    g2 = grads("xla")
    for name, a, b in zip("qkv", g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=ATOL,
                                   err_msg=f"d{name} (compact2 op level)")


# --------------------------------------------------------------------------
# eligibility matrix (rope × qk-norm × MLA × window), structured reports
# --------------------------------------------------------------------------

_TINY_MLA = MLAConfig(kv_lora_rank=16, q_lora_rank=24, nope_head_dim=16,
                      rope_head_dim=8, v_head_dim=16)


def _matrix_cfg(rope_on, qk_norm, mla, window):
    a = AttentionConfig(num_heads=2, num_kv_heads=2, head_dim=32, sfa_k=4,
                        rope=rope_on, qk_norm=qk_norm,
                        mla=_TINY_MLA if mla else None,
                        window=window, backend="pallas", bwd_emit="compact")
    name = (f"mx-r{int(rope_on)}q{int(qk_norm)}"
            f"m{int(mla)}w{int(window is not None)}")
    return ModelConfig(name=name, family="dense", num_layers=1, d_model=48,
                       d_ff=64, vocab_size=64, attention=a)


def test_seam_eligibility_matrix(rng):
    """Every (rope × qk-norm × MLA × window) combination routes exactly as
    documented: the seam engages iff nothing but (possibly) rope sits
    between projection and kernel, every skip is recorded as a structured
    ``CompactSeamReport`` naming the blocking feature, and the window/MLA
    combinations additionally surface the backend's own ``FallbackReport``
    (pallas -> xla)."""
    U.clear_reports()           # one call resets every component
    for rope_on, qk_norm, mla, window in itertools.product(
            (False, True), (False, True), (False, True), (None, 16)):
        cfg = _matrix_cfg(rope_on, qk_norm, mla, window)
        params = attn.attention_init(jax.random.fold_in(rng, 5), cfg)
        x = jax.random.normal(jax.random.fold_in(rng, 6),
                              (1, 64, cfg.d_model))
        attn.attention_apply(params, x, cfg=cfg, mode="train")
        expect_seam = not qk_norm and not mla and window is None
        reports = [r for r in U.collect_reports("compact_seam")
                   if r.where == f"{cfg.name}/attention"]
        assert len(reports) == 1, (cfg.name, reports)
        r = reports[0]
        assert r.component == "compact_seam"
        assert r.eligible == expect_seam, (cfg.name, r)
        if expect_seam:
            assert r.reason is None
        else:
            blocker = ("MLA" if mla else
                       "qk-norm" if qk_norm else "window")
            assert blocker.lower().split("-")[0] in r.reason.lower(), r
        if window is not None and not mla:
            # windowed pallas request falls back to the xla oracle at the
            # backend layer too — both report surfaces stay consistent,
            # and the unified protocol carries the backend's extras
            assert any(f.detail("requested") == "pallas"
                       and f.detail("selected") == "xla" and not f.eligible
                       for f in U.collect_reports("backend")), cfg.name
    # the unified collector sees every component's records in one call
    assert {r.component for r in U.collect_reports()} >= {"backend",
                                                          "compact_seam"}
    U.clear_reports()


def test_seam_reports_dedupe():
    attn.clear_compact_seam_reports()
    attn._record_seam("x/attention", False, "why")
    attn._record_seam("x/attention", False, "why")
    attn._record_seam("x/attention", True, None)
    assert len(attn.compact_seam_reports()) == 2
    attn.clear_compact_seam_reports()


def test_rope_protect_still_falls_back():
    cfg = _rope_cfg(2, 2, sfa_rope_protect=4)
    reason = attn.compact_seam_ineligible_reason(cfg)
    assert reason is not None and "protect" in reason
    cfg2 = dataclasses.replace(
        cfg, attention=dataclasses.replace(cfg.attention, sfa_rope_protect=0))
    assert attn.compact_train_eligible(cfg2)


# --------------------------------------------------------------------------
# TP dimension of the eligibility matrix (ISSUE 9): the seam is TP-eligible
# when both head counts divide the model-axis degree (whole per-device head
# slices keep dQ/dK code grads reduction-free, distributed/shard.py)
# --------------------------------------------------------------------------

def test_seam_tp_eligibility_matrix(monkeypatch):
    """Unit-level TP sweep without a mesh: ``axis_size("model")`` is the
    only TP input to the eligibility rule, so patching it enumerates the
    matrix on any device count. Divisible head counts stay eligible;
    non-divisible fall back with a structured reason naming the degree; a
    ring-active layer steps aside to the op-level ring path."""
    for tp, h, hkv, eligible in ((1, 4, 2, True), (2, 4, 2, True),
                                 (4, 4, 2, False),   # hkv=2 % 4
                                 (2, 3, 3, False),   # h=3 % 2
                                 (8, 8, 8, True)):
        monkeypatch.setattr(
            attn, "axis_size",
            lambda name, _tp=tp: _tp if name == "model" else 1)
        cfg = _rope_cfg(h, hkv, bwd_emit="compact2")
        reason = attn.compact_seam_ineligible_reason(cfg)
        if eligible:
            assert reason is None, (tp, h, hkv, reason)
        else:
            assert reason and "divide" in reason and str(tp) in reason, \
                (tp, h, hkv, reason)
    # ring context parallelism routes around the seam entirely
    monkeypatch.setattr(attn, "axis_size", lambda name: 1)
    monkeypatch.setattr(attn, "ring_degree", lambda *a, **k: 8)
    cfg_ring = _rope_cfg(4, 2, ring=True)
    reason = attn.compact_seam_ineligible_reason(cfg_ring)
    assert reason and "ring" in reason


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >= 2 emulated devices: XLA_FLAGS="
                           "--xla_force_host_platform_device_count=8")
def test_seam_taken_under_tp2_grad_parity(rng):
    """Acceptance (ISSUE 9): on a real model=2 mesh the ``compact2`` seam
    is TAKEN (not fallen back) for divisible GQA heads and its weight/input
    grads match the single-device run <= 1e-4 — including the concat of
    shard_map'd dwq/dwk with the replicated dwv in ``_sfa_proj_attend_bwd``
    on a (data, model) mesh."""
    from repro.distributed.sharding import axis_rules
    from repro.launch.mesh import make_debug_mesh

    cfg = _rope_cfg(4, 2, bwd_emit="compact2")
    params, (gp_ref, gx_ref) = _attn_grads(rng, cfg)
    mesh = make_debug_mesh(model=2)
    attn.clear_compact_seam_reports()
    with mesh, axis_rules(mesh):
        x = jax.random.normal(jax.random.fold_in(rng, 9),
                              (2, 96, cfg.d_model))

        def loss(p, x):
            o = attn.attention_apply(p, x, cfg=cfg, mode="train").out
            w = jnp.arange(o.size, dtype=o.dtype).reshape(o.shape) / o.size
            return jnp.sum(o * w + 0.5 * o * o)

        g_tp = jax.jit(jax.grad(loss, argnums=(0, 1)))(params, x)
    assert any(r.taken for r in attn.compact_seam_reports()), \
        attn.compact_seam_reports()
    np.testing.assert_allclose(np.asarray(gx_ref), np.asarray(g_tp[1]),
                               atol=ATOL)
    for key in ("w_qkv", "w_o"):
        np.testing.assert_allclose(np.asarray(gp_ref[key]["w"]),
                                   np.asarray(g_tp[0][key]["w"]), atol=ATOL)
    # non-divisible heads fall back with the structured TP reason
    with mesh, axis_rules(mesh):
        reason = attn.compact_seam_ineligible_reason(_rope_cfg(3, 3))
    assert reason and "divide" in reason
