"""Code-residual remat policies + the unified policy/report config API.

Pinned here:
  * grad parity — whole-model ``loss_fn`` gradients under
    ``remat="codes"`` and ``remat="full"`` match the un-remat'd
    (``"none"``) path to <= 1e-4, on RoPE'd and rope-free geometries
    (GQA included: the k-codes are tagged BEFORE the group repeat);
  * the saveable contract — ``CODE_SAVEABLES`` is exactly the compact-code
    vocabulary (grep-able: no dense (n, d) q/k name may ever appear), and
    a jaxpr audit proves every ``name_p``-tagged code saveable in a real
    traced step has a k-width trailing axis, not a d-width one;
  * ``TrainPolicy`` — ``validate()`` rejects incoherent combos at config
    time; the deprecated loose kwargs / bool ``remat`` keep working one
    release behind a DeprecationWarning and alias to the same configs;
  * unified reports — ``core.reports.collect_reports()`` surfaces the
    remat routing decision (codes silently-degrades-to-full is recorded,
    not swallowed) alongside the seam/ring/backend components;
  * eval-mode remat — ``forward_logits(mode="eval")`` checkpoints too
    (the old guard was train-only), observable as a compiled peak-memory
    drop when differentiating through an eval forward.
"""
import dataclasses

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AttentionConfig, ModelConfig, TrainPolicy
from repro.core import reports as U
from repro.core.remat import (
    CODE_SAVEABLES, checkpoint_policy, clear_remat_reports, normalize_remat,
)
from repro.models import attention as attn
from repro.models import init as model_init, loss_fn
from repro.models.model import forward_logits

ATOL = 1e-4


def _cfg(rope=False, h=4, hkv=2, hd=32, k=4, remat="none", **kw):
    a = AttentionConfig(num_heads=h, num_kv_heads=hkv, head_dim=hd, sfa_k=k,
                        rope=rope, backend="pallas", bwd_emit="compact",
                        **kw)
    return ModelConfig(name="rp-test", family="dense", num_layers=2,
                       d_model=48, d_ff=64, vocab_size=64, loss_chunk=32,
                       remat=remat, attention=a)


def _batch(rng, b=2, n=96, vocab=64):
    toks = jax.random.randint(jax.random.fold_in(rng, 3), (b, n + 1), 0,
                              vocab)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def _grads(cfg, rng, batch):
    params = model_init(jax.random.fold_in(rng, 1), cfg)
    g = jax.jit(jax.grad(lambda p: loss_fn(p, batch, cfg)[0]))(params)
    return params, g


# --------------------------------------------------------------------------
# grad parity: codes == full == none, rope'd and rope-free geometries
# --------------------------------------------------------------------------

@pytest.mark.parametrize("rope", [False, True])
def test_remat_policy_grad_parity(rng, rope):
    batch = _batch(rng)
    cfg0 = _cfg(rope=rope, remat="none")
    params = model_init(jax.random.fold_in(rng, 1), cfg0)
    grads = {}
    for remat in ("none", "full", "codes"):
        cfg = dataclasses.replace(cfg0, remat=remat)
        grads[remat] = jax.jit(
            jax.grad(lambda p: loss_fn(p, batch, cfg)[0]))(params)
    flat0, tree0 = jax.tree_util.tree_flatten(grads["none"])
    for remat in ("full", "codes"):
        flat, tree = jax.tree_util.tree_flatten(grads[remat])
        assert tree == tree0
        for a, b in zip(flat0, flat):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=ATOL, err_msg=f"remat={remat!r} vs 'none'")


# --------------------------------------------------------------------------
# the saveable contract: codes only, never dense q/k
# --------------------------------------------------------------------------

def test_code_saveables_name_no_dense_tensors():
    """Grep-able contract: the saveable vocabulary is exactly the compact
    codes + the per-row LSE — adding a dense q/k name here must fail."""
    assert set(CODE_SAVEABLES) == {
        "sfa_q_code_vals", "sfa_q_code_idx",
        "sfa_k_code_vals", "sfa_k_code_idx", "sfa_lse",
    }
    for name in CODE_SAVEABLES:
        assert "dense" not in name
        assert name.startswith("sfa_")


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in jax.tree_util.tree_leaves(
                    v, is_leaf=lambda x: isinstance(
                        x, (jex_core.Jaxpr, jex_core.ClosedJaxpr))):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    yield from _walk_eqns(sub.jaxpr)
                elif isinstance(sub, jex_core.Jaxpr):
                    yield from _walk_eqns(sub)


def test_traced_saveables_are_k_width(rng):
    """Every ``name_p``-tagged saveable in a real traced train step is a
    compact tensor: code tags carry a trailing k-width axis (never the
    d-width of dense q/k), index tags are int16, LSE tags are (bh, n)."""
    cfg = _cfg(rope=True, remat="codes")
    k, hd = cfg.attention.sfa_k, cfg.attention.head_dim
    batch = _batch(rng)
    params = model_init(jax.random.fold_in(rng, 1), cfg)
    jaxpr = jax.make_jaxpr(lambda p: loss_fn(p, batch, cfg)[0])(params)
    seen = {}
    for eqn in _walk_eqns(jaxpr.jaxpr):
        if eqn.primitive.name != "name":
            continue
        name = eqn.params["name"]
        aval = eqn.invars[0].aval
        seen.setdefault(name, aval)
        if name.endswith("_code_vals") or name.endswith("_code_idx"):
            assert aval.shape[-1] in (k, 2 * k), (name, aval)
            assert aval.shape[-1] != hd, (name, aval)
        if name.endswith("_code_idx"):
            assert aval.dtype == jnp.int16, (name, aval)
        if name == "sfa_lse":
            assert aval.ndim == 2, (name, aval)
    assert set(seen) == set(CODE_SAVEABLES), seen
    # and the policy object names exactly this vocabulary
    assert checkpoint_policy("codes") is not None
    assert checkpoint_policy("full") is None
    assert checkpoint_policy("none") is None


# --------------------------------------------------------------------------
# TrainPolicy: config-time validation + deprecation aliasing
# --------------------------------------------------------------------------

def test_train_policy_validate_rejects_incoherent_combos():
    a = _cfg().attention
    with pytest.raises(ValueError, match="pallas"):
        TrainPolicy(remat="codes", backend="xla").validate(a)
    with pytest.raises(ValueError, match="sfa_k"):
        TrainPolicy(remat="codes").validate(
            dataclasses.replace(a, sfa_k=None))
    with pytest.raises(ValueError, match="divide"):
        TrainPolicy(tp=3).validate(a)                     # 4/2 heads, tp=3
    with pytest.raises(ValueError, match="causal"):
        TrainPolicy(ring=True).validate(
            dataclasses.replace(a, causal=False))
    with pytest.raises(ValueError, match="bwd_emit"):
        TrainPolicy(bwd_emit="sparse").validate(a)
    with pytest.raises(ValueError, match="remat"):
        TrainPolicy(remat="sometimes").validate(a)
    # coherent combos pass and normalize
    p = TrainPolicy(remat="codes", bwd_emit="compact", tp=2).validate(a)
    assert p.remat == "codes"


def test_train_policy_apply_and_from_model_roundtrip():
    cfg = _cfg(remat="full")
    cfg2 = TrainPolicy.from_model(cfg).apply(cfg)
    assert cfg2 == cfg
    cfg3 = TrainPolicy.from_model(cfg, remat="codes").apply(cfg)
    assert cfg3.remat == "codes"
    assert cfg3.attention == cfg.attention


def test_bool_remat_deprecation_aliases():
    with pytest.warns(DeprecationWarning):
        cfg = _cfg(remat=True)
    assert cfg.remat == "full"
    with pytest.warns(DeprecationWarning):
        cfg = _cfg(remat=False)
    assert cfg.remat == "none"
    with pytest.warns(DeprecationWarning):
        p = TrainPolicy(remat=True).validate(cfg.attention)
    assert p.remat == "full"
    assert normalize_remat(True) == "full"
    assert normalize_remat(False) == "none"
    with pytest.raises(ValueError):
        normalize_remat("sometimes")


def test_make_train_step_legacy_kwargs_alias(rng):
    """The pre-policy loose kwargs still work (one release), warn, and
    produce the same step as the TrainPolicy spelling."""
    from repro.optim import OptimizerConfig, init_opt_state
    from repro.train.train_step import make_train_step

    cfg = _cfg(remat="none")
    opt = OptimizerConfig(lr=1e-3, warmup_steps=0, total_steps=2)
    batch = _batch(rng, n=64)
    params = model_init(jax.random.fold_in(rng, 1), cfg)
    with pytest.warns(DeprecationWarning, match="policy"):
        legacy = make_train_step(cfg, opt, bwd_emit="dense",
                                 attn_backend="xla")
    new = make_train_step(cfg, opt, policy=TrainPolicy.from_model(
        cfg, bwd_emit="dense", backend="xla"))
    p1, _, m1 = legacy(params, init_opt_state(params), batch)
    p2, _, m2 = new(params, init_opt_state(params), batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    with pytest.raises(ValueError, match="not both"):
        make_train_step(cfg, opt, policy=TrainPolicy(), bwd_emit="dense")


# --------------------------------------------------------------------------
# unified reports: remat routing is recorded, one collector sees it all
# --------------------------------------------------------------------------

def test_remat_codes_ineligible_degrades_to_full_with_report(rng):
    """codes on a stack that never tags the saveables (xla backend, dense
    emit) must apply "full" and record why — not silently save nothing."""
    U.clear_reports()
    cfg = _cfg(remat="codes")
    cfg = dataclasses.replace(cfg, attention=dataclasses.replace(
        cfg.attention, backend="xla", bwd_emit="dense"))
    assert attn.remat_codes_ineligible_reason(cfg) is not None
    batch = _batch(rng, n=64)
    params = model_init(jax.random.fold_in(rng, 1), cfg)
    jax.eval_shape(lambda p: loss_fn(p, batch, cfg)[0], params)
    rep = [r for r in U.collect_reports("remat") if not r.eligible]
    assert rep, U.collect_reports("remat")
    assert rep[0].detail("requested") == "codes"
    assert rep[0].detail("applied") == "full"
    assert "pallas" in rep[0].reason
    # the eligible path records eligible=True
    U.clear_reports("remat")
    cfg2 = _cfg(remat="codes")
    jax.eval_shape(lambda p: loss_fn(p, batch, cfg2)[0], params)
    rep2 = U.collect_reports("remat")
    assert rep2 and all(r.eligible for r in rep2), rep2
    assert {"remat", "compact_seam", "backend", "ring"} <= set(
        U.components())
    U.clear_reports()
    assert not U.collect_reports()
    clear_remat_reports()      # native accessors keep working too


# --------------------------------------------------------------------------
# eval-mode remat: the old train-only guard is gone
# --------------------------------------------------------------------------

def test_eval_mode_forward_checkpoints_too(rng):
    """Differentiating through ``forward_logits(mode="eval")`` under
    ``remat="full"`` must compile to a smaller live-temporary peak than
    ``remat="none"`` — impossible under the old ``mode == "train"`` guard,
    where eval forwards never checkpointed at all."""
    n = 256
    peaks = {}
    for remat in ("none", "full"):
        cfg = dataclasses.replace(
            _cfg(remat=remat), num_layers=4, loss_chunk=64)
        params = jax.eval_shape(
            lambda: model_init(jax.random.PRNGKey(0), cfg))
        batch = {"tokens": jax.ShapeDtypeStruct((1, n), jnp.int32)}

        def score(p, b):
            return jnp.sum(forward_logits(p, b, cfg, mode="eval").logits)

        c = jax.jit(jax.grad(score)).lower(params, batch).compile()
        peaks[remat] = c.memory_analysis().temp_size_in_bytes
    assert peaks["full"] < peaks["none"], peaks
