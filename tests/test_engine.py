"""Decode-engine regression tests (serve/engine.py): slot lifecycle, length
accounting, EOS/budget termination, and prefill->decode cache handoff.

Prompts use only lengths {3, 4} so every test reuses the same two prefill
compiles (engine jit caches are shared per-config via _jitted_fns)."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init as model_init
from repro.serve.engine import DecodeEngine, EngineConfig


def _cfg(name="gpt2-small"):
    # float32 so engine-vs-reference argmax comparisons aren't bf16-tie flaky
    cfg = get_config(name).reduced()
    return dataclasses.replace(cfg, dtype="float32")


@pytest.fixture(scope="module")
def dense_setup():
    cfg = _cfg()
    params = model_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 32)
    return DecodeEngine(params, cfg, EngineConfig(**kw))


def test_slot_insert_evict_lifecycle(dense_setup):
    cfg, params = dense_setup
    eng = _engine(cfg, params)
    p = np.array([1, 2, 3], np.int64)
    s0 = eng.add_request(p, max_new_tokens=3)
    s1 = eng.add_request(p + 1, max_new_tokens=5)
    assert (s0, s1) == (0, 1)
    assert eng.live.tolist() == [True, True]
    with pytest.raises(RuntimeError):
        eng.add_request(p, max_new_tokens=2)            # no free slots
    while eng.live.any():
        eng.step()
    assert eng.live.tolist() == [False, False]
    # budget termination: exactly max_new_tokens tokens per request
    assert len(eng.outputs[0]) == 3
    assert len(eng.outputs[1]) == 5
    # freed slots are reusable
    s2 = eng.add_request(p, max_new_tokens=2)
    assert s2 == 0 and eng.live[0]


def test_length_accounting_after_step(dense_setup):
    cfg, params = dense_setup
    eng = _engine(cfg, params)
    pa = np.array([5, 6, 7, 8], np.int64)
    pb = np.array([9, 10, 11], np.int64)
    sa = eng.add_request(pa, max_new_tokens=8)
    sb = eng.add_request(pb, max_new_tokens=2)
    assert int(eng.lengths[sa]) == len(pa)              # prompt in cache
    assert int(eng.lengths[sb]) == len(pb)
    eng.step()                                           # both live: +1 each
    assert int(eng.lengths[sa]) == len(pa) + 1
    assert int(eng.lengths[sb]) == len(pb) + 1
    assert not eng.live[sb]                              # budget 2 exhausted
    eng.step()                                           # only sa live now
    assert int(eng.lengths[sa]) == len(pa) + 2
    assert int(eng.lengths[sb]) == len(pb) + 1           # dead slot frozen


def test_max_new_tokens_exact_budget(dense_setup):
    """max_new_tokens ∈ {1, 2} produce EXACTLY that many tokens, each the
    prefix of the longer greedy run (regression: a budget-1 request used to
    go live with budget 0 and decode a second token past its budget)."""
    cfg, params = dense_setup
    p = np.array([1, 2, 3], np.int64)
    ref = _engine(cfg, params).generate(p, max_new_tokens=4)
    for mn in (1, 2):
        eng = _engine(cfg, params)
        slot = eng.add_request(p, max_new_tokens=mn)
        if mn == 1:
            assert not eng.live[slot]           # budget spent at prefill
        while eng.live.any():
            eng.step()
        assert eng.outputs[slot] == ref[:mn]
        # the slot is freed once the budget is exhausted — immediately
        # reusable for the next request
        assert eng.add_request(p, max_new_tokens=2) == slot
    with pytest.raises(ValueError, match="max_new_tokens"):
        _engine(cfg, params).add_request(p, max_new_tokens=0)


def test_overlong_prompt_rejected(dense_setup):
    """A prompt with no free cache position left to decode into must be
    rejected up front (regression: it used to prefill, then write the first
    decoded token out of bounds)."""
    cfg, params = dense_setup
    eng = _engine(cfg, params, max_len=8)
    with pytest.raises(ValueError, match="max_len"):
        eng.add_request(np.arange(8, dtype=np.int64))    # len == max_len
    with pytest.raises(ValueError, match="max_len"):
        eng.add_request(np.arange(9, dtype=np.int64))
    # boundary: max_len-1 leaves exactly one decode position
    slot = eng.add_request(np.arange(7, dtype=np.int64), max_new_tokens=5)
    while eng.live.any():
        eng.step()
    assert len(eng.outputs[slot]) == 2          # prefill token + 1 decode


def test_overlong_prompt_rejected_patch_frontend():
    """The patch frontend contributes prefix_len positions to the cache:
    over-length accounting must include them (regression: a prompt that fit
    token-wise but not with its patch prefix was admitted)."""
    cfg = dataclasses.replace(get_config("paligemma-3b").reduced(),
                              dtype="float32")
    pre = cfg.frontend.prefix_len
    params = model_init(jax.random.PRNGKey(0), cfg)
    eng = DecodeEngine(params, cfg, EngineConfig(max_slots=1,
                                                 max_len=pre + 4))
    patches = np.zeros((pre, cfg.frontend.input_dim), np.float32)
    # 4 tokens + prefix_len patches == max_len: no room to decode
    with pytest.raises(ValueError, match="patch-frontend prefix"):
        eng.add_request(np.arange(4, dtype=np.int64), max_new_tokens=2,
                        extra_inputs={"patches": patches})
    # one token fewer fits, and the slot length includes the prefix
    slot = eng.add_request(np.arange(3, dtype=np.int64), max_new_tokens=2,
                           extra_inputs={"patches": patches})
    assert int(eng.lengths[slot]) == pre + 3


def test_lengths_through_evict_and_reuse(dense_setup):
    """Slot evict/reuse stress on the length bookkeeping: only slots that
    actually decoded get +1 (regression: every live-at-step-start slot was
    bumped, so a slot freed mid-run drifted and poisoned page accounting),
    and a reused slot restarts at its new prompt length."""
    cfg, params = dense_setup
    eng = _engine(cfg, params)
    pa = np.array([1, 2, 3, 4], np.int64)
    pb = np.array([5, 6, 7], np.int64)
    sa = eng.add_request(pa, max_new_tokens=6)
    sb = eng.add_request(pb, max_new_tokens=2)
    eng.step()                        # both decode; sb's budget is spent
    assert not eng.live[sb]
    frozen = int(eng.lengths[sb])
    assert frozen == len(pb) + 1      # its one decoded token, nothing more
    eng.step()                        # only sa decodes
    assert int(eng.lengths[sb]) == frozen        # dead slot must not drift
    assert int(eng.lengths[sa]) == len(pa) + 2
    pc = np.array([8, 9, 10], np.int64)
    sc = eng.add_request(pc, max_new_tokens=3)
    assert sc == sb                   # freed slot reused
    assert int(eng.lengths[sc]) == len(pc)
    while eng.live.any():
        eng.step()
    assert int(eng.lengths[sc]) == len(pc) + 2   # max_new-1 decode steps
    assert int(eng.lengths[sa]) == len(pa) + 5


def test_eos_termination(dense_setup):
    cfg, params = dense_setup
    ref = _engine(cfg, params).generate(np.array([1, 2, 3], np.int64),
                                        max_new_tokens=8)
    assert len(ref) == 8
    # greedy decode is deterministic: re-running with eos_id = a generated
    # token must stop at its first occurrence, keeping the EOS token itself.
    # Pick the first token past position 0 that has not appeared earlier, so
    # that first occurrence is where it stands in ``ref``.
    j = next((i for i in range(1, len(ref)) if ref[i] not in ref[:i]), 0)
    out = _engine(cfg, params, eos_id=ref[j]).generate(
        np.array([1, 2, 3], np.int64), max_new_tokens=8)
    assert out == ref[:j + 1]


def test_slot_isolation_batched_vs_solo(dense_setup):
    """Prefill->decode handoff: a request's tokens are identical whether it
    shares the decode batch with another slot or runs alone (padded prompts
    of different lengths land in the right cache rows)."""
    cfg, params = dense_setup
    pa = np.array([3, 1, 4, 1], np.int64)
    pb = np.array([2, 7, 5], np.int64)                   # different length
    solo = _engine(cfg, params).generate(pa, max_new_tokens=6)
    eng = _engine(cfg, params)
    sa = eng.add_request(pa, max_new_tokens=6)
    sb = eng.add_request(pb, max_new_tokens=6)
    while eng.live.any():
        eng.step()
    assert eng.outputs[sa] == solo
    assert len(eng.outputs[sb]) == 6


def test_prefill_decode_handoff_matches_full_forward(dense_setup):
    """Greedy continuation via the engine == greedy continuation by re-running
    the full forward each step (teacher-forcing oracle, padded prompt)."""
    from repro.models import forward_logits
    cfg, params = dense_setup
    prompt = [2, 3, 5, 7]
    out = _engine(cfg, params).generate(np.array(prompt, np.int64),
                                        max_new_tokens=4)
    seq = list(prompt)
    oracle = []
    for _ in range(4):
        import jax.numpy as jnp
        logits = forward_logits(params, {"tokens": jnp.asarray([seq])},
                                cfg).logits
        nxt = int(np.argmax(np.asarray(logits[0, -1])))
        oracle.append(nxt)
        seq.append(nxt)
    assert out == oracle


@pytest.fixture(scope="module")
def sfa_setup():
    cfg = _cfg("gpt2-small-sfa8")
    assert cfg.attention.sfa_k is not None
    params = model_init(jax.random.PRNGKey(2), cfg)
    return cfg, params


@pytest.mark.parametrize("backend", [
    "pallas",
    # feature-major interpret-mode kernel is ~45 s on CPU: slow lane only
    pytest.param("pallas_fm", marks=pytest.mark.slow),
])
def test_decode_backend_parity_full_engine(sfa_setup, backend):
    """flash_sfa_decode / flash_sfa_decode_fm selected as serving backends
    through the registry produce greedy tokens identical to the XLA gather
    oracle over >=32 decode steps with ragged slot lengths."""
    cfg, params = sfa_setup
    prompts = [np.array([1, 2, 3], np.int64), np.array([4, 5, 6, 7], np.int64)]
    outs = {}
    for be in ("xla", backend):
        eng = _engine(cfg, params, max_len=48, decode_backend=be)
        s0 = eng.add_request(prompts[0], max_new_tokens=33)
        s1 = eng.add_request(prompts[1], max_new_tokens=33)
        while eng.live.any():
            eng.step()
        assert len(eng.outputs[s0]) == 33       # 1 prefill + 32 decode steps
        outs[be] = (eng.outputs[s0], eng.outputs[s1])
    assert outs[backend] == outs["xla"]


def test_dense_cache_pallas_request_falls_back(dense_setup):
    """Dense caches have no Pallas decode kernel: an explicit request runs
    on the oracle and surfaces a structured report (no silent divergence)."""
    from repro.models import backends as B
    cfg, params = dense_setup
    B.clear_fallback_reports()
    ref = _engine(cfg, params).generate(np.array([1, 2, 3], np.int64),
                                        max_new_tokens=6)
    out = _engine(cfg, params, decode_backend="pallas").generate(
        np.array([1, 2, 3], np.int64), max_new_tokens=6)
    assert out == ref
    assert any(r.requested == "pallas" and "dense" in r.reason
               for r in B.fallback_reports())


def test_slot_lengths_stay_on_host(dense_setup):
    """Per-slot length bookkeeping must not sync the device every step."""
    cfg, params = dense_setup
    eng = _engine(cfg, params)
    eng.add_request(np.array([1, 2, 3], np.int64), max_new_tokens=3)
    assert isinstance(eng.lengths, np.ndarray)
    eng.step()
    assert isinstance(eng.lengths, np.ndarray)


@pytest.mark.slow
def test_fm_persistent_cache_decode_stress(sfa_setup):
    """Decode stress run: pallas_fm serving greedy tokens off the persistent
    FeatureMajorKV image — maintained only by prefill insert_slot handoff
    and per-step column writes, never re-materialized — stays identical to
    the XLA gather oracle over 48+ ragged-length engine steps with slot
    eviction and slot reuse (a third request lands in the evicted slot
    mid-run while another slot keeps decoding)."""
    cfg, params = sfa_setup
    pa = np.array([1, 2, 3], np.int64)
    pb = np.array([4, 5, 6, 7], np.int64)       # ragged vs pa
    pc = np.array([8, 9, 10], np.int64)

    def run(be):
        eng = _engine(cfg, params, max_slots=2, max_len=64,
                      decode_backend=be)
        sa = eng.add_request(pa, max_new_tokens=50)
        sb = eng.add_request(pb, max_new_tokens=9)
        steps, sc = 0, None
        while eng.live.any():
            eng.step()
            steps += 1
            if sc is None and not eng.live[sb]:
                # slot eviction + reuse: B's budget is exhausted, C prefills
                # into the freed slot (insert_slot handoff) while A decodes
                out_b = list(eng.outputs[sb])
                sc = eng.add_request(pc, max_new_tokens=45)
                assert sc == sb
        return {"a": eng.outputs[sa], "b": out_b,
                "c": eng.outputs[sc]}, steps, eng

    ref, steps_ref, eng_ref = run("xla")
    fm, steps_fm, eng_fm = run("pallas_fm")
    assert steps_fm == steps_ref and steps_fm >= 48
    assert len(fm["a"]) == 50 and len(fm["b"]) == 9 and len(fm["c"]) == 45
    assert fm == ref
    # the layouts really differ: the oracle engine serves token-major codes,
    # the pallas_fm engine the persistent feature-major image — whose token
    # axis is allocated in whole 128-token kernel tiles (no per-step pad)
    from repro.core.kv_cache import FeatureMajorKV, SparseKV, kv_cache_nodes
    assert all(isinstance(n, SparseKV)
               for n in kv_cache_nodes(eng_ref.caches))
    fm_nodes = kv_cache_nodes(eng_fm.caches)
    assert all(isinstance(n, FeatureMajorKV) for n in fm_nodes)
    assert all(n.k_feat.shape[-1] % 128 == 0 for n in fm_nodes)


def test_sfa_sparse_cache_handoff():
    """Same lifecycle checks through the SFA sparse-KV cache path."""
    cfg = _cfg("gpt2-small-sfa8")
    assert cfg.attention.sfa_k is not None
    params = model_init(jax.random.PRNGKey(1), cfg)
    eng = _engine(cfg, params)
    pa = np.array([1, 2, 3, 4], np.int64)
    solo = eng.generate(pa, max_new_tokens=5)
    assert len(solo) == 5
    eng2 = _engine(cfg, params)
    sa = eng2.add_request(pa, max_new_tokens=5)
    sb = eng2.add_request(np.array([8, 9, 10], np.int64), max_new_tokens=3)
    while eng2.live.any():
        eng2.step()
    assert eng2.outputs[sa] == solo
    assert len(eng2.outputs[sb]) == 3
