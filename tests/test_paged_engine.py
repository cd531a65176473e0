"""Paged-KV serving engine tests (serve/engine.py PagedDecodeEngine):
greedy-token parity with the slot DecodeEngine across decode backends,
chunked-prefill interleaving, page accounting, admission queueing, and
preemption-by-recompute determinism.

Pages are 8 tokens here (reduced configs) — the paged Pallas kernels tile
by page, so small pages exercise the same block-table indexing the
128-token production pages use. Prompt lengths reuse a tiny set so the
per-config jit caches amortize across tests."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init as model_init
from repro.serve import (DecodeEngine, EngineConfig, PagedDecodeEngine,
                         PagedEngineConfig, paged_page_bytes)

PROMPT = np.array([2, 3, 5, 7, 11, 13, 17, 19, 23, 2, 3], np.int64)


def _cfg(name="gpt2-small", backend=None):
    cfg = dataclasses.replace(get_config(name).reduced(), dtype="float32")
    if backend is not None:
        cfg = dataclasses.replace(cfg, attention=dataclasses.replace(
            cfg.attention, decode_backend=backend))
    return cfg


@pytest.fixture(scope="module")
def dense_setup():
    cfg = _cfg()
    return cfg, model_init(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def sfa_setup():
    cfg = _cfg("gpt2-small-sfa8")
    assert cfg.attention.sfa_k is not None
    return cfg, model_init(jax.random.PRNGKey(0), cfg)


def _paged(cfg, params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("page_size", 8)
    return PagedDecodeEngine(params, cfg, PagedEngineConfig(**kw))


def _slot_ref(cfg, params, prompt, max_new):
    eng = DecodeEngine(params, cfg, EngineConfig(max_slots=1, max_len=48))
    return eng.generate(prompt, max_new_tokens=max_new)


# --------------------------------------------------------------------------
# greedy-token parity vs the slot engine
# --------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [None, 4])
def test_paged_matches_slot_engine_dense(dense_setup, chunk):
    cfg, params = dense_setup
    ref = _slot_ref(cfg, params, PROMPT, 6)
    eng = _paged(cfg, params, prefill_chunk=chunk)
    assert eng.generate(PROMPT, max_new_tokens=6) == ref


@pytest.mark.parametrize("backend", [
    "xla",
    "pallas",
    # interpret-mode feature-major kernel is slow on CPU: slow lane only
    pytest.param("pallas_fm", marks=pytest.mark.slow),
])
@pytest.mark.parametrize("chunk", [None, 4])
def test_paged_matches_slot_engine_sfa(backend, chunk):
    """Block-table-indexed decode reads (xla gather oracle, token-major
    pallas kernel, feature-major pallas_fm kernel) + whole-prompt or
    chunked prefill: greedy tokens identical to the contiguous slot
    engine."""
    cfg = _cfg("gpt2-small-sfa8", backend)
    params = model_init(jax.random.PRNGKey(0), cfg)
    ref = _slot_ref(cfg, params, PROMPT, 6)
    eng = _paged(cfg, params, prefill_chunk=chunk)
    assert eng.generate(PROMPT, max_new_tokens=6) == ref


def test_chunked_prefill_interleaves_with_decode(sfa_setup):
    """Chunked prefill must not stall running decodes: while request B's
    prompt lands chunk-by-chunk, request A keeps emitting one token per
    step, and B's final tokens still match its solo run."""
    cfg, params = sfa_setup
    solo_a = _slot_ref(cfg, params, PROMPT, 12)
    solo_b = _slot_ref(cfg, params, PROMPT[:7], 5)
    eng = _paged(cfg, params, prefill_chunk=4)
    ra = eng.add_request(PROMPT, max_new_tokens=12)
    # A's prefill takes 3 chunk ticks; the activation tick also decodes
    for _ in range(3):
        eng.step()
    assert len(eng.outputs[ra]) == 2
    rb = eng.add_request(PROMPT[:7], max_new_tokens=5)
    a_before = len(eng.outputs[ra])
    ticks = 0
    while eng._inflight is not None or not eng.outputs[rb]:
        eng.step()
        ticks += 1
    # every tick of B's 2-chunk prefill also decoded a token for A
    assert len(eng.outputs[ra]) == a_before + ticks
    while eng.busy:
        eng.step()
    assert eng.outputs[ra] == solo_a
    assert eng.outputs[rb] == solo_b


# --------------------------------------------------------------------------
# scheduling: queueing, preemption, page accounting
# --------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [None, 4])
def test_queueing_and_preemption_match_solo_runs(sfa_setup, chunk):
    """Four requests, two slots, and a pool of six 8-token pages (shared
    budget sized via paged_page_bytes): admission queues, decode-time page
    exhaustion preempts the youngest request, and recompute-on-resume keeps
    every greedy stream exactly equal to its solo run."""
    cfg, params = sfa_setup
    prompts = [PROMPT, PROMPT[:7], PROMPT[:5], PROMPT[:9]]
    news = [6, 8, 5, 7]
    solo = [_slot_ref(cfg, params, p, mn) for p, mn in zip(prompts, news)]
    per = paged_page_bytes(cfg, page_size=8)
    eng = _paged(cfg, params, prefill_chunk=chunk,
                 mem_budget_bytes=6 * per)
    assert eng.num_pages == 1 + 6                # budget → 6 pages + trash
    rids = [eng.add_request(p, max_new_tokens=mn)
            for p, mn in zip(prompts, news)]
    util_peak, steps = 0.0, 0
    while eng.busy:
        eng.step()
        util_peak = max(util_peak, eng.page_utilization())
        steps += 1
        assert steps < 500, "scheduler livelock"
    for rid, want in zip(rids, solo):
        assert eng.outputs[rid] == want
    assert util_peak > 0.5                       # the pool was actually used
    # every page returned to the free list; block tables fully cleared
    assert len(eng.free_pages) == eng.num_pages - 1
    assert eng.page_utilization() == 0.0
    assert (eng.bt == 0).all()


def test_preemption_counted(sfa_setup):
    """A pool of three 8-token pages and two requests: the younger one
    needs its second page while the older holds the other two, so it is
    preempted exactly once, and both streams still match their solo
    runs."""
    cfg, params = sfa_setup
    prompts, news = [PROMPT, PROMPT[:7]], [10, 5]
    solo = [_slot_ref(cfg, params, p, mn) for p, mn in zip(prompts, news)]
    per = paged_page_bytes(cfg, page_size=8)
    eng = _paged(cfg, params, max_len=24, mem_budget_bytes=3 * per)
    assert eng.num_pages - 1 == 3
    rids = [eng.add_request(p, max_new_tokens=mn)
            for p, mn in zip(prompts, news)]
    while eng.busy:
        eng.step()
    assert [eng.outputs[r] for r in rids] == solo
    assert eng.counters.preemptions == 1
    assert eng.counters.pages_in_use_peak == 3


@pytest.mark.parametrize("n", [4, 7, 11])
def test_chunk_and_decode_ticks_counted(dense_setup, n):
    """A prompt of n tokens lands in ceil(n / chunk) chunks; a lone
    request decodes on every tick after its prefill until its budget."""
    cfg, params = dense_setup
    eng = _paged(cfg, params, prefill_chunk=4)
    eng.generate(PROMPT[:n], max_new_tokens=5)
    assert eng.counters.chunk_ticks == -(-n // 4)
    assert eng.counters.decode_ticks == 5 - 1
    assert eng.counters.preemptions == 0
    # the four decodes write positions n .. n + 3
    assert eng.counters.pages_in_use_peak == -(-(n + 4) // 8)


def test_engine_phases_are_profiler_spans(dense_setup, tmp_path):
    """Each phase of a tick is a span in the profiler's trace, on the
    device trace's clock."""
    cfg, params = dense_setup
    eng = _paged(cfg, params, prefill_chunk=4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.generate(PROMPT, max_new_tokens=3)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    names = {e.name for plane in data.planes for line in plane.lines
             for e in line.events if e.name.startswith("engine.")}
    assert names == {"engine.admit", "engine.prefill", "engine.ensure_pages",
                     "engine.decode", "engine.sample"}


def test_page_accounting_single_request(dense_setup):
    """Pages are allocated on demand (prompt pages up front, decode pages
    as the sequence crosses page boundaries) and all return on free."""
    cfg, params = dense_setup
    eng = _paged(cfg, params)
    total = eng.num_pages - 1
    rid = eng.add_request(PROMPT, max_new_tokens=8)     # 11 tokens, 8/page
    eng.step()
    # prompt + first decode token need ceil(12/8) = 2 pages
    assert len(eng.free_pages) == total - 2
    while not eng.done[rid]:
        eng.step()
    # 11 + 8 = 19 tokens crossed into a third page mid-decode
    assert len(eng.outputs[rid]) == 8
    assert len(eng.free_pages) == total          # all pages back
    assert not eng.busy


def test_first_token_reported_by_step(dense_setup):
    """step() reports a request the very tick it activates (the activation
    tick also decodes, so outputs already holds prefill + decode tokens and
    the returned token is the most recent)."""
    cfg, params = dense_setup
    eng = _paged(cfg, params)
    rid = eng.add_request(PROMPT[:5], max_new_tokens=3)
    out = eng.step()
    assert rid in out
    assert eng.outputs[rid] == [eng.outputs[rid][0], out[rid]]


# --------------------------------------------------------------------------
# request validation + budget semantics (paged mirrors the fixed slot engine)
# --------------------------------------------------------------------------

def test_paged_max_new_tokens_exact_budget(dense_setup):
    cfg, params = dense_setup
    ref = _slot_ref(cfg, params, PROMPT, 4)
    for mn in (1, 2):
        eng = _paged(cfg, params)
        assert eng.generate(PROMPT, max_new_tokens=mn) == ref[:mn]
        assert not eng.busy
        assert len(eng.free_pages) == eng.num_pages - 1
    with pytest.raises(ValueError, match="max_new_tokens"):
        _paged(cfg, params).add_request(PROMPT, max_new_tokens=0)


def test_paged_overlong_prompt_rejected(dense_setup):
    cfg, params = dense_setup
    eng = _paged(cfg, params)
    with pytest.raises(ValueError, match="max_len"):
        eng.add_request(np.arange(48, dtype=np.int64))


def test_pool_floored_to_one_request(dense_setup):
    """A memory budget below one request's worst case is floored to
    max_pages: a lone request always runs (no admission livelock) and
    still matches the slot engine."""
    cfg, params = dense_setup
    tiny = _paged(cfg, params,
                  mem_budget_bytes=2 * paged_page_bytes(cfg, page_size=8))
    assert tiny.num_pages - 1 == tiny.max_pages
    ref = _slot_ref(cfg, params, PROMPT, 6)
    assert tiny.generate(PROMPT, max_new_tokens=6) == ref


def test_paged_cache_bytes_budget(sfa_setup):
    """The realized pool respects the byte budget: cache bytes scale with
    the budget, and paged_page_bytes is the true marginal page cost."""
    cfg, params = sfa_setup
    per = paged_page_bytes(cfg, page_size=8)
    small = _paged(cfg, params, mem_budget_bytes=6 * per)
    big = _paged(cfg, params, mem_budget_bytes=10 * per)
    assert big.num_pages - small.num_pages == 4
    assert big.cache_bytes() - small.cache_bytes() == 4 * per


def test_mla_configs_refused_for_chunked_prefill():
    """Chunked prefill does not cover MLA caches: the chunk path must
    refuse loudly (whole-prompt paged serving still works)."""
    import jax.numpy as jnp

    from repro.models.attention import attention_apply
    cfg = _cfg("deepseek-v2-236b")
    assert cfg.attention.mla is not None
    with pytest.raises(NotImplementedError, match="MLA"):
        attention_apply({}, jnp.zeros((1, 4, cfg.d_model)), cfg=cfg,
                        mode="chunk", cache=object(), cache_len=0, slot=0)
