"""Serving launcher: build a model and answer batched requests.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
        --requests 4 --max-new 16 --decode-backend pallas

    # paged engine: shared page pool, 64 MiB budget, chunked prefill
    PYTHONPATH=src python -m repro.launch.serve --arch gpt2-small-sfa8 \
        --paged --mem-budget-mb 64 --prefill-chunk 128

``--full`` serves the published widths (the default is the reduced
CPU-size config).

``--decode-backend`` selects the serving attention kernel through the
backend registry (repro/models/backends.py): ``pallas`` = token-major
``flash_sfa_decode``, ``pallas_fm`` = feature-major on the persistent
``FeatureMajorKV`` image (the cache layout follows the backend), ``xla`` =
gather oracle, ``auto`` = platform default. ``--fm-debug`` turns on the
pallas_fm persistent-image integrity assertion (costly: it re-derives the
image every step — a correctness tool, not a serving mode).

``--paged`` serves through the ``PagedDecodeEngine`` (DESIGN.md §5):
block-table KV over a shared page pool (``--page-size`` tokens per page),
sized by ``--mem-budget-mb`` (default: full residency), with optional
chunked prefill (``--prefill-chunk`` tokens per engine tick) so long
prompts don't stall running decodes. Requests beyond the slot/page supply
queue and are admitted FCFS; decode-time page exhaustion preempts the
youngest request (recompute-on-resume, greedy streams unchanged).

``--speculative`` serves through the ``SpeculativeDecodeEngine``
(DESIGN.md §6, implies ``--paged``): each tick drafts ``--draft-len``
tokens with the cache re-thresholded to the top-``--draft-k`` sub-code
(default k/4 — same weights, same cache, k'^2/d draft cost), verifies
them in one batched full-k pass, and accepts the longest matching prefix
plus the bonus token. Greedy-only; streams are bit-identical to the
non-speculative paged engine. Acceptance stats print at exit.

Capability fallbacks (windowed or rope-protected layers, MLA, dense
caches) and the at-rest cache bytes are printed at exit.
"""
import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.core.kv_cache import kv_cache_nodes
from repro.launch.compile_cache import use_compile_cache
from repro.models import init as model_init
from repro.models.backends import fallback_reports, set_fm_debug
from repro.serve import (DecodeEngine, EngineConfig, PagedDecodeEngine,
                         PagedEngineConfig, SpeculativeDecodeEngine,
                         SpeculativeEngineConfig)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--decode-backend", default=None,
                    choices=["xla", "pallas", "pallas_fm", "auto"])
    ap.add_argument("--fm-debug", action="store_true",
                    help="assert the persistent feature-major K image "
                         "matches its recomputed form every pallas_fm step")
    ap.add_argument("--paged", action="store_true",
                    help="serve through the paged/block-KV engine")
    ap.add_argument("--page-size", type=int, default=128,
                    help="tokens per pool page (= decode kernel tile)")
    ap.add_argument("--mem-budget-mb", type=float, default=None,
                    help="KV pool byte budget; smaller budgets queue "
                         "admissions and preempt on page exhaustion "
                         "(default: full residency)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill: tokens landed per engine tick "
                         "interleaved with decode (default: whole-prompt)")
    ap.add_argument("--speculative", action="store_true",
                    help="self-speculative decoding on the paged engine: "
                         "draft with the nested top-k' sub-code, verify in "
                         "one full-k pass (greedy-only; implies --paged)")
    ap.add_argument("--draft-len", type=int, default=4,
                    help="tokens drafted per speculative engine tick")
    ap.add_argument("--draft-k", type=int, default=None,
                    help="draft-pass sparse k' (default: sfa_k // 4)")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="serve the published widths instead of the "
                         "reduced CPU-size config")
    args = ap.parse_args()

    use_compile_cache()
    if args.fm_debug:
        set_fm_debug(True)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = model_init(jax.random.PRNGKey(0), cfg)
    if args.speculative:
        args.paged = True
    if args.paged:
        budget = (None if args.mem_budget_mb is None
                  else int(args.mem_budget_mb * 2**20))
        if args.speculative:
            eng = SpeculativeDecodeEngine(params, cfg, SpeculativeEngineConfig(
                max_slots=max(args.requests, 2), max_len=args.max_len,
                page_size=args.page_size, mem_budget_bytes=budget,
                prefill_chunk=args.prefill_chunk,
                temperature=args.temperature,
                decode_backend=args.decode_backend,
                draft_len=args.draft_len, draft_k=args.draft_k))
        else:
            eng = PagedDecodeEngine(params, cfg, PagedEngineConfig(
                max_slots=max(args.requests, 2), max_len=args.max_len,
                page_size=args.page_size, mem_budget_bytes=budget,
                prefill_chunk=args.prefill_chunk,
                temperature=args.temperature,
                decode_backend=args.decode_backend))
    else:
        eng = DecodeEngine(params, cfg, EngineConfig(
            max_slots=max(args.requests, 2), max_len=args.max_len,
            temperature=args.temperature,
            decode_backend=args.decode_backend))
    rs = np.random.RandomState(0)
    rids = []
    for i in range(args.requests):
        prompt = rs.randint(0, cfg.vocab_size,
                            size=rs.randint(4, 32)).astype(np.int32)
        rids.append(eng.add_request(prompt, args.max_new))
    steps = 0
    if args.paged:
        while eng.busy:
            eng.step()
            steps += 1
        for rid in rids:
            print(f"request {rid}: {eng.outputs[rid]}")
        total = sum(len(eng.outputs[r]) for r in rids)
        print(f"{steps} engine ticks, {total} tokens, "
              f"{eng.num_pages - 1} pool pages x {eng.ecfg.page_size} tok, "
              f"final page utilization {eng.page_utilization():.2f}")
        if args.speculative:
            s = eng.spec_stats
            print(f"speculative: draft_len={eng.ecfg.draft_len} "
                  f"draft_k={eng.draft_k} alpha={s['alpha']:.2f} "
                  f"accepted-tokens/step={s['acc_per_step']:.2f}")
    else:
        while eng.live.any():
            eng.step()
            steps += 1
        for i in range(args.requests):
            print(f"slot {i}: {eng.outputs[i]}")
        print(f"{steps} batched decode steps, "
              f"{sum(len(o) for o in eng.outputs)} tokens")
    layouts = sorted({type(n).__name__
                      for n in kv_cache_nodes(eng.caches)})
    print(f"kv cache at rest: {eng.cache_bytes() / 2**20:.2f} MiB "
          f"({', '.join(layouts)})")
    for rep in fallback_reports():
        print(f"backend fallback: {rep.requested} -> {rep.selected} "
              f"({rep.reason}) at {rep.where}")


if __name__ == "__main__":
    main()
