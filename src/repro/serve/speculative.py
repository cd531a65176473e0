"""Self-speculative decoding via nested-k sparse codes (DESIGN.md §6).

SFA gives a draft-model family for free: because ``topk_mask`` selects by
a global magnitude threshold, the top-k' entries of a stored top-k code ARE
the global top-k' code (``core/sparse.py::sub_k``) — same weights, same KV
cache, overlap cost k'^2/d instead of k^2/d (paper Eq. 3). The
``SpeculativeDecodeEngine`` exploits this as an engine mode on top of the
paged engine:

  1. **draft** — ``draft_len`` batched decode steps with ``sfa_draft_k``
     set on the attention config: the backend re-thresholds the stored
     codes to k' per step (and sparsifies the query at k'), so the draft
     pass reads k'/k of the cache bytes. Draft K/V writes land normally
     (positions L..L+J-1) but their layer>1 hidden states saw low-k'
     reads — they are provisional.
  2. **verify** — ONE batched full-k pass per live slot
     (``models/model.py::verify_step``): the C = draft_len + 1 tokens
     [pending, d_1..d_J] are chunk-written at positions L..L+J with full-k
     codes (overwriting every provisional draft write — the K/V-resolution
     contract) and every query is scored at its own causal length through
     the backend ``verify`` entry point (one multi-token kernel launch).
  3. **accept** — the standard greedy rule: with targets
     ``tg[j] = argmax(logits[j])``, accept the longest prefix where
     ``d_{j+1} == tg[j]``, then emit the bonus token ``tg[m]`` — at least
     one token per tick, and every emitted token is exactly the token the
     non-speculative engine would have produced (bit-identical streams).
  4. **rewind** — rejected positions need no data rollback (all reads are
     length-masked and future writes overwrite sequentially); the length
     rolls back to L + accepted + 1 and pages allocated for the rejected
     lookahead return to the free list.

Greedy-only by construction: the acceptance rule compares argmaxes, so
``temperature > 0`` is refused rather than silently biased.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.models import decode_step, verify_step
from repro.serve.engine import PagedDecodeEngine, PagedEngineConfig


@functools.lru_cache(maxsize=16)
def _spec_jitted_fns(cfg: ModelConfig, draft_k: int):
    """Compiled draft-decode (low-k' read path) + verify steps, shared per
    (config, draft_k) like the engine's other jit caches. The draft config
    differs from ``cfg`` only in ``attention.sfa_draft_k`` — same cache
    pytree signature, so draft and full decode share the engine caches."""
    draft_cfg = dataclasses.replace(cfg, attention=dataclasses.replace(
        cfg.attention, sfa_draft_k=draft_k))
    drf = jax.jit(lambda p, tok, caches, lens: decode_step(p, tok, caches,
                                                           lens, draft_cfg))
    ver = jax.jit(lambda p, toks, caches, off, slot: verify_step(
        p, toks, caches, off, slot, cfg))
    return drf, ver


@dataclasses.dataclass
class SpeculativeEngineConfig(PagedEngineConfig):
    draft_len: int = 4               # J: drafted tokens per engine tick
    # draft-pass k' (None = max(1, sfa_k // 4) — the paper's k-vs-accuracy
    # charts put k/4 well inside the usable range, and k'^2/d makes the
    # draft overlap pass 16x cheaper there)
    draft_k: Optional[int] = None


class SpeculativeDecodeEngine(PagedDecodeEngine):
    """Paged engine tick with draft/verify/accept/rewind in place of the
    single decode step. Scheduling (admission, chunked prefill, preemption
    by recompute) is inherited unchanged — the engine invariant
    ``lengths = prompt + emitted - 1`` with the last emitted token pending
    holds after every tick, so a preempted speculative request resumes
    through the exact same replay path as the base engine."""

    def __init__(self, params, cfg: ModelConfig, ecfg: SpeculativeEngineConfig):
        a = cfg.attention
        if a is None or a.sfa_k is None:
            raise ValueError(
                "speculative decoding drafts by re-thresholding stored "
                "top-k codes (sub_k): the config must set attention.sfa_k")
        if a.mla is not None:
            raise NotImplementedError(
                "speculative decoding does not cover MLA caches (no "
                "multi-token verify path through the latent cache)")
        if ecfg.temperature > 0:
            raise ValueError(
                "speculative decoding is greedy-only: the acceptance rule "
                "compares argmaxes (temperature must be 0)")
        if ecfg.draft_len < 1:
            raise ValueError(f"draft_len must be >= 1, got {ecfg.draft_len}")
        super().__init__(params, cfg, ecfg)
        dk = (ecfg.draft_k if ecfg.draft_k is not None
              else max(1, a.sfa_k // 4))
        if not 1 <= dk <= a.sfa_k:
            raise ValueError(f"draft_k must be in [1, sfa_k={a.sfa_k}], "
                             f"got {dk}")
        self.draft_k = dk
        # self.cfg carries the decode_backend override applied by the base
        self._draft, self._verify = _spec_jitted_fns(self.cfg, dk)
        self._spec = {"ticks": 0, "drafted": 0, "accepted": 0, "emitted": 0}

    # ------------------------------------------------------------------
    @property
    def spec_stats(self) -> dict:
        """Acceptance telemetry: ``alpha`` = accepted drafts / drafted,
        ``acc_per_step`` = emitted tokens per decode tick (>= 1; the bonus
        token makes a tick never slower than a plain decode step)."""
        s = dict(self._spec)
        s["alpha"] = s["accepted"] / max(s["drafted"], 1)
        s["acc_per_step"] = s["emitted"] / max(s["ticks"], 1)
        return s

    # ------------------------------------------------------------------
    def _decode_page_span(self, slot: int):
        # draft writes reach position L + J - 1 and verify writes L + J;
        # reserve the pages under the whole lookahead (positions past the
        # block table route to the trash page — near-max_len slots draft
        # into it harmlessly, those tokens are never emitted)
        page = self.ecfg.page_size
        first = int(self.lengths[slot])
        last = min(first + self.ecfg.draft_len, self.max_pages * page - 1)
        return range(first // page, last // page + 1)

    def _rewind(self, slot: int):
        """Return the pages allocated past the accepted length to the free
        list (the rejected lookahead). Content needs no rollback: every
        read is length-masked and sequential decode overwrites positions
        >= lengths before they become visible."""
        keep = (int(self.lengths[slot]) - 1) // self.ecfg.page_size
        row = self.bt[slot]
        for j in range(keep + 1, self.max_pages):
            if row[j]:
                self.free_pages.append(int(row[j]))
                row[j] = 0
                self._bt_dirty = True

    def _decode_tick(self) -> dict[int, int]:
        live_before = self.live.copy()
        self._push_bt()
        page = self.ecfg.page_size
        sentinel = self.max_pages * page
        J = self.ecfg.draft_len
        # pending tokens BEFORE drafting mutates nothing: slot state
        # (lengths, last_token) is only committed at acceptance
        t0 = np.asarray(self.last_token).astype(np.int64)
        cur = self.last_token
        drafts = np.zeros((J, self.ecfg.max_slots), np.int64)
        for j in range(J):
            lens = np.where(self.live, self.lengths + j,
                            sentinel).astype(np.int32)
            logits, self.caches = self._draft(self.params, cur, self.caches,
                                              jnp.asarray(lens))
            cur = self._sample(logits)
            with TraceAnnotation("engine.sample"):
                drafts[j] = np.asarray(cur)
        out = {}
        self._spec["ticks"] += 1
        new_last = np.asarray(self.last_token).copy()
        for slot in np.where(live_before)[0]:
            slot = int(slot)
            L = int(self.lengths[slot])
            toks = np.concatenate([t0[slot:slot + 1], drafts[:, slot]])
            logits, self.caches = self._verify(
                self.params, jnp.asarray(toks[None, :], jnp.int32),
                self.caches, jnp.int32(L), jnp.int32(slot))
            targets = self._sample(logits)
            with TraceAnnotation("engine.sample"):
                tg = np.asarray(targets).astype(np.int64)       # (C,)
            m = 0
            while m < J and drafts[m, slot] == tg[m]:
                m += 1
            self._spec["drafted"] += J
            self._spec["accepted"] += m
            rid = int(self.slot_rid[slot])
            emitted = 0
            # per-token emission replays the base engine's checks exactly:
            # eos / budget / max_len truncate the accepted run mid-stream,
            # so the emitted prefix is token-for-token what a sequence of
            # plain decode ticks would have produced
            for i in range(m + 1):
                t = int(tg[i])
                out[rid] = t
                self.outputs[rid].append(t)
                self.budgets[slot] -= 1
                emitted += 1
                self._spec["emitted"] += 1
                new_last[slot] = t
                if (t == self.ecfg.eos_id or self.budgets[slot] <= 0 or
                        L + emitted >= self.ecfg.max_len):
                    self._finish(slot)
                    break
            self.lengths[slot] = L + emitted
            if self.live[slot]:
                self._rewind(slot)
        self.last_token = jnp.asarray(new_last.astype(np.int32))
        return out
