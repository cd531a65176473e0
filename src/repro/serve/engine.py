"""Batched decode engine: slots, prefill→decode handoff, typed KV caches.

Continuous-batching-lite: a fixed number of slots; requests prefill
individually (batch-1 prefill, realistic for latency-bound serving) and are
inserted into a slot of the batched decode cache; every ``step()`` decodes
one token for all live slots. Greedy or temperature sampling; slots free on
EOS/max_tokens. The decode step is a single jitted function over the full
slot batch — the shape the decode_32k/long_500k dry-run cells lower.

Caches are typed ``KVCache`` pytrees (repro/core/kv_cache.py): slot
insertion dispatches on each field's structural token axis instead of
shape-sniffing, and ``EngineConfig.decode_backend`` selects the serving
attention kernel through the backend registry (``"pallas"`` = token-major
``flash_sfa_decode``, ``"pallas_fm"`` = feature-major ``flash_sfa_decode_fm``
on the *persistent* ``FeatureMajorKV`` image — the cache layout follows the
selected backend, so prefill handoff, per-step writes, and slot
eviction/reuse all maintain the image incrementally with zero per-step
re-materialization; ``"xla"`` = the gather oracle). Slot lengths live
host-side (NumPy): the decode step reads them as device inputs, but
per-slot bookkeeping never forces a device→host sync.
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
from repro.core.kv_cache import KVCache, cache_nbytes
from repro.models import (decode_step, init_decode_caches,
                          init_paged_decode_caches, prefill, prefill_chunk)
from repro.models.attention import decode_cache_token_multiple


@functools.lru_cache(maxsize=16)
def _jitted_fns(cfg: ModelConfig):
    """Compiled prefill/decode shared across engines with the same config —
    spinning up a new engine (tests, multi-tenant serving) reuses the jit
    cache instead of re-tracing the whole model. Bounded so a long-lived
    process serving many distinct configs doesn't pin executables forever."""
    pre = jax.jit(lambda p, batch: prefill(p, batch, cfg))
    dec = jax.jit(lambda p, tok, caches, lens: decode_step(p, tok, caches,
                                                           lens, cfg))
    return pre, dec


class _SamplerMixin:
    """Shared sampling policy: greedy at ``temperature <= 0``, else
    temperature-scaled categorical off the engine's own PRNG stream. One
    implementation for every engine (slot, paged, speculative) — the
    engines only need ``self.ecfg.temperature`` and ``self._rng``."""

    def _sample(self, logits):
        if self.ecfg.temperature <= 0:
            return jnp.argmax(logits, -1).astype(jnp.int32)
        self._rng, sub = jax.random.split(self._rng)
        return jax.random.categorical(
            sub, logits / self.ecfg.temperature, -1).astype(jnp.int32)


@dataclasses.dataclass
class EngineConfig:
    max_slots: int = 8
    max_len: int = 512
    eos_id: int = -1                 # -1: never stop on token
    temperature: float = 0.0         # 0 = greedy
    seed: int = 0
    # None = use cfg.attention.decode_backend; else override per engine
    # ("xla" | "pallas" | "pallas_fm" | "auto")
    decode_backend: Optional[str] = None


class DecodeEngine(_SamplerMixin):
    def __init__(self, params, cfg: ModelConfig, ecfg: EngineConfig):
        if ecfg.decode_backend is not None and cfg.attention is not None:
            cfg = dataclasses.replace(cfg, attention=dataclasses.replace(
                cfg.attention, decode_backend=ecfg.decode_backend))
        self.params = params
        self.cfg = cfg
        self.ecfg = ecfg
        # token axis allocated in whole kernel tiles (pallas_fm streams the
        # persistent image 128 tokens at a time; a ragged tail would make
        # the kernel pad-copy the whole cache every step). max_len keeps
        # its request-cap meaning; only the allocation rounds up.
        mult = decode_cache_token_multiple(cfg)
        self._cache_len = -(-ecfg.max_len // mult) * mult
        self.caches = init_decode_caches(cfg, ecfg.max_slots, self._cache_len)
        # host-side slot lengths: per-slot bookkeeping (EOS/max_len checks)
        # must not force a device→host transfer every step
        self.lengths = np.zeros((ecfg.max_slots,), np.int32)
        self.last_token = jnp.zeros((ecfg.max_slots,), jnp.int32)
        self.live = np.zeros((ecfg.max_slots,), bool)
        self.outputs: list[list[int]] = [[] for _ in range(ecfg.max_slots)]
        self.budgets = np.zeros((ecfg.max_slots,), np.int64)
        self._rng = jax.random.PRNGKey(ecfg.seed)
        self._prefill, self._decode = _jitted_fns(cfg)

    # ------------------------------------------------------------------
    def cache_bytes(self) -> int:
        """At-rest bytes of the engine's KV caches (KVCache leaves only) —
        the serving-side number the bench kvreal_* rows model per token."""
        return cache_nbytes(self.caches)

    # ------------------------------------------------------------------
    def _insert_cache(self, slot: int, one_caches):
        """Insert a batch-1 prefill cache into the slot of the batched
        cache. KVCache nodes know their token axis (insert_slot pads it to
        the allocated cache length from the source's own length); SSM
        recurrent states have no length axis and land with a plain slot
        update."""
        max_len = self._cache_len

        def ins(dst, src):
            if isinstance(dst, KVCache):
                return dst.insert_slot(src, slot=slot, max_len=max_len)
            if src is None:
                return dst
            start = (0, slot) + (0,) * (src.ndim - 2)
            return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype),
                                                start)

        self.caches = jax.tree.map(
            ins, self.caches, one_caches,
            is_leaf=lambda x: isinstance(x, KVCache))

    def add_request(self, prompt: np.ndarray, max_new_tokens: int = 32,
                    extra_inputs: Optional[dict] = None) -> int:
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        free = np.where(~self.live)[0]
        if len(free) == 0:
            raise RuntimeError("no free slots")
        slot = int(free[0])
        n = int(prompt.shape[0])
        if self.cfg.frontend is not None and self.cfg.frontend.kind == "patch" \
                and extra_inputs and "patches" in extra_inputs:
            n += self.cfg.frontend.prefix_len
        if n >= self.ecfg.max_len:
            raise ValueError(
                f"prompt is {n} tokens (patch-frontend prefix included) but "
                f"max_len is {self.ecfg.max_len}: the engine needs at least "
                f"one free cache position past the prompt to decode")
        batch = {"tokens": jnp.asarray(prompt[None, :], jnp.int32)}
        if extra_inputs:
            batch.update({k: jnp.asarray(v[None]) for k, v in
                          extra_inputs.items()})
        logits, one_caches = self._prefill(self.params, batch)
        self._insert_cache(slot, one_caches)
        tok = self._sample(logits)
        self.lengths[slot] = n
        self.last_token = self.last_token.at[slot].set(int(tok[0]))
        self.outputs[slot] = [int(tok[0])]
        self.budgets[slot] = max_new_tokens - 1
        # a request whose budget is already exhausted (max_new_tokens == 1)
        # — or whose first sampled token is EOS — never goes live: step()
        # must not decode (and append) another token past the budget
        self.live[slot] = (self.budgets[slot] > 0
                           and int(tok[0]) != self.ecfg.eos_id)
        return slot

    def step(self) -> dict[int, int]:
        """Decode one token for every live slot; returns {slot: token}."""
        if not self.live.any():
            return {}
        live_before = self.live.copy()
        logits, self.caches = self._decode(self.params, self.last_token,
                                           self.caches,
                                           jnp.asarray(self.lengths))
        toks = self._sample(logits)
        # every slot that decoded gained one cache entry — bump BEFORE the
        # free checks, so a slot freed below is frozen at its true content
        # length (a stale +1 here becomes a page-accounting bug once freed
        # slots return their pages to the paged pool)
        self.lengths = self.lengths + live_before.astype(np.int32)
        out = {}
        for slot in np.where(live_before)[0]:
            t = int(toks[slot])
            out[int(slot)] = t
            self.outputs[slot].append(t)
            self.budgets[slot] -= 1
            if (t == self.ecfg.eos_id or self.budgets[slot] <= 0 or
                    int(self.lengths[slot]) >= self.ecfg.max_len):
                self.live[slot] = False
        self.last_token = toks
        return out

    def generate(self, prompt: np.ndarray, max_new_tokens: int = 32,
                 extra_inputs: Optional[dict] = None) -> list[int]:
        """Single-request convenience wrapper."""
        slot = self.add_request(prompt, max_new_tokens, extra_inputs)
        while self.live[slot]:
            self.step()
        return self.outputs[slot]


# ==========================================================================
# paged engine
# ==========================================================================

@functools.lru_cache(maxsize=16)
def _paged_jitted_fns(cfg: ModelConfig):
    """Compiled chunk-prefill / page-insert steps, shared per config (the
    decode step and whole-prompt prefill reuse ``_jitted_fns``)."""
    pch = jax.jit(lambda p, toks, caches, off, valid, slot: prefill_chunk(
        p, toks, caches, off, valid, slot, cfg))
    ins = jax.jit(lambda caches, src, pids: [
        c.insert_pages(s, pids) for c, s in zip(caches, src)])
    return pch, ins


@dataclasses.dataclass
class PagedEngineConfig:
    max_slots: int = 8
    max_len: int = 512               # per-request cap (prompt + output)
    page_size: int = 128             # tokens per pool page (= kernel tile)
    # pool memory budget in bytes (KV pools, all layers). None sizes the
    # pool for full residency (max_slots × max_pages); smaller budgets make
    # admission queue and decode growth preempt (recompute on re-admission)
    mem_budget_bytes: Optional[int] = None
    # prefill granularity: None = whole-prompt prefill landed via
    # insert_pages; an int C = chunked prefill, one C-token chunk per step
    # interleaved with decode (no whole-prompt stall)
    prefill_chunk: Optional[int] = None
    eos_id: int = -1
    temperature: float = 0.0
    seed: int = 0
    decode_backend: Optional[str] = None


@dataclasses.dataclass
class EngineCounters:
    """Scheduling events of a paged engine since it was built. The pages
    in use now are ``PagedDecodeEngine.page_utilization()``."""
    chunk_ticks: int = 0           # chunked-prefill chunks run
    decode_ticks: int = 0          # ticks that decoded the live slots
    preemptions: int = 0           # live requests evicted for their pages
    pages_in_use_peak: int = 0     # most pool pages held at once


@dataclasses.dataclass
class _PagedRequest:
    rid: int
    prompt: np.ndarray               # tokens to (re)prefill
    max_new: int
    # set on requeue after preemption: the already-sampled-but-unwritten
    # token and the remaining budget (greedy recompute resumes exactly)
    resume_token: Optional[int] = None
    budget: Optional[int] = None


class PagedDecodeEngine(_SamplerMixin):
    """Paged/block-KV serving engine (DESIGN.md §5).

    vLLM-style block tables over the typed paged cache pytrees: one shared
    page pool per layer, ``page_size``-token pages allocated on demand from
    a host-side free list, slots holding ``(max_pages,)`` block-table rows
    that are pushed to the device only when they change. Prompts land
    whole-prompt (``insert_pages``) or chunked (``prefill_chunk``, one chunk
    per ``step()`` interleaved with decode — no whole-prompt stall);
    admission queues when slots or pages run out, and decode-time page
    exhaustion preempts the youngest live request (recompute-on-resume, so
    greedy streams are bit-reproducible). Greedy tokens match the slot
    ``DecodeEngine`` exactly; requests are keyed by rid, not slot.

    Each phase of ``step()`` runs in a profiler span (``engine.admit``,
    ``engine.prefill``, ``engine.ensure_pages``, ``engine.decode``; the
    host read of sampled tokens in ``engine.sample``), on the clock of the
    device trace; ``counters`` counts the scheduling events.
    """

    def __init__(self, params, cfg: ModelConfig, ecfg: PagedEngineConfig):
        if ecfg.decode_backend is not None and cfg.attention is not None:
            cfg = dataclasses.replace(cfg, attention=dataclasses.replace(
                cfg.attention, decode_backend=ecfg.decode_backend))
        self.params = params
        self.cfg = cfg
        self.ecfg = ecfg
        page = ecfg.page_size
        self.max_pages = -(-ecfg.max_len // page)
        if ecfg.mem_budget_bytes is None:
            pool = ecfg.max_slots * self.max_pages
        else:
            from repro.serve.kv_cache import paged_page_bytes
            per = paged_page_bytes(cfg, page_size=page)
            pool = max(self.max_pages, ecfg.mem_budget_bytes // max(per, 1))
            pool = min(pool, ecfg.max_slots * self.max_pages)
        # + the reserved trash page 0 (dead-slot decode writes land there)
        self.num_pages = 1 + int(pool)
        self.caches = init_paged_decode_caches(
            cfg, slots=ecfg.max_slots, num_pages=self.num_pages,
            page_size=page, max_pages=self.max_pages)
        self.bt = np.zeros((ecfg.max_slots, self.max_pages), np.int32)
        self._bt_dirty = True
        self.free_pages = list(range(self.num_pages - 1, 0, -1))  # pop() = 1
        self.lengths = np.zeros((ecfg.max_slots,), np.int32)
        self.live = np.zeros((ecfg.max_slots,), bool)
        self.last_token = jnp.zeros((ecfg.max_slots,), jnp.int32)
        self.budgets = np.zeros((ecfg.max_slots,), np.int64)
        self.slot_rid = np.full((ecfg.max_slots,), -1, np.int64)
        self.slot_seq = np.zeros((ecfg.max_slots,), np.int64)  # admission age
        self.outputs: dict[int, list[int]] = {}
        self.done: dict[int, bool] = {}
        self.queue: list[_PagedRequest] = []
        self._by_rid: dict[int, _PagedRequest] = {}
        self._emitted: dict[int, int] = {}   # first tokens this tick
        self._inflight = None            # chunked prefill in progress
        self._next_rid = 0
        self._seq = 0
        self._rng = jax.random.PRNGKey(ecfg.seed)
        self._prefill, self._decode = _jitted_fns(cfg)
        self._chunk, self._insert = _paged_jitted_fns(cfg)
        self.counters = EngineCounters()

    # ------------------------------------------------------------------
    def cache_bytes(self) -> int:
        """At-rest bytes of the paged pools (block tables included)."""
        return cache_nbytes(self.caches)

    def _pages_in_use(self) -> int:
        return self.num_pages - 1 - len(self.free_pages)

    def page_utilization(self) -> float:
        """Fraction of allocatable pool pages currently holding live data."""
        return self._pages_in_use() / max(self.num_pages - 1, 1)

    def _note_pages(self):
        c = self.counters
        c.pages_in_use_peak = max(c.pages_in_use_peak, self._pages_in_use())

    @property
    def busy(self) -> bool:
        return bool(self.queue) or self._inflight is not None \
            or bool(self.live.any())

    # ------------------------------------------------------------------
    def add_request(self, prompt: np.ndarray, max_new_tokens: int = 32) -> int:
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        n = int(prompt.shape[0])
        if n >= self.ecfg.max_len:
            raise ValueError(
                f"prompt is {n} tokens but max_len is {self.ecfg.max_len}: "
                f"the engine needs at least one free cache position past "
                f"the prompt to decode")
        page = self.ecfg.page_size
        worst = min(n + max_new_tokens, self.ecfg.max_len)
        if -(-worst // page) > self.num_pages - 1:
            raise ValueError(
                f"request needs up to {-(-worst // page)} pages but the pool "
                f"holds {self.num_pages - 1}: raise mem_budget_bytes or "
                f"lower max_new_tokens")
        rid = self._next_rid
        self._next_rid += 1
        self.outputs[rid] = []
        self.done[rid] = False
        self.queue.append(_PagedRequest(rid=rid,
                                        prompt=np.asarray(prompt, np.int64),
                                        max_new=max_new_tokens))
        return rid

    # ---- page + block-table plumbing ---------------------------------
    def _push_bt(self):
        if not self._bt_dirty:
            return
        bt = jnp.asarray(self.bt)

        def rep(c):
            layers = c.block_table.shape[0]
            return dataclasses.replace(
                c, block_table=jnp.broadcast_to(bt, (layers,) + bt.shape))

        self.caches = [rep(c) for c in self.caches]
        self._bt_dirty = False

    def _release_slot(self, slot: int):
        self.free_pages.extend(int(p) for p in self.bt[slot] if p)
        self.bt[slot, :] = 0
        self._bt_dirty = True
        self.live[slot] = False
        self.slot_rid[slot] = -1

    def _finish(self, slot: int):
        self.done[int(self.slot_rid[slot])] = True
        self._release_slot(slot)

    def _preempt(self, slot: int) -> _PagedRequest:
        """Evict a live slot; recompute-on-resume keeps greedy streams
        exact: the requeued prompt replays everything already in the cache
        and ``resume_token`` re-seeds the pending (sampled, unwritten)
        token."""
        self.counters.preemptions += 1
        rid = int(self.slot_rid[slot])
        req = self._by_rid[rid]
        out = self.outputs[rid]
        requeued = _PagedRequest(
            rid=rid,
            prompt=np.concatenate([req.prompt,
                                   np.asarray(out[:-1], np.int64)]),
            max_new=req.max_new,
            resume_token=out[-1],
            budget=int(self.budgets[slot]))
        self._release_slot(slot)
        return requeued

    # ---- scheduling phases -------------------------------------------
    def _admit(self):
        """Admit queued requests FCFS while slots + reserved pages last.
        Whole-prompt prefills land immediately (several per tick, like the
        slot engine filling its free slots); chunked prefill carries at
        most one in-flight prompt, so it admits one per tick."""
        while self.queue and self._inflight is None:
            free = np.where(~self.live & (self.slot_rid < 0))[0]
            if len(free) == 0:
                return
            req = self.queue[0]
            page = self.ecfg.page_size
            need = -(-(len(req.prompt) + 1) // page)   # prompt + 1 decode
            if len(self.free_pages) < need:
                return
            self.queue.pop(0)
            slot = int(free[0])
            for j in range(need):
                self.bt[slot, j] = self.free_pages.pop()
            self._bt_dirty = True
            self._note_pages()
            self.slot_rid[slot] = req.rid
            self._seq += 1
            self.slot_seq[slot] = self._seq
            self._by_rid[req.rid] = req
            if self.ecfg.prefill_chunk is None:
                self._prefill_whole(slot, req)
            else:
                self._inflight = {"slot": slot, "req": req, "off": 0}

    def _prefill_whole(self, slot: int, req: _PagedRequest):
        plen = len(req.prompt)
        logits, one_caches = self._prefill(
            self.params, {"tokens": jnp.asarray(req.prompt[None, :],
                                                jnp.int32)})
        npg = -(-plen // self.ecfg.page_size)
        pids = jnp.asarray(self.bt[slot, :npg])
        self.caches = self._insert(self.caches, one_caches, pids)
        self._activate(slot, req, logits)

    def _prefill_tick(self):
        """Advance the in-flight chunked prefill by ONE chunk."""
        if self._inflight is None:
            return
        st = self._inflight
        slot, req, off = st["slot"], st["req"], st["off"]
        prompt, c = req.prompt, self.ecfg.prefill_chunk
        take = min(c, len(prompt) - off)
        chunk = np.zeros(c, np.int64)
        chunk[:take] = prompt[off:off + take]
        self._push_bt()
        logits, self.caches = self._chunk(
            self.params, jnp.asarray(chunk[None, :], jnp.int32), self.caches,
            jnp.int32(off), jnp.int32(take), jnp.int32(slot))
        self.counters.chunk_ticks += 1
        st["off"] = off + take
        if st["off"] >= len(prompt):
            self._inflight = None
            self._activate(slot, req, logits[None])

    def _activate(self, slot: int, req: _PagedRequest, logits):
        """Prefill done: seed the first token and go live (or finish
        immediately when the budget is already exhausted / EOS — mirroring
        the slot engine's fixed admission semantics)."""
        rid = req.rid
        if req.resume_token is None:
            first = self._sample(logits)
            with TraceAnnotation("engine.sample"):
                tok = int(first[0])
            self.outputs[rid].append(tok)
            self._emitted[rid] = tok
            budget = req.max_new - 1
        else:
            tok = req.resume_token            # already sampled pre-emption
            budget = req.budget
        self.lengths[slot] = len(req.prompt)
        self.last_token = self.last_token.at[slot].set(tok)
        self.budgets[slot] = budget
        if budget > 0 and tok != self.ecfg.eos_id:
            self.live[slot] = True
        else:
            self._finish(slot)

    def _decode_page_span(self, slot: int):
        """Logical page indices that must be allocated before this slot
        decodes this tick — the page under the next write position. The
        speculative engine widens this to cover its draft lookahead."""
        pidx = int(self.lengths[slot]) // self.ecfg.page_size
        return range(pidx, pidx + 1)

    def _ensure_decode_pages(self):
        """Allocate the page span under each live slot's upcoming writes
        (``_decode_page_span``); page exhaustion preempts the youngest live
        request (its pages come back to the pool; it requeues at the
        front)."""
        requeue = []
        for slot in np.where(self.live)[0]:
            if not self.live[slot]:
                continue                      # preempted below this tick
            for pidx in self._decode_page_span(slot):
                if not self.live[slot]:
                    break
                while self.bt[slot, pidx] == 0 and not self.free_pages:
                    live = np.where(self.live)[0]
                    victims = sorted(live, key=lambda s: int(self.slot_seq[s]))
                    victim = int(victims[-1])     # youngest admission
                    requeue.append(self._preempt(victim))
                    if victim == slot:
                        break
                if not self.live[slot]:
                    break
                if self.bt[slot, pidx] == 0:
                    self.bt[slot, pidx] = self.free_pages.pop()
                    self._bt_dirty = True
                    self._note_pages()
        # youngest was preempted first; resume in admission order (oldest
        # requeued entry at the very front)
        for req in requeue:
            self.queue.insert(0, req)

    def _decode_tick(self) -> dict[int, int]:
        live_before = self.live.copy()
        self._push_bt()
        # non-live slots decode at a past-the-table sentinel position: their
        # fixed-width batch writes land in the trash page, never in pages a
        # queued/prefilling tenant of the same slot just got allocated
        sentinel = self.max_pages * self.ecfg.page_size
        lens = np.where(self.live, self.lengths, sentinel).astype(np.int32)
        logits, self.caches = self._decode(self.params, self.last_token,
                                           self.caches, jnp.asarray(lens))
        toks = self._sample(logits)
        with TraceAnnotation("engine.sample"):
            host_toks = np.asarray(toks)
        self.lengths = self.lengths + live_before.astype(np.int32)
        out = {}
        for slot in np.where(live_before)[0]:
            t = int(host_toks[slot])
            rid = int(self.slot_rid[slot])
            out[rid] = t
            self.outputs[rid].append(t)
            self.budgets[slot] -= 1
            if (t == self.ecfg.eos_id or self.budgets[slot] <= 0 or
                    int(self.lengths[slot]) >= self.ecfg.max_len):
                self._finish(slot)
        self.last_token = toks
        return out

    def step(self) -> dict[int, int]:
        """One engine tick: admit ≤1 queued request, advance the in-flight
        chunked prefill by one chunk, grow/steal decode pages, then decode
        one token for every live slot. Returns {rid: token} — the most
        recent token per request this tick (a request that activates AND
        decodes in one tick emits two; ``outputs`` holds the full
        stream)."""
        if not self.busy:
            return {}
        self._emitted = {}
        with TraceAnnotation("engine.admit"):
            self._admit()
        with TraceAnnotation("engine.prefill"):
            self._prefill_tick()
        with TraceAnnotation("engine.ensure_pages"):
            self._ensure_decode_pages()
        out = {}
        if self.live.any():
            self.counters.decode_ticks += 1
            with TraceAnnotation("engine.decode"):
                out = self._decode_tick()
        return {**self._emitted, **out}

    def generate(self, prompt: np.ndarray, max_new_tokens: int = 32) -> list:
        """Single-request convenience wrapper."""
        rid = self.add_request(prompt, max_new_tokens)
        while not self.done[rid]:
            self.step()
        return self.outputs[rid]
