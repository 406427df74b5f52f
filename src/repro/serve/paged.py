"""Paged KV serving: cache capacity bounded by HBM bytes, not slots.

The fixed-slot :class:`~repro.serve.engine.ServeEngine` allocates one
contiguous ``(n_slots, max_len, ...)`` cache, so a single long-context
request sizes *every* slot and ``n_slots`` — not token budget — caps
concurrency. This module replaces that paradigm:

* :class:`PagedKVCache` — a pure host-side allocator over a pool of
  fixed-size pages: free-list alloc/release, per-page refcounts, and a
  prefix registry that shares prompt-prefix pages across requests
  (copy-on-write by construction: decode always writes at positions
  past the shared prefix, which land in the writer's private pages, so
  a shared page is never mutated).
* :class:`PagedServeEngine` — the ServeEngine with the contiguous cache
  swapped for one pooled ``(L, n_pages, page_size) + row`` KV buffer
  per layer group plus per-slot page tables
  (``models.model.paged_cache_spec``). Admission allocates a request's
  worst-case pages up front (``Scheduler.pages_for`` — window-capped,
  so sliding-window configs never hold more than ``ceil(W/ps)`` pages)
  and *waits on the page budget*, not on free slots; retirement frees
  the pages back to the pool. Prefill still runs at the scheduler's
  bucketed shapes, then a jitted scatter writes the rows through the
  page table, so the compile-count bound is unchanged.
* two cache groups — a model of mixed attention kinds keeps its full
  layers' pages as above and its window layers' rows in a second pool,
  where slot ``s`` owns the ring of pages ``s * npw + 1 .. (s + 1) *
  npw`` (``npw = ceil(W/ps)``) under a page table written once: a ring
  never outgrows its window, so that group needs no allocator.
* prefix caching — full prompt pages are registered under a chained
  content hash; a later prompt sharing the prefix maps those physical
  pages into its table (refcount++), sets ``pos`` past them, and
  decode-feeds only the unshared tail through the *already compiled*
  step function — repeated-system-prompt traces skip the duplicate
  prefill entirely. Registered pages survive release at refcount 1
  (the registry's reference) and are evicted LRU when the free list
  runs dry.

Token streams are bit-identical to the fixed-slot engine on the same
requests: prefill math is shared, the paged gather attends over exactly
the same cache rows, and sampling is seeded per request id
(tests/test_serve_paged.py asserts parity across every cache family).
"""
from __future__ import annotations

import hashlib
import logging
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.models.model import (PAGED_CACHE_AXES, decode_step_paged,
                                init_paged_cache, page_count,
                                paged_cache_axes, write_prefill_pages,
                                write_prefill_pages_quant)
from repro.serve.engine import Request, ServeEngine, _splice
from repro.serve.scheduler import PAD_SAFE_FAMILIES, AdmissionPlan

log = logging.getLogger("repro.serve")

#: Physical page 0 is never allocated: unowned page-table entries point
#: at it and retired slots write their (masked) decode rows into it.
NULL_PAGE = 0


class PagesExhausted(RuntimeError):
    """Raised by :meth:`PagedKVCache.alloc` when the pool cannot supply
    the requested pages even after evicting idle prefix pages."""


def prefix_page_keys(tokens: np.ndarray, page_size: int,
                     n_pages: Optional[int] = None) -> List[bytes]:
    """Chained content hash of each *full* page of ``tokens``: page i's
    key commits to tokens[0 : (i+1)*page_size], so a key matches only
    when the entire prefix through that page matches."""
    toks = np.asarray(tokens, np.int64)
    total = len(toks) // page_size
    n = total if n_pages is None else min(n_pages, total)
    keys, h = [], hashlib.blake2b(digest_size=16)
    for i in range(n):
        h.update(toks[i * page_size:(i + 1) * page_size].tobytes())
        keys.append(h.digest())
        h = hashlib.blake2b(keys[-1], digest_size=16)
    return keys


class PagedKVCache:
    """Host-side page allocator + prefix registry (no device state —
    the pooled buffers live in the engine's cache pytree).

    ``capacity`` pages are allocatable (physical pages 1..n_pages-1;
    page 0 is the reserved null page). Every allocated page carries a
    refcount; :meth:`release` frees at zero. Prefix registration adds
    one registry reference, so a registered page idles at refcount 1
    until a later prompt maps it (hit) or the allocator evicts it (LRU)
    to satisfy a new allocation.
    """

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 2:
            raise ValueError(f"n_pages must be >= 2 (page 0 is the null "
                             f"page), got {n_pages}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self._free: List[int] = list(range(n_pages - 1, 0, -1))
        self._rc = np.zeros((n_pages,), np.int64)
        self._prefix: "OrderedDict[bytes, int]" = OrderedDict()  # key->page
        self._key_of: Dict[int, bytes] = {}                      # page->key
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    # ------------------------------------------------------------- queries
    @property
    def capacity(self) -> int:
        return self.n_pages - 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def evictable_pages(self) -> int:
        """Registered pages held only by the registry (refcount 1)."""
        return sum(1 for p in self._prefix.values() if self._rc[p] == 1)

    @property
    def live_pages(self) -> int:
        return self.capacity - self.free_pages

    def refcount(self, page: int) -> int:
        return int(self._rc[page])

    def can_allocate(self, n: int) -> bool:
        return self.free_pages + self.evictable_pages >= n

    # ---------------------------------------------------------- alloc/free
    def alloc(self, n: int) -> List[int]:
        """Take ``n`` pages off the free list (evicting idle prefix
        pages LRU-first if needed); each comes back with refcount 1."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if not self.can_allocate(n):
            raise PagesExhausted(
                f"need {n} pages, have {self.free_pages} free + "
                f"{self.evictable_pages} evictable of {self.capacity}")
        while self.free_pages < n:
            self._evict_one()
        pages = [self._free.pop() for _ in range(n)]
        self._rc[pages] += 1
        return pages

    def retain(self, pages: Sequence[int]):
        for p in pages:
            if self._rc[p] < 1:
                raise PagesExhausted(f"retain of free page {p}")
            self._rc[p] += 1

    def release(self, pages: Sequence[int]):
        for p in pages:
            if p == NULL_PAGE:
                continue
            if self._rc[p] < 1:
                raise PagesExhausted(f"double release of page {p}")
            self._rc[p] -= 1
            if self._rc[p] == 0:
                self._free.append(int(p))

    def _evict_one(self):
        for key, page in self._prefix.items():        # insertion = LRU order
            if self._rc[page] == 1:
                del self._prefix[key]
                del self._key_of[page]
                self.release([page])
                self.evictions += 1
                return
        raise PagesExhausted("no evictable prefix pages")

    # ------------------------------------------------------------- prefixes
    def lookup(self, tokens: np.ndarray,
               max_pages: Optional[int] = None) -> List[int]:
        """Longest-prefix walk: the registered pages whose chained keys
        match ``tokens``'s leading full pages. Matched pages are
        retained for the caller and touched to MRU."""
        keys = prefix_page_keys(tokens, self.page_size, max_pages)
        pages: List[int] = []
        for key in keys:
            page = self._prefix.get(key)
            if page is None:
                break
            pages.append(page)
        self.retain(pages)
        for key in keys[: len(pages)]:
            self._prefix.move_to_end(key)
        if pages:
            self.hits += 1
        else:
            self.misses += 1
        return pages

    def register(self, tokens: np.ndarray, pages: Sequence[int]):
        """Publish ``pages`` (the caller's content-final pages holding
        ``tokens``'s leading full pages) for future sharing. Each newly
        registered page gains the registry's reference; keys already
        present keep their existing page (the caller's copy stays
        private and frees normally)."""
        keys = prefix_page_keys(tokens, self.page_size, len(pages))
        for key, page in zip(keys, pages):
            if key in self._prefix:
                continue
            self._prefix[key] = int(page)
            self._key_of[int(page)] = key
            self.retain([page])

    def drop_prefixes(self):
        """Release every registry reference (tests assert refcounts all
        reach zero after this + request release — the no-leak check)."""
        pages = list(self._prefix.values())
        self._prefix.clear()
        self._key_of.clear()
        self.release(pages)


class PagedServeEngine(ServeEngine):
    """Continuous batching over a paged KV pool.

    ``page_budget`` is the pool size in pages (including the reserved
    null page); the default matches the fixed-slot engine's KV bytes
    exactly (``n_slots * ceil(W / page_size)`` allocatable pages), so
    benchmarks compare the two engines at equal HBM. ``n_slots`` still
    bounds the decode batch width, but admission waits on *pages*: with
    short contexts in flight, many more than ``page_budget / ceil(W/ps)``
    requests fit.

    ``prefix_cache`` enables prompt-prefix page sharing. It is only
    sound for pad-safe attention families without a sliding window
    (recurrent state is not paged; a windowed cache wraps, so its rows
    are position-, not content-, addressed) and degrades to off
    elsewhere.
    """

    def __init__(self, params, cfg, rt, n_slots: int = 4,
                 max_len: int = 512, page_size: int = 16,
                 page_budget: Optional[int] = None,
                 prefix_cache: bool = True, **kw):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.page_size = int(page_size)
        window = max_len
        if cfg.sliding_window and not cfg.mixed_attention:
            window = min(cfg.sliding_window, max_len)
        self._npp = page_count(window, page_size)   # page-table width
        if page_budget is None:
            # equal-HBM default: the fixed-slot engine's KV *bytes* at
            # the activation dtype, converted into pages at the cache's
            # storage dtype. With kv_dtype == rt.dtype this is exactly
            # the seed's n_slots * ceil(W/ps); under kv_dtype='int8' a
            # page costs D*1 + 2 bytes per (token, kv-head) — payload
            # plus the bf16 scale side-band — so the same byte budget
            # buys ~2D/(D+2) times the pages.
            base = n_slots * self._npp
            if rt.kv_dtype and rt.kv_dtype != rt.dtype:
                per_tok_base = cfg.head_dim * jnp.dtype(rt.dtype).itemsize
                per_tok_kv = (cfg.head_dim * jnp.dtype(rt.kv_dtype).itemsize
                              + (2 if rt.kv_dtype == "int8" else 0))
                base = base * per_tok_base // per_tok_kv
            page_budget = base + 1
        self.n_pages = int(page_budget)
        self.pages = PagedKVCache(self.n_pages, self.page_size)
        # the window layers' group of a model of mixed kinds: pages per
        # slot's ring (``paged_cache_spec``'s ring pool), 0 without one
        self._npp_ring = page_count(min(cfg.sliding_window, max_len),
                                    page_size) if cfg.mixed_attention else 0
        self._prefix_on = bool(prefix_cache) \
            and cfg.family in PAD_SAFE_FAMILIES \
            and not cfg.sliding_window
        # (shared, private) physical pages held per slot
        self._slot_pages: List[Tuple[List[int], List[int]]] = \
            [([], []) for _ in range(n_slots)]
        super().__init__(params, cfg, rt, n_slots=n_slots,
                         max_len=max_len, **kw)
        if self._npp_ring:
            self.cache["ptw"] = self.cache["ptw"].at[:].set(
                jnp.asarray(self._ring_table()))

        ps = self.page_size

        if "ks" in self.cache:
            def _scatter_fn(kp, vp, ksp, vsp, k, v, ks, vs, page_ids):
                return write_prefill_pages_quant(
                    kp, vp, ksp, vsp, k, v, ks, vs, page_ids,
                    page_size=ps)
        else:
            def _scatter_fn(kp, vp, k, v, page_ids):
                return write_prefill_pages(kp, vp, k, v, page_ids,
                                           page_size=ps)

        # compiles once per (prefill bucket, admit width) — the same
        # bound the prefill itself already pays
        self._scatter = jax.jit(_scatter_fn)

    # ------------------------------------------------------------ cache hooks
    def _init_cache(self):
        return init_paged_cache(self.cfg, self.n_slots, self.n_pages,
                                self.page_size, self.max_len,
                                self.rt.dtype, kv_dtype=self.rt.kv_dtype)

    def _decode(self, params, cache, tokens):
        return decode_step_paged(params, self.cfg, cache, tokens, self.rt,
                                 page_size=self.page_size,
                                 window=self.scheduler.window)

    def _cache_axes(self) -> Dict[str, tuple]:
        return paged_cache_axes(self.cfg, self.rt.kv_dtype)

    @property
    def _has_kv(self) -> bool:
        return "kp" in self.cache

    # ---------------------------------------------------------------- budget
    def _admit_need(self, req: Request,
                    plan: Optional[AdmissionPlan] = None) -> int:
        """Worst-case pages an admission allocates up front: the pages
        the request can ever address (window-capped) or, if larger, the
        prefill bucket's scatter span (the tail pages of which are freed
        right after the scatter)."""
        if not self._has_kv:
            return 0
        need = self.scheduler.pages_for(len(req.prompt),
                                        req.max_new_tokens, self.page_size)
        if plan is None:
            plan = self.scheduler.plan(len(req.prompt))
        scatter = page_count(min(plan.prefill_len, self.scheduler.window),
                             self.page_size)
        return max(need, scatter)

    def submit(self, req: Request):
        """Page-budget admission control on top of the base cache-bounds
        contract: a request whose worst-case pages exceed the pool can
        never be admitted — reject/truncate/error it *now* rather than
        deadlocking the queue head."""
        S = int(len(req.prompt))
        if S >= 1 and self._has_kv:
            ps, cap = self.page_size, self.pages.capacity
            need = self.scheduler.pages_for(S, req.max_new_tokens, ps)
            scatter = page_count(
                min(self.scheduler.plan(S).prefill_len,
                    self.scheduler.window), ps)
            if max(need, scatter) > cap:
                why = (f"needs {max(need, scatter)} pages of page_size="
                       f"{ps} > pool capacity {cap}")
                if self.overflow == "error":
                    raise ValueError(f"request rid={req.rid} over page "
                                     f"budget: {why}")
                budget = cap * ps - S
                if (self.overflow == "truncate" and scatter <= cap
                        and budget >= 1):
                    log.warning("rid=%d truncated: %s -> max_new_tokens=%d",
                                req.rid, why, budget)
                    req.max_new_tokens = budget
                    req.truncated = True
                else:
                    self._reject(req, why)
                    return
        super().submit(req)

    # ---------------------------------------------------------------- admit
    def _head_fits(self) -> bool:
        # head of line waits for pages
        return self.pages.can_allocate(self._admit_need(self.queue[0]))

    def _next_group(self, n_free: int):
        """Same-plan grouping as the base engine, additionally gated on
        the *cumulative* page budget of the group."""
        width = self.scheduler.admit_width
        req0 = self.queue.pop(0)
        plan = self.scheduler.plan(len(req0.prompt))
        group = [req0]
        pages_needed = self._admit_need(req0, plan)
        while len(group) < min(width, n_free) and self.queue:
            nxt = self.queue[0]
            if self.scheduler.plan(len(nxt.prompt)) != plan:
                break
            need = self._admit_need(nxt, plan)
            if not self.pages.can_allocate(pages_needed + need):
                break
            pages_needed += need
            group.append(self.queue.pop(0))
        return group, plan

    def _admit_group(self, group: List[Request], plan: AdmissionPlan,
                     slots: List[int]):
        if not self._has_kv:
            # pure-SSM: state cache, nothing pages — splice exactly the
            # leaves prefill produced (the page table rides untouched)
            single, out = self._prefill_group(group, plan)
            with TraceAnnotation("serve.splice"):
                names = [n for n in self.cache if n in single]
                sub = _splice({n: self.cache[n] for n in names},
                              {n: single[n] for n in names}, slots,
                              rows=range(len(group)), axes=PAGED_CACHE_AXES)
                self.cache = dict(self.cache, **sub)
            for j, (req, slot) in enumerate(zip(group, slots)):
                self._finish_admit(req, slot, plan, out[j])
            return
        cold: List[Tuple[Request, int]] = []
        for req, slot in zip(group, slots):
            shared: List[int] = []
            if self._prefix_on:
                # leave at least one prompt token to decode-feed: the
                # engine needs a last_token to prime the step with
                max_shared = (len(req.prompt) - 1) // self.page_size
                if max_shared >= 1:
                    shared = self.pages.lookup(req.prompt, max_shared)
            if shared:
                self._admit_prefix_hit(req, slot, shared)
            else:
                cold.append((req, slot))
        if cold:
            self._admit_cold(cold, plan)

    def _admit_prefix_hit(self, req: Request, slot: int,
                          shared: List[int]):
        """Admission that skips prefill: the shared pages already hold
        the prefix KV; the unshared prompt tail rides the decode step as
        forced tokens (the proven chunked-prefill machinery)."""
        ps = self.page_size
        shared_len = len(shared) * ps
        need = self.scheduler.pages_for(len(req.prompt),
                                        req.max_new_tokens, ps)
        private = self.pages.alloc(need - len(shared))
        self._slot_pages[slot] = (shared, private)
        with TraceAnnotation("serve.splice"):
            self._set_page_table([slot], [shared + private])
            self.cache["pos"] = self.cache["pos"].at[slot].set(shared_len)
        self.stats.prefix_hits += 1
        self.stats.prefix_hit_tokens += shared_len
        plan = AdmissionPlan("chunk", shared_len)
        self._finish_admit(req, slot, plan, None, start_pos=shared_len)

    def _admit_cold(self, pairs: List[Tuple[Request, int]],
                    plan: AdmissionPlan):
        ps, W = self.page_size, self.scheduler.window
        P = plan.prefill_len
        group = [req for req, _ in pairs]
        slots = [slot for _, slot in pairs]
        single, out = self._prefill_group(group, plan)

        with TraceAnnotation("serve.scatter"):
            n_scatter = page_count(min(P, W), ps)
            width = max(self.scheduler.admit_width, len(pairs))
            page_ids = np.zeros((width, n_scatter), np.int32)  # pads->null
            held: List[List[int]] = []
            for j, (req, _) in enumerate(pairs):
                pages = self.pages.alloc(self._admit_need(req, plan))
                page_ids[j] = pages[:n_scatter]
                held.append(pages)
            with self._ctx():
                if "ks" in self.cache:
                    # int8 KV: the prefill cache leaves are already
                    # quantized — scatter payload + scale side-bands
                    kp, vp, ksp, vsp = self._scatter(
                        self.cache["kp"], self.cache["vp"],
                        self.cache["ks"], self.cache["vs"],
                        single["k"], single["v"],
                        single["ks"], single["vs"], jnp.asarray(page_ids))
                    self.cache = dict(self.cache, kp=kp, vp=vp,
                                      ks=ksp, vs=vsp)
                else:
                    kp, vp = self._scatter(
                        self.cache["kp"], self.cache["vp"],
                        single["k"], single["v"], jnp.asarray(page_ids))
                    self.cache = dict(self.cache, kp=kp, vp=vp)
            if self._npp_ring:
                self._scatter_ring(slots, single, width)

        rows = []
        for j, (req, slot) in enumerate(pairs):
            pages, need = held[j], self.scheduler.pages_for(
                len(req.prompt), req.max_new_tokens, ps)
            if len(pages) > need:       # scatter-only tail: pad rows the
                self.pages.release(pages[need:])   # mask hides forever
                pages = pages[:need]
            self._slot_pages[slot] = ([], pages)
            rows.append(pages)
            if self._prefix_on and plan.mode == "pad":
                # pad mode prefilled the whole prompt: its full pages
                # are content-final -> publish them for sharing
                n_full = min(len(req.prompt) // ps, len(pages))
                self.pages.register(req.prompt, pages[:n_full])
        with TraceAnnotation("serve.splice"):
            # per-slot contiguous leaves (pos + recurrent state) splice
            # as in the fixed engine — only the KV rows page
            names = [n for n in ("pos", "conv", "ssm") if n in self.cache]
            sub = _splice({n: self.cache[n] for n in names},
                          {n: single[n] for n in names}, slots,
                          rows=range(len(pairs)), axes=PAGED_CACHE_AXES)
            self.cache = dict(self.cache, **sub)
            self._set_page_table(slots, rows)
        for j, (req, slot) in enumerate(pairs):
            self._finish_admit(req, slot, plan, out[j])

    def _ring_table(self) -> np.ndarray:
        """The window layers' page table: slot ``s``'s ring is pages
        ``s * npw + 1 .. (s + 1) * npw`` (page 0 is the null page)."""
        npw = self._npp_ring
        return 1 + np.arange(self.n_slots * npw, dtype=np.int32).reshape(
            self.n_slots, npw)

    def _scatter_ring(self, slots: List[int], single, width: int):
        """Scatter the window layers' prefill rings into the admitted
        slots' own rings (the group's pad rows into the null page)."""
        page_ids = np.zeros((width, self._npp_ring), np.int32)
        page_ids[:len(slots)] = self._ring_table()[slots]
        with self._ctx():
            kp, vp = self._scatter(self.cache["kpw"], self.cache["vpw"],
                                   single["kw"], single["vw"],
                                   jnp.asarray(page_ids))
        self.cache = dict(self.cache, kpw=kp, vpw=vp)

    def _set_page_table(self, slots: List[int], rows):
        """Write ``rows`` (ragged lists of physical pages) into the
        device page table, null-padded to the table width."""
        table = np.zeros((len(slots), self._npp), np.int32)
        for i, row in enumerate(rows):
            table[i, : len(row)] = row
        self.cache["pt"] = self.cache["pt"].at[
            jnp.asarray(slots, jnp.int32)].set(jnp.asarray(table))

    # ---------------------------------------------------------------- retire
    def _release_slot(self, slot: int):
        shared, private = self._slot_pages[slot]
        self.pages.release(shared)
        self.pages.release(private)
        self._slot_pages[slot] = ([], [])
        # repoint the stale table row at the null page so the retired
        # slot's (masked) decode writes can never touch rebound pages
        self._set_page_table([slot], [[]])

    # ---------------------------------------------------------------- stats
    def _allocated_tokens(self, active: List[int]) -> int:
        if not self._has_kv:
            return super()._allocated_tokens(active)
        held = sum(len(sh) + len(pv)
                   for sh, pv in (self._slot_pages[s] for s in active))
        return held * self.page_size

    def _count_kv(self, active: List[int]):
        super()._count_kv(active)
        self.stats.ring_alloc_token_steps += \
            len(active) * self._npp_ring * self.page_size

    def _fetch(self, out) -> np.ndarray:
        """The step's output, and an expert model's step counters in
        the same ``device_get``."""
        counts = self.cache.get("expert_counts")
        if counts is None:
            return super()._fetch(out)
        out_np, counts = jax.device_get((out, counts))
        self.stats.expert_rows += int(counts[0])
        self.stats.experts_hit += int(counts[1])
        return np.asarray(out_np)

    @property
    def prefix_hit_rate(self) -> float:
        total = self.pages.hits + self.pages.misses
        return self.pages.hits / total if total else 0.0
