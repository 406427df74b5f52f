"""Serving engine: scheduled prefill + batched decode with continuous
batching.

The engine holds one jointly-batched cache of ``n_slots`` sequences;
each slot has its own position counter (``cache['pos']`` is per-
sequence). Finished slots are refilled from the request queue by
prefilling the new prompt at a :class:`~repro.serve.scheduler.Scheduler`
-chosen bucketed shape and splicing its cache into the slot — insertion
is a pure pytree update keyed by the cache spec's *declared* batch axes
(``models.model.CACHE_AXES``), so the decode step stays one compiled
function and splice can never guess an axis from a shape collision.

Three seed bugs are fixed here, each with a regression test:

* **KV overflow** — ``decode_step`` writes at ``pos % W`` unbounded, so
  a request with ``prompt_len + max_new_tokens > max_len`` used to wrap
  the cache and corrupt live context. The budget is now enforced at
  :meth:`submit` (``models.model.cache_token_budget``): reject loudly,
  truncate loudly, or raise — never clamp silently. ``run`` raises when
  requests remain unserved instead of dropping them from ``finished``.
* **splice-by-shape** — ``_splice`` matched ``big.shape[0] ==
  small.shape[0] and small.shape[1] == 1``, which corrupts the cache as
  soon as ``n_slots`` collides with ``n_layers``/small dims (e.g. a
  width-``n_slots`` batched admission). It now indexes the declared
  batch axis and splices any number of slots at once.
* **dead ``greedy=False``** — the non-greedy admission branch emitted
  a hard-coded token 0. Admission and decode both route through one
  seeded :class:`~repro.serve.sampling.Sampler` (greedy / temperature /
  top-k), with EOS and per-request stop-token termination.

Each phase of a step runs under a ``jax.profiler.TraceAnnotation``
named ``serve.<phase>`` (admit, prefill, prefill_fetch, scatter, splice,
decode, wait, fetch, sample, release). With the profiler on they land
on the host plane, on the clock the device planes share, so a device
trace says which phase the host was in while the chip sat idle; with it
off each costs one context-manager entry and builds no metadata.
``serve.wait`` (the step program still running) and ``serve.fetch``
(the device-to-host copy of its output: one token id a slot under a
greedy sampler, whose argmax ends the program, else the logits) split
what was one ``np.asarray``.
"""
from __future__ import annotations

import logging
import time
from collections import Counter
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.models import (cache_token_budget, decode_step, init_cache,
                          prefill)
from repro.models.model import CACHE_AXES, ModelRuntime
from repro.serve.sampling import Sampler
from repro.serve.scheduler import AdmissionPlan, Scheduler

log = logging.getLogger("repro.serve")


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # (S,) int32
    max_new_tokens: int = 16
    stop_tokens: Tuple[int, ...] = ()   # per-request terminators (w/ eos_id)
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False
    finish_reason: Optional[str] = None  # length | stop | rejected: <why>
    truncated: bool = False              # overflow='truncate' shrank budget
    # time.perf_counter() when queued by submit and when admitted (taken
    # off the queue for prefill or a prefix hit)
    t_queued: float = float("nan")
    t_admitted: float = float("nan")


@dataclass
class EngineStats:
    """Live counters the benchmark and the compile-count tests read."""

    prefill_traces: Counter = field(default_factory=Counter)  # (len, width)
    prefills: int = 0          # prefill *calls* (>= admissions / width)
    prefill_tokens: int = 0    # tokens pushed through prefill (width * P)
    steps: int = 0             # decode steps executed
    occupancy_sum: int = 0     # sum of active slots over decode steps
    max_active: int = 0        # peak concurrent in-flight requests
    tokens_out: int = 0        # sampled (served) tokens
    forced_tokens: int = 0     # chunked-prefill prompt tokens decode-fed
    rejected: int = 0
    # KV-cache accounting (per decode step): live context tokens of the
    # active slots vs the cache tokens their requests hold allocated —
    # the paged-vs-fixed utilization headline in serve_throughput.
    live_token_steps: int = 0
    alloc_token_steps: int = 0
    # the window layers' ring group of a model of mixed kinds (paged)
    ring_alloc_token_steps: int = 0
    # expert models (paged engine): (token, expert) rows routed to held
    # experts, and held experts with at least one row, summed over
    # layers and decode steps
    expert_rows: int = 0
    experts_hit: int = 0
    # prefix caching (paged engine only)
    prefix_hits: int = 0         # admissions that reused >= 1 prefix page
    prefix_hit_tokens: int = 0   # prompt tokens served from shared pages

    @property
    def prefill_compiles(self) -> int:
        return sum(self.prefill_traces.values())

    def occupancy(self, n_slots: int) -> float:
        if not self.steps:
            return 0.0
        return self.occupancy_sum / (self.steps * n_slots)

    @property
    def kv_utilization(self) -> float:
        """Live context tokens / allocated cache tokens, averaged over
        decode steps. The fixed-slot engine allocates the full window
        per active request; the paged engine only the pages held."""
        if not self.alloc_token_steps:
            return 0.0
        return self.live_token_steps / self.alloc_token_steps


def make_serve_step(cfg: ModelConfig, rt: ModelRuntime) -> Callable:
    """jit-compiled one-token decode over the whole slot batch."""

    def step(params, cache, tokens):
        return decode_step(params, cfg, cache, tokens, rt)

    return jax.jit(step)


def _splice(cache: Dict[str, jax.Array], single: Dict[str, jax.Array],
            slots, rows: Optional[Sequence[int]] = None,
            axes: Optional[Dict[str, tuple]] = None) -> Dict[str, Any]:
    """Insert prefilled cache rows into batch ``slots``.

    The batch axis of every leaf comes from the cache spec's declared
    axis names (``models.model.CACHE_AXES`` — ``"pos": ("batch",)``,
    ``"k": (None, "batch", ...)``, ...), never from shape heuristics:
    the seed version guessed from ``big.shape[0] == small.shape[0]``,
    which silently corrupts whenever ``n_slots`` collides with
    ``n_layers`` or a non-unit small batch (see tests). ``slots`` may be
    one int or a sequence; ``rows`` selects which rows of ``single`` to
    take (default: the first ``len(slots)``).
    """
    axes = CACHE_AXES if axes is None else axes
    if isinstance(slots, (int, np.integer)):
        slots = [int(slots)]
    slots = list(slots)
    rows = list(rows) if rows is not None else list(range(len(slots)))
    if len(rows) != len(slots):
        raise ValueError(f"rows/slots length mismatch: {rows} vs {slots}")
    out = dict(cache)
    sl = jnp.asarray(slots, jnp.int32)
    rw = jnp.asarray(rows, jnp.int32)
    for name, big in cache.items():
        leaf_axes = axes.get(name)
        if leaf_axes is None or "batch" not in leaf_axes:
            raise KeyError(
                f"cache leaf {name!r} has no declared batch axis "
                f"(CACHE_AXES) — refusing to splice by shape guessing")
        b = leaf_axes.index("batch")
        small = single[name]
        pre = (slice(None),) * b
        out[name] = big.at[pre + (sl,)].set(
            small[pre + (rw,)].astype(big.dtype))
    return out


class ServeEngine:
    """Continuous-batching engine: scheduled admission, budget-checked
    caches, pluggable sampling, measurable stats.

    ``overflow`` governs requests whose ``prompt_len + max_new_tokens``
    exceeds the ``max_len`` cache budget (the cache-bounds contract,
    :func:`repro.models.model.cache_token_budget`):

    * ``'reject'`` (default) — the request lands in :attr:`rejected`
      with ``finish_reason='rejected: ...'`` and a warning log; it is
      never silently dropped.
    * ``'truncate'`` — ``max_new_tokens`` is shrunk to fit (loudly,
      ``truncated=True``); a prompt that cannot emit even one token is
      still rejected.
    * ``'error'`` — :meth:`submit` raises ``ValueError``.

    ``greedy=False`` maps onto a seeded temperature sampler for
    backwards compatibility; pass ``sampler=`` for full control.
    """

    def __init__(self, params, cfg: ModelConfig, rt: ModelRuntime,
                 n_slots: int = 4, max_len: int = 512,
                 greedy: bool = True,
                 sampler: Optional[Sampler] = None,
                 scheduler: Optional[Scheduler] = None,
                 overflow: str = "reject",
                 eos_id: Optional[int] = None):
        if cfg.is_encoder_only:
            raise ValueError(
                f"{cfg.name} is encoder-only: no autoregressive decode")
        if overflow not in ("reject", "truncate", "error"):
            raise ValueError(f"unknown overflow policy {overflow!r}")
        self.params = params
        self.cfg = cfg
        self.rt = rt
        self.n_slots = n_slots
        self.max_len = max_len
        self.sampler = sampler if sampler is not None else (
            Sampler() if greedy else Sampler(kind="temperature"))
        self.scheduler = scheduler if scheduler is not None else (
            Scheduler(cfg=cfg, max_len=max_len))
        if self.scheduler.max_len != max_len:
            raise ValueError(
                f"scheduler.max_len={self.scheduler.max_len} != engine "
                f"max_len={max_len}")
        self.overflow = overflow
        self.eos_id = eos_id
        self.cache = self._place_cache(self._init_cache())
        self.slots: List[Optional[Request]] = [None] * n_slots
        self.last_tokens = np.zeros((n_slots,), np.int32)
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self.rejected: List[Request] = []
        self.stats = EngineStats()
        self._tails: List[List[int]] = [[] for _ in range(n_slots)]
        self._rngs: List[Optional[np.random.Generator]] = [None] * n_slots
        # host-side per-slot context length (tokens in cache), for the
        # KV-utilization stats — no device sync on the hot path
        self._host_pos = np.zeros((n_slots,), np.int64)

        stats = self.stats
        # a greedy engine's programs end in each row's argmax: a step
        # copies one id a slot to the host, not a row of logits
        pick = ((lambda lg: jnp.argmax(lg, axis=-1).astype(jnp.int32))
                if self.sampler.on_device else (lambda lg: lg))

        def _step_fn(p, cache, tokens):
            cache, logits = self._decode(p, cache, tokens)
            return cache, pick(logits)

        def _prefill_fn(p, toks, lengths):
            stats.prefill_traces[(toks.shape[1], toks.shape[0])] += 1
            cache, logits = prefill(p, cfg, {"tokens": toks}, max_len, rt,
                                    lengths=lengths)
            return cache, pick(logits)

        # a model of mixed attention kinds rewrites the cache in place:
        # its pools and weights fill the chip, no second pool fits
        donate = (1,) if cfg.mixed_attention else ()
        self._step = jax.jit(_step_fn, donate_argnums=donate)
        self._prefill = jax.jit(_prefill_fn)

    # -------------------------------------------------------- placement hooks
    def _place_cache(self, cache):
        """Sharded subclasses device_put the cache onto the mesh."""
        return cache

    def _ctx(self):
        """Ambient context every jitted call runs under (mesh + recipe
        for the sharded engine; nothing here)."""
        return nullcontext()

    # ------------------------------------------------------------ cache hooks
    def _init_cache(self):
        """Build the (device) decode cache; the paged engine overrides
        this with the pooled page buffers."""
        return init_cache(self.cfg, self.n_slots, self.max_len,
                          self.rt.dtype, kv_dtype=self.rt.kv_dtype)

    def _decode(self, params, cache, tokens):
        """The decode step the jitted engine step traces."""
        return decode_step(params, self.cfg, cache, tokens, self.rt)

    def _cache_axes(self) -> Dict[str, tuple]:
        """Declared logical axes of every cache leaf (splice + sharding)."""
        return CACHE_AXES

    def _release_slot(self, slot: int):
        """Called when the request in ``slot`` retires (paged engine
        frees its pages here)."""

    def _head_fits(self) -> bool:
        """Whether the head of the queue can be admitted now (the paged
        engine waits on its page budget)."""
        return True

    def kv_cache_bytes(self) -> int:
        """Device bytes held by the KV cache (contiguous or paged),
        including the quantization scale side-bands under
        ``kv_dtype='int8'``."""
        return sum(int(self.cache[k].size
                       * jnp.dtype(self.cache[k].dtype).itemsize)
                   for k in ("k", "v", "kw", "vw", "kp", "vp", "kpw", "vpw",
                             "ks", "vs")
                   if k in self.cache)

    def _live_tokens(self, active: List[int]) -> int:
        W = self.scheduler.window
        return int(sum(min(int(self._host_pos[s]), W) for s in active))

    def _count_kv(self, active: List[int]):
        """Per decode step: the active slots' live and allocated cache
        tokens."""
        self.stats.live_token_steps += self._live_tokens(active)
        self.stats.alloc_token_steps += self._allocated_tokens(active)

    def _fetch(self, out) -> np.ndarray:
        """The step's output on the host: token ids, or logits where
        the host samples."""
        return np.asarray(out)

    def _allocated_tokens(self, active: List[int]) -> int:
        """Cache tokens the active requests hold allocated. The fixed
        engine reserves one full window per slot, live or not — that is
        exactly the dead-HBM problem the paged engine removes."""
        return self.n_slots * self.scheduler.window

    # ---------------------------------------------------------------- admin
    def submit(self, req: Request):
        """Admission control: enforce the cache-bounds budget *now*,
        not after the cache has been corrupted."""
        S = int(len(req.prompt))
        budget = cache_token_budget(self.cfg, self.max_len, S)
        if S < 1:
            self._reject(req, "empty prompt")
            return
        if req.max_new_tokens <= budget:
            self._enqueue(req)
            return
        why = (f"prompt_len={S} + max_new_tokens={req.max_new_tokens} "
               f"> max_len={self.max_len}")
        if self.overflow == "error":
            raise ValueError(f"request rid={req.rid} over cache budget: "
                             f"{why}")
        if self.overflow == "truncate" and budget >= 1:
            log.warning("rid=%d truncated: %s -> max_new_tokens=%d",
                        req.rid, why, budget)
            req.max_new_tokens = budget
            req.truncated = True
            self._enqueue(req)
            return
        self._reject(req, why)

    def _enqueue(self, req: Request):
        req.t_queued = time.perf_counter()
        self.queue.append(req)

    def _reject(self, req: Request, why: str):
        log.warning("rid=%d rejected: %s", req.rid, why)
        req.finish_reason = f"rejected: {why}"
        self.rejected.append(req)
        self.stats.rejected += 1

    # ---------------------------------------------------------------- admit
    def _admit(self):
        free = [i for i, r in enumerate(self.slots) if r is None]
        while free and self.queue and self._head_fits():
            group, plan = self._next_group(len(free))
            now = time.perf_counter()
            for req in group:
                req.t_admitted = now
            slots = free[: len(group)]
            free = free[len(group):]
            self._admit_group(group, plan, slots)

    def _next_group(self, n_free: int) -> Tuple[List[Request], AdmissionPlan]:
        """Pop up to ``admit_width`` head-of-queue requests sharing one
        admission plan (one prefill shape)."""
        width = self.scheduler.admit_width
        req0 = self.queue.pop(0)
        plan = self.scheduler.plan(len(req0.prompt))
        group = [req0]
        while (len(group) < min(width, n_free) and self.queue
               and self.scheduler.plan(len(self.queue[0].prompt)) == plan):
            group.append(self.queue.pop(0))
        return group, plan

    def _prefill_group(self, group: List[Request], plan: AdmissionPlan):
        """Run the (bucketed) batched prefill for one admission group;
        returns the single-call cache + per-row output (a token id, or
        logits where the host samples)."""
        width = max(self.scheduler.admit_width, len(group))
        P = plan.prefill_len
        # TraceMe metadata splits at commas: ids are joined by spaces
        meta = ({"rids": " ".join(str(r.rid) for r in group), "bucket": P}
                if TraceAnnotation.is_enabled() else {})
        with TraceAnnotation("serve.prefill", **meta):
            toks = np.zeros((width, P), np.int32)
            lengths = np.ones((width,), np.int32)
            for j, req in enumerate(group):
                if plan.mode == "pad":
                    toks[j, : len(req.prompt)] = req.prompt
                    lengths[j] = len(req.prompt)
                else:                            # chunk: exact prefix
                    toks[j] = req.prompt[:P]
                    lengths[j] = P
            with self._ctx():
                single, out = self._prefill(
                    self.params, jnp.asarray(toks), jnp.asarray(lengths))
        self.stats.prefills += 1
        self.stats.prefill_tokens += width * P
        with TraceAnnotation("serve.prefill_fetch"):
            return single, np.asarray(out)

    def _admit_group(self, group: List[Request], plan: AdmissionPlan,
                     slots: List[int]):
        single, out = self._prefill_group(group, plan)
        with TraceAnnotation("serve.splice"):
            self.cache = _splice(self.cache, single, slots,
                                 rows=range(len(group)),
                                 axes=self._cache_axes())
        for j, (req, slot) in enumerate(zip(group, slots)):
            self._finish_admit(req, slot, plan, out[j])

    def _finish_admit(self, req: Request, slot: int, plan: AdmissionPlan,
                      row: Optional[np.ndarray],
                      start_pos: Optional[int] = None):
        """Per-slot bookkeeping shared by every admission path: seed the
        sampler stream, arm the chunked-prefill tail (or emit the first
        token), record the host-side context length."""
        P = plan.prefill_len
        self.slots[slot] = req
        self._rngs[slot] = self.sampler.stream(req.rid)
        if start_pos is None:
            start_pos = len(req.prompt) if plan.mode == "pad" else P
        self._host_pos[slot] = start_pos
        if start_pos < len(req.prompt):
            # chunked prefill: the rest of the prompt rides the
            # decode step as forced inputs; prefill output unused.
            self.last_tokens[slot] = int(req.prompt[start_pos])
            self._tails[slot] = [int(t)
                                 for t in req.prompt[start_pos + 1:]]
        else:
            self._tails[slot] = []
            self._emit(slot, row)

    # ---------------------------------------------------------------- step
    def _emit(self, slot: int, row: np.ndarray):
        """Take ``slot``'s token from its row of a program's output (the
        id, or logits to sample); retire the request on budget
        exhaustion or a stop token."""
        req = self.slots[slot]
        tok = self.sampler.sample(row, self._rngs[slot])
        req.out_tokens.append(tok)
        self.last_tokens[slot] = tok
        self.stats.tokens_out += 1
        stop = set(req.stop_tokens)
        if self.eos_id is not None:
            stop.add(self.eos_id)
        if tok in stop:
            req.done, req.finish_reason = True, "stop"
        elif len(req.out_tokens) >= req.max_new_tokens:
            req.done, req.finish_reason = True, "length"
        if req.done:
            self.finished.append(req)
            self.slots[slot] = None
            self._tails[slot] = []
            self._rngs[slot] = None
            with TraceAnnotation("serve.release"):
                self._release_slot(slot)

    def step(self) -> int:
        """One engine iteration: admit new requests, decode one token
        for every active slot. Returns the number of active slots."""
        with TraceAnnotation("serve.admit"):
            self._admit()
        active = [i for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return 0
        self._count_kv(active)
        self.stats.max_active = max(self.stats.max_active, len(active))
        meta = ({"slots": " ".join(map(str, active))}
                if TraceAnnotation.is_enabled() else {})
        with TraceAnnotation("serve.decode", **meta), self._ctx():
            self.cache, out = self._step(
                self.params, self.cache, jnp.asarray(self.last_tokens))
        with TraceAnnotation("serve.wait"):
            jax.block_until_ready(out)
        with TraceAnnotation("serve.fetch"):
            out_np = self._fetch(out)
        with TraceAnnotation("serve.sample"):
            for slot in active:
                self._host_pos[slot] += 1
                if self._tails[slot]:
                    # chunked prefill tail: force the next prompt token
                    self.last_tokens[slot] = self._tails[slot].pop(0)
                    self.stats.forced_tokens += 1
                else:
                    self._emit(slot, out_np[slot])
        self.stats.steps += 1
        self.stats.occupancy_sum += len(active)
        return len(active)

    def run(self, max_iters: int = 1000) -> List[Request]:
        """Drive until every submitted request finished. Raises if
        ``max_iters`` elapses with requests still queued or in flight —
        never silently drops work (rejected requests are surfaced via
        :attr:`rejected`, not lost)."""
        it = 0
        while (self.queue or any(s is not None for s in self.slots)) \
                and it < max_iters:
            self.step()
            it += 1
        leftover = [r.rid for r in self.queue] + \
            [r.rid for r in self.slots if r is not None]
        if leftover:
            raise RuntimeError(
                f"run(max_iters={max_iters}) exhausted with requests "
                f"never served: rids={leftover} — raise max_iters or "
                f"check admission")
        return self.finished
