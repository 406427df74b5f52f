"""Paged-KV serving coverage: the PagedKVCache allocator (free list,
refcounts, LRU prefix eviction, exhaustion), chained prefix hashing,
page-budget admission (incl. the sliding-window ``ceil(W/ps)`` cap and
the overflow policies), paged-vs-contiguous token parity across every
cache family, and prefix-cache correctness (warm == cold tokens, zero
refcounts after release, no leak across ``run()``)."""
import numpy as np
import pytest

import jax

from repro.configs import ARCHS, smoke_config
from repro.models import init_params
from repro.models.model import ModelRuntime, page_count
from repro.serve import (PagedKVCache, PagedServeEngine, PagesExhausted,
                         Request, Scheduler, ServeEngine,
                         prefix_page_keys)

CFG = smoke_config(ARCHS["minicpm-2b"])
RT = ModelRuntime(dtype="float32", remat="none", attn_chunk=16,
                  moe_dropless=True)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


# ------------------------------------------------------ allocator basics
def test_allocator_validates_construction():
    with pytest.raises(ValueError, match="n_pages"):
        PagedKVCache(1, 8)
    with pytest.raises(ValueError, match="page_size"):
        PagedKVCache(4, 0)


def test_alloc_release_refcounts():
    pool = PagedKVCache(5, 8)           # pages 1..4 allocatable
    assert pool.capacity == 4 and pool.free_pages == 4
    pages = pool.alloc(3)
    assert len(set(pages)) == 3 and 0 not in pages
    assert pool.free_pages == 1 and pool.live_pages == 3
    assert all(pool.refcount(p) == 1 for p in pages)
    pool.retain(pages[:1])
    pool.release(pages)                 # pages[0] survives at rc 1
    assert pool.free_pages == 3 and pool.refcount(pages[0]) == 1
    pool.release(pages[:1])
    assert pool.free_pages == 4 and pool.live_pages == 0


def test_alloc_exhaustion_and_double_release():
    pool = PagedKVCache(4, 8)
    pages = pool.alloc(3)
    assert not pool.can_allocate(1)
    with pytest.raises(PagesExhausted):
        pool.alloc(1)
    pool.release(pages)
    with pytest.raises(PagesExhausted):
        pool.release(pages[:1])         # double release is loud
    with pytest.raises(PagesExhausted):
        pool.retain([pages[0]])         # retain of a free page too


def test_release_ignores_null_page():
    pool = PagedKVCache(4, 8)
    pool.release([0, 0])                # null rows in a page table
    assert pool.free_pages == 3


# ---------------------------------------------------------- prefix hashes
def test_prefix_keys_full_pages_only_and_chained():
    toks = np.arange(20, dtype=np.int64)
    keys = prefix_page_keys(toks, 8)
    assert len(keys) == 2               # 20 tokens -> 2 full pages of 8
    assert prefix_page_keys(toks, 8, n_pages=1) == keys[:1]
    # chained: a flipped token in page 0 changes *every* later key
    other = toks.copy()
    other[0] += 1
    keys2 = prefix_page_keys(other, 8)
    assert keys2[0] != keys[0] and keys2[1] != keys[1]
    # same page-0 content, divergence in page 1: key 0 shared
    other2 = toks.copy()
    other2[12] += 1
    keys3 = prefix_page_keys(other2, 8)
    assert keys3[0] == keys[0] and keys3[1] != keys[1]


def test_register_lookup_longest_prefix():
    pool = PagedKVCache(8, 4)
    toks = np.arange(12, dtype=np.int64)        # 3 full pages
    held = pool.alloc(3)
    pool.register(toks, held)
    assert all(pool.refcount(p) == 2 for p in held)   # holder + registry
    # exact prefix: all three pages, retained for the caller
    got = pool.lookup(toks)
    assert got == held and pool.hits == 1
    assert all(pool.refcount(p) == 3 for p in held)
    pool.release(got)
    # divergence inside page 1 -> only page 0 matches
    fork = toks.copy()
    fork[5] += 1
    got = pool.lookup(fork)
    assert got == held[:1]
    pool.release(got)
    # unrelated prompt: miss
    assert pool.lookup(np.arange(100, 112, dtype=np.int64)) == []
    assert pool.misses == 1
    pool.release(held)                  # registry keeps them at rc 1
    assert pool.evictable_pages == 3 and pool.free_pages == 4


def test_lru_eviction_frees_idle_prefix_pages():
    pool = PagedKVCache(4, 4)           # 3 allocatable pages
    a = pool.alloc(2)
    pool.register(np.arange(8, dtype=np.int64), a)
    pool.release(a)                     # idle at rc 1, evictable
    assert pool.free_pages == 1 and pool.can_allocate(3)
    got = pool.alloc(3)                 # forces 2 LRU evictions
    assert pool.evictions == 2 and sorted(got) == sorted([*a, 3])
    assert pool.lookup(np.arange(8, dtype=np.int64)) == []  # gone
    pool.release(got)


def test_drop_prefixes_zeroes_all_refcounts():
    pool = PagedKVCache(6, 4)
    held = pool.alloc(2)
    pool.register(np.arange(8, dtype=np.int64), held)
    pool.release(held)
    pool.drop_prefixes()
    assert pool.live_pages == 0 and pool.free_pages == pool.capacity
    assert all(pool.refcount(p) == 0 for p in range(1, pool.n_pages))


# ------------------------------------------------- pages_for (admission)
def test_pages_for_rounds_up():
    sched = Scheduler(cfg=CFG, max_len=64)
    assert sched.pages_for(10, 5, 8) == 2      # ceil(15/8)
    assert sched.pages_for(16, 0, 8) == 2
    with pytest.raises(ValueError, match="page_size"):
        sched.pages_for(10, 5, 0)


def test_pages_for_window_cap_sliding_window():
    """Satellite contract: a sliding-window config caps live pages at
    ceil(W/ps) — mirroring the contiguous cache's wrap — instead of
    rejecting long prompts."""
    cfg = smoke_config(ARCHS["mixtral-8x22b"])
    assert cfg.sliding_window == 32
    sched = Scheduler(cfg=cfg, max_len=64)
    assert sched.window == 32
    assert sched.pages_for(100, 50, 8) == page_count(32, 8) == 4
    assert sched.pages_for(4, 4, 8) == 1       # short stays short


def test_sliding_window_long_prompt_admits_capped(params):
    """A prompt longer than the KV window serves through a pool holding
    only ceil(W/ps) pages — window-capped admission, not rejection."""
    cfg = smoke_config(ARCHS["mixtral-8x22b"])
    p = init_params(jax.random.PRNGKey(0), cfg)
    prompt = (np.arange(40) % cfg.vocab_size).astype(np.int32)  # > W=32
    npp = page_count(32, 8)

    def serve(eng):
        eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=6))
        return {r.rid: r.out_tokens for r in eng.run()}

    want = serve(ServeEngine(p, cfg, RT, n_slots=1, max_len=64))
    eng = PagedServeEngine(p, cfg, RT, n_slots=1, max_len=64,
                           page_size=8, page_budget=npp + 1)
    assert eng.pages.capacity == npp == 4
    got = serve(eng)
    assert got == want and not eng.rejected
    assert eng.pages.live_pages == 0           # all freed at retirement


# -------------------------------------------------- paged-vs-fixed parity
def _serve(eng, prompts, max_new=4):
    for i, prompt in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=prompt, max_new_tokens=max_new))
    return {r.rid: r.out_tokens for r in eng.run()}


def _prompts(cfg, n=5, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size,
                         int(rng.integers(3, 20))).astype(np.int32)
            for _ in range(n)]


@pytest.mark.parametrize("arch", ["minicpm-2b",      # dense
                                  "mixtral-8x22b",   # MoE + sliding window
                                  "zamba2-2.7b",     # hybrid attn/ssm
                                  "mamba2-1.3b"])    # pure SSM (no KV)
def test_paged_token_parity_across_families(arch):
    """The paged engine must emit bit-identical tokens to the
    contiguous engine for every cache family, slot churn included."""
    cfg = smoke_config(ARCHS[arch])
    p = init_params(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(cfg)
    want = _serve(ServeEngine(p, cfg, RT, n_slots=3, max_len=64), prompts)
    got = _serve(PagedServeEngine(p, cfg, RT, n_slots=3, max_len=64,
                                  page_size=8), prompts)
    assert got == want, (arch, got, want)


def test_paged_pallas_policy_token_parity(params):
    """Paged serving under the all-pallas policy (paged decode attention
    kernel, interpret mode) matches the XLA policy token-for-token."""
    prompts = _prompts(CFG, n=3)
    rt_pallas = ModelRuntime(dtype="float32", remat="none", attn_chunk=16,
                             moe_dropless=True, use_kernels=True)
    want = _serve(PagedServeEngine(params, CFG, RT, n_slots=2, max_len=64,
                                   page_size=8), prompts)
    got = _serve(PagedServeEngine(params, CFG, rt_pallas, n_slots=2,
                                  max_len=64, page_size=8), prompts)
    assert got == want


# --------------------------------------------------- page-budget admission
def test_page_budget_queues_instead_of_slots(params):
    """With pages, not slots, as the scarce resource, a tight budget
    serializes admission but every request still serves."""
    prompts = [(np.arange(12) + 5 * i).astype(np.int32) % CFG.vocab_size
               for i in range(6)]
    # each request needs 2 pages of 8 (12 prompt + 4 new = 16 tokens);
    # capacity 4 pages -> at most 2 in flight despite 4 slots
    eng = PagedServeEngine(params, CFG, RT, n_slots=4, max_len=64,
                           page_size=8, page_budget=5, prefix_cache=False)
    done = _serve(eng, prompts)
    assert sorted(done) == list(range(6))
    assert eng.stats.max_active <= 2
    assert eng.pages.live_pages == 0


def test_page_budget_overflow_reject(params):
    eng = PagedServeEngine(params, CFG, RT, n_slots=2, max_len=64,
                           page_size=8, page_budget=4)    # 3 pages
    eng.submit(Request(rid=0, prompt=np.arange(20, dtype=np.int32),
                       max_new_tokens=12))     # 4 pages > 3
    eng.submit(Request(rid=1, prompt=np.arange(4, dtype=np.int32),
                       max_new_tokens=4))
    done = eng.run()
    assert [r.rid for r in done] == [1]
    assert [r.rid for r in eng.rejected] == [0]
    assert "pool capacity" in eng.rejected[0].finish_reason


def test_page_budget_overflow_truncate(params):
    eng = PagedServeEngine(params, CFG, RT, n_slots=1, max_len=64,
                           page_size=8, page_budget=5,    # 4 pages
                           overflow="truncate")
    eng.submit(Request(rid=0, prompt=np.arange(20, dtype=np.int32),
                       max_new_tokens=20))     # 5 pages > 4
    r = eng.run()[0]
    assert r.truncated and len(r.out_tokens) == 12   # 4*8 - 20 budget
    assert r.finish_reason == "length"


def test_page_budget_overflow_error(params):
    eng = PagedServeEngine(params, CFG, RT, n_slots=1, max_len=64,
                           page_size=8, page_budget=4, overflow="error")
    with pytest.raises(ValueError, match="page budget"):
        eng.submit(Request(rid=0, prompt=np.arange(20, dtype=np.int32),
                           max_new_tokens=12))


# ----------------------------------------------------------- prefix cache
def _prefix_prompts(cfg, sys_len=24, n=4, seed=9):
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(0, cfg.vocab_size, sys_len)
    return [np.concatenate([sys_prompt,
                            rng.integers(0, cfg.vocab_size,
                                         int(rng.integers(3, 9)))])
            .astype(np.int32) for _ in range(n)]


def test_prefix_cache_token_parity_and_savings(params):
    """Warm prefix cache: identical tokens to the cold engine, nonzero
    hits, and strictly fewer prefill tokens + calls."""
    prompts = _prefix_prompts(CFG)
    cold = PagedServeEngine(params, CFG, RT, n_slots=2, max_len=64,
                            page_size=8, prefix_cache=False)
    warm = PagedServeEngine(params, CFG, RT, n_slots=2, max_len=64,
                            page_size=8, prefix_cache=True)
    want = _serve(cold, prompts)
    got = _serve(warm, prompts)
    assert got == want
    assert warm.stats.prefix_hits > 0
    assert 0.0 < warm.prefix_hit_rate <= 1.0
    assert warm.stats.prefix_hit_tokens > 0
    assert warm.stats.prefill_tokens < cold.stats.prefill_tokens
    assert warm.stats.prefills < cold.stats.prefills
    assert cold.stats.prefix_hits == 0


def test_prefix_cache_no_leak_across_runs(params):
    """Refcounts return to zero: after retirement only registry refs
    remain, and drop_prefixes releases those — across two run() waves."""
    warm = PagedServeEngine(params, CFG, RT, n_slots=2, max_len=64,
                            page_size=8, prefix_cache=True)
    _serve(warm, _prefix_prompts(CFG, n=3, seed=1))
    _serve(warm, _prefix_prompts(CFG, n=3, seed=2))   # second wave
    # only registered prefix pages are still held, each exactly once
    assert warm.pages.live_pages == warm.pages.evictable_pages > 0
    warm.pages.drop_prefixes()
    assert warm.pages.live_pages == 0
    assert warm.pages.free_pages == warm.pages.capacity
    assert all(warm.pages.refcount(pg) == 0
               for pg in range(1, warm.pages.n_pages))


def test_prefix_cache_off_for_sliding_window():
    """Windowed caches are position-addressed, so prefix sharing must
    auto-disable (pages aren't content-final once the cache wraps)."""
    cfg = smoke_config(ARCHS["mixtral-8x22b"])
    p = init_params(jax.random.PRNGKey(0), cfg)
    eng = PagedServeEngine(p, cfg, RT, n_slots=1, max_len=64,
                           page_size=8, prefix_cache=True)
    assert not eng._prefix_on


# ----------------------------------------------- float pools read in place
#: one query per kv head at minicpm-2b's head width: the pool stores
#: merged rows, ``Hkv * hd``, the slab the live-page kernel reads
MHA = CFG.replace(n_kv_heads=CFG.n_heads, d_head=64)
ONE_KIND = {"gqa": smoke_config(ARCHS["starcoder2-3b"]),
            "mha": MHA,
            "window": smoke_config(ARCHS["mixtral-8x22b"])}


def _scans(jaxpr):
    """Every scan equation of ``jaxpr`` and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _scans(inner)


@pytest.mark.parametrize("name", sorted(ONE_KIND))
def test_paged_decode_pools_ride_the_scan_carry(name):
    """A one-kind float decode step carries its pools through the layer
    scan: no scan output (ys) is the pool or a layer's slice of it, and
    an MHA pool's rows are merged."""
    from repro.models import abstract_params, decode_step_paged
    from repro.models.model import paged_cache_spec

    cfg = ONE_KIND[name]
    window = min(cfg.sliding_window or 64, 64)
    spec = paged_cache_spec(cfg, 2, 2 * page_count(window, 8) + 1, 8, 64,
                            "float32")
    pool = spec["kp"][0]
    rows = (cfg.n_kv_heads * cfg.head_dim,) if name == "mha" \
        else (cfg.n_kv_heads, cfg.head_dim)
    assert pool[3:] == rows
    cache = {k: jax.ShapeDtypeStruct(s, np.dtype(d))
             for k, (s, d) in spec.items()}
    jaxpr = jax.make_jaxpr(lambda p, c, t: decode_step_paged(
        p, cfg, c, t, RT, page_size=8, window=window))(
        abstract_params(cfg, "float32"), cache,
        jax.ShapeDtypeStruct((2,), np.int32)).jaxpr
    scans = list(_scans(jaxpr))
    assert scans
    carried = []
    for eqn in scans:
        nc = eqn.params["num_carry"]
        ys = [tuple(v.aval.shape) for v in eqn.outvars[nc:]]
        assert pool not in ys and pool[1:] not in ys, (name, ys)
        carried += [tuple(v.aval.shape) for v in eqn.outvars[:nc]]
    assert carried.count(pool) == 2                 # kp and vp


def test_paged_step_donates_the_cache(params):
    """The engine's step rewrites the cache in place: the cache a step
    was handed is deleted, the one it returned is live."""
    eng = PagedServeEngine(params, CFG, RT, n_slots=2, max_len=64,
                           page_size=8)
    eng.submit(Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                       max_new_tokens=4))
    eng.step()                                  # admits, then decodes
    before = eng.cache
    eng.step()
    assert before["kp"].is_deleted() and before["vp"].is_deleted()
    assert not eng.cache["kp"].is_deleted()


@pytest.mark.parametrize("kernels", [False, True])
def test_merged_rows_token_parity_with_prefix_hits(kernels):
    """MHA pools of merged rows: prefill rows scattered into them and
    read back by decode (XLA gather, or the live-page kernel in
    interpret mode) serve the contiguous engine's tokens, with prefix
    cache hits and without."""
    p = init_params(jax.random.PRNGKey(0), MHA)
    rt = ModelRuntime(dtype="float32", remat="none", attn_chunk=16,
                      moe_dropless=True, use_kernels=kernels)
    prompts = _prefix_prompts(MHA) + _prompts(MHA, n=2)
    want = _serve(ServeEngine(p, MHA, RT, n_slots=2, max_len=64), prompts)
    warm = PagedServeEngine(p, MHA, rt, n_slots=2, max_len=64, page_size=8)
    assert warm.cache["kp"].shape[3:] == (MHA.n_kv_heads * 64,)
    assert _serve(warm, prompts) == want
    assert warm.stats.prefix_hits > 0
    cold = PagedServeEngine(p, MHA, rt, n_slots=2, max_len=64, page_size=8,
                            prefix_cache=False)
    assert _serve(cold, prompts) == want
