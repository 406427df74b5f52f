"""ServeEngine coverage: continuous-batching slot refill, declared-axes
``_splice`` correctness (incl. the shape-heuristic misfire regressions),
cache-budget overflow enforcement, the repaired ``greedy=False`` path
(seeded sampling), EOS/stop-token termination, and re-admission of
queued requests into freed slots."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, smoke_config
from repro.models import init_params
from repro.models.model import ModelRuntime
from repro.serve import Request, Sampler, Scheduler, ServeEngine
from repro.serve.engine import _splice

CFG = smoke_config(ARCHS["minicpm-2b"])
RT = ModelRuntime(dtype="float32", remat="none", attn_chunk=16)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


# ---------------------------------------------------------------- _splice
def test_splice_batch_leading_cache():
    """Leaves whose declared batch axis leads (e.g. a per-slot state):
    row `slot` replaced."""
    axes = {"state": ("batch", None, None)}
    big = {"state": jnp.arange(4 * 3 * 2, dtype=jnp.float32)
           .reshape(4, 3, 2)}
    small = {"state": -jnp.ones((1, 3, 2), jnp.float32)}
    out = _splice(big, small, 2, axes=axes)
    np.testing.assert_array_equal(np.asarray(out["state"][2]),
                                  -np.ones((3, 2), np.float32))
    for keep in (0, 1, 3):
        np.testing.assert_array_equal(np.asarray(out["state"][keep]),
                                      np.asarray(big["state"][keep]))


def test_splice_layer_batch_cache():
    """(L, B, ...) leaves (stacked KV cache): column `slot` replaced in
    every layer."""
    L, B = 3, 4
    axes = {"k": (None, "batch", None)}
    big = {"k": jnp.arange(L * B * 5, dtype=jnp.float32)
           .reshape(L, B, 5)}
    small = {"k": -jnp.ones((L, 1, 5), jnp.float32)}
    out = _splice(big, small, 1, axes=axes)
    np.testing.assert_array_equal(np.asarray(out["k"][:, 1]),
                                  -np.ones((L, 5), np.float32))
    for keep in (0, 2, 3):
        np.testing.assert_array_equal(np.asarray(out["k"][:, keep]),
                                      np.asarray(big["k"][:, keep]))


def test_splice_pos_vector():
    """1-D per-sequence position counters splice by slot index."""
    big = {"pos": jnp.array([5, 6, 7, 8], jnp.int32)}
    small = {"pos": jnp.array([42], jnp.int32)}
    out = _splice(big, small, 3)
    np.testing.assert_array_equal(np.asarray(out["pos"]),
                                  [5, 6, 7, 42])


def test_splice_heuristic_misfire_regression():
    """REGRESSION (splice-by-shape bug): a batched admission whose
    small batch equals ``n_slots`` satisfied the seed heuristic
    ``big.shape[0] == small.shape[0] and small.shape[1] == 1`` *shape-
    compatibly* on the wrong axis — ``big.at[slot].set(small[0])``
    overwrote a whole layer with one layer row. Declared axes make the
    layout unambiguous."""
    L = B = 2                      # n_layers == n_slots == admitted batch
    axes = {"k": (None, "batch", None)}
    big = {"k": jnp.zeros((L, B, 3), jnp.float32)}
    small = {"k": jnp.stack([jnp.full((B, 3), 1.0 + i) for i in range(L)])}
    out = _splice(big, small, [0, 1], rows=[0, 1], axes=axes)
    # every layer keeps its own rows: layer i must hold value 1+i
    np.testing.assert_array_equal(np.asarray(out["k"]),
                                  np.asarray(small["k"]))
    # the seed heuristic's path on the same inputs: shape-compatible,
    # silently wrong (layer 0's row broadcast over the batch axis)
    wrong = big["k"].at[0].set(small["k"][0])
    assert not np.array_equal(np.asarray(wrong), np.asarray(out["k"]))


def test_splice_refuses_undeclared_leaf():
    """No declared batch axis -> loud KeyError, never a shape guess."""
    with pytest.raises(KeyError):
        _splice({"mystery": jnp.zeros((4, 4))},
                {"mystery": jnp.zeros((1, 4))}, 0, axes={})


def test_splice_real_model_cache(params):
    """Splicing a real prefilled batch=1 cache into a batch=4 cache
    only touches the target slot, for every leaf layout the model
    produces."""
    from repro.models import init_cache, prefill

    max_len = 32
    big = init_cache(CFG, 4, max_len, RT.dtype)
    toks = jnp.arange(7, dtype=jnp.int32)[None, :] % CFG.vocab_size
    single, _ = prefill(params, CFG, {"tokens": toks}, max_len, RT)
    out = _splice(big, single, 2)
    for key in big:
        b, o, s = (np.asarray(big[key]), np.asarray(out[key]),
                   np.asarray(single[key]))
        if key == "pos":                               # (B,)
            np.testing.assert_array_equal(o[2], s[0])
            np.testing.assert_array_equal(o[[0, 1, 3]], b[[0, 1, 3]])
        else:                                          # (L, B, ...)
            np.testing.assert_array_equal(o[:, 2], s[:, 0])
            np.testing.assert_array_equal(o[:, [0, 1, 3]],
                                          b[:, [0, 1, 3]])


# ------------------------------------------------------- overflow budget
def test_overflow_rejected_not_dropped(params):
    """REGRESSION (KV overflow bug): prompt_len + max_new_tokens >
    max_len used to clamp-write the cache past max_len; now the request
    is rejected at submit, surfaced via `rejected`, and the in-budget
    request still serves."""
    eng = ServeEngine(params, CFG, RT, n_slots=2, max_len=32)
    eng.submit(Request(rid=0, prompt=np.arange(20, dtype=np.int32),
                       max_new_tokens=20))
    eng.submit(Request(rid=1, prompt=np.arange(4, dtype=np.int32),
                       max_new_tokens=4))
    done = eng.run()
    assert [r.rid for r in done] == [1]
    assert [r.rid for r in eng.rejected] == [0]
    assert eng.rejected[0].finish_reason.startswith("rejected:")
    assert eng.stats.rejected == 1


def test_overflow_truncate_is_loud(params):
    eng = ServeEngine(params, CFG, RT, n_slots=1, max_len=32,
                      overflow="truncate")
    eng.submit(Request(rid=0, prompt=np.arange(20, dtype=np.int32),
                       max_new_tokens=20))
    r = eng.run()[0]
    assert r.truncated and len(r.out_tokens) == 12   # 32 - 20 budget
    assert r.finish_reason == "length"


def test_overflow_error_policy_raises(params):
    eng = ServeEngine(params, CFG, RT, n_slots=1, max_len=32,
                      overflow="error")
    with pytest.raises(ValueError, match="cache budget"):
        eng.submit(Request(rid=0, prompt=np.arange(30, dtype=np.int32),
                           max_new_tokens=5))


def test_overflow_budget_respected_under_decode(params):
    """A request using its exact budget decodes fine: positions never
    pass max_len (the cache-bounds contract)."""
    eng = ServeEngine(params, CFG, RT, n_slots=1, max_len=24)
    eng.submit(Request(rid=0, prompt=np.arange(8, dtype=np.int32),
                       max_new_tokens=16))
    r = eng.run(max_iters=64)[0]
    assert len(r.out_tokens) == 16
    assert int(np.asarray(eng.cache["pos"]).max()) <= 24


def test_run_surfaces_unserved_requests(params):
    """REGRESSION: exhausting max_iters with work in flight raises
    instead of silently dropping the requests from `finished`."""
    eng = ServeEngine(params, CFG, RT, n_slots=1, max_len=64)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=np.arange(4, dtype=np.int32),
                           max_new_tokens=8))
    with pytest.raises(RuntimeError, match="never served"):
        eng.run(max_iters=2)


# ------------------------------------------------------------- sampling
def _sample_run(params, sampler, n=4, max_new=5):
    eng = ServeEngine(params, CFG, RT, n_slots=2, max_len=64,
                      sampler=sampler)
    for i in range(n):
        eng.submit(Request(rid=i,
                           prompt=(np.arange(3 + i) % CFG.vocab_size)
                           .astype(np.int32),
                           max_new_tokens=max_new))
    return {r.rid: r.out_tokens for r in eng.run()}


def test_greedy_false_regression(params):
    """REGRESSION (dead ``greedy=False`` branch): the seed admission
    emitted a hard-coded token 0 for every non-greedy request. The path
    now routes through the seeded Sampler: valid ids, not the constant-0
    stream, and reproducible run-to-run."""
    eng = ServeEngine(params, CFG, RT, n_slots=2, max_len=64,
                      greedy=False)
    for i in range(3):
        eng.submit(Request(rid=i,
                           prompt=(np.arange(4 + i) % CFG.vocab_size)
                           .astype(np.int32), max_new_tokens=6))
    toks = [t for r in eng.run() for t in r.out_tokens]
    assert all(0 <= t < CFG.vocab_size for t in toks)
    assert any(t != 0 for t in toks)


def test_seeded_sampling_reproducible(params):
    s7 = Sampler(kind="temperature", temperature=0.9, top_k=16, seed=7)
    a = _sample_run(params, s7)
    b = _sample_run(params, s7)
    c = _sample_run(params, Sampler(kind="temperature", temperature=0.9,
                                    top_k=16, seed=8))
    assert a == b                      # same seed -> identical tokens
    assert a != c                      # different seed -> different draw
    assert all(0 <= t < CFG.vocab_size
               for ts in a.values() for t in ts)


def test_greedy_sampler_matches_argmax(params):
    """The greedy Sampler is the seed argmax path, token for token."""
    a = _sample_run(params, Sampler())
    b = _sample_run(params, Sampler(kind="greedy", seed=123))
    assert a == b                      # greedy ignores the seed


@pytest.mark.parametrize("page_size", [0, 8], ids=["fixed", "paged"])
def test_greedy_argmax_on_device_matches_host(params, page_size):
    """A greedy engine's programs end in the argmax and a step copies
    one int32 id a slot; the tokens equal the host's argmax of the
    copied logits (``Sampler(host=True)``), token for token."""
    from repro.launch.serve import build_engine

    def run(sampler):
        eng = build_engine(params, CFG, RT, n_slots=2, max_len=64,
                           sampler=sampler, page_size=page_size)
        seen = []
        fetch = eng._fetch
        eng._fetch = lambda out: seen.append(fetch(out)) or seen[-1]
        for i in range(3):
            eng.submit(Request(rid=i,
                               prompt=(np.arange(5 + 3 * i) * 7
                                       % CFG.vocab_size).astype(np.int32),
                               max_new_tokens=6))
        return {r.rid: r.out_tokens for r in eng.run()}, seen

    dev, dev_out = run(Sampler())
    host, host_out = run(Sampler(host=True))
    assert dev == host
    assert all(o.shape == (2,) and o.dtype == np.int32 for o in dev_out)
    assert all(o.shape == (2, CFG.vocab_size) for o in host_out)


# ---------------------------------------------------------- termination
def test_eos_termination(params):
    base = ServeEngine(params, CFG, RT, n_slots=1, max_len=64)
    base.submit(Request(rid=0, prompt=np.array([1, 2, 3], np.int32),
                        max_new_tokens=8))
    ref = base.run()[0].out_tokens
    eos = ref[2]
    eng = ServeEngine(params, CFG, RT, n_slots=1, max_len=64,
                      eos_id=eos)
    eng.submit(Request(rid=0, prompt=np.array([1, 2, 3], np.int32),
                       max_new_tokens=8))
    r = eng.run()[0]
    assert r.finish_reason == "stop"
    assert r.out_tokens == ref[: r.out_tokens.index(eos) + 1]


def test_per_request_stop_tokens(params):
    base = ServeEngine(params, CFG, RT, n_slots=1, max_len=64)
    base.submit(Request(rid=0, prompt=np.array([1, 2, 3], np.int32),
                        max_new_tokens=8))
    ref = base.run()[0].out_tokens
    eng = ServeEngine(params, CFG, RT, n_slots=2, max_len=64)
    eng.submit(Request(rid=0, prompt=np.array([1, 2, 3], np.int32),
                       max_new_tokens=8, stop_tokens=(ref[1],)))
    eng.submit(Request(rid=1, prompt=np.array([1, 2, 3], np.int32),
                       max_new_tokens=8))
    done = {r.rid: r for r in eng.run()}
    assert done[0].finish_reason == "stop"
    assert done[0].out_tokens == ref[:2]
    assert done[1].out_tokens == ref       # stop set is per-request


# ---------------------------------------------------------- slot refill
def test_slots_refill_from_queue(params):
    """More requests than slots: freed slots must be re-admitted from
    the queue until everything finishes."""
    eng = ServeEngine(params, CFG, RT, n_slots=2, max_len=64)
    for i in range(6):
        eng.submit(Request(rid=i,
                           prompt=(np.arange(3 + i) % CFG.vocab_size)
                           .astype(np.int32),
                           max_new_tokens=4))
    done = eng.run()
    assert sorted(r.rid for r in done) == [0, 1, 2, 3, 4, 5]
    assert all(len(r.out_tokens) == 4 for r in done)
    assert all(r.done for r in done)
    assert all(r.finish_reason == "length" for r in done)
    assert eng.queue == [] and all(s is None for s in eng.slots)


def test_active_slot_count_tracks_occupancy(params):
    eng = ServeEngine(params, CFG, RT, n_slots=3, max_len=64)
    assert eng.step() == 0                         # nothing submitted
    eng.submit(Request(rid=0,
                       prompt=np.array([1, 2, 3], np.int32),
                       max_new_tokens=3))
    # prefill emits token 1 at admission; two decode steps remain
    assert eng.step() == 1                         # one slot active
    assert eng.step() == 1                         # finishes this step
    assert eng.step() == 0                         # drained
    assert [r.rid for r in eng.finished] == [0]
    assert eng.stats.tokens_out == 3
    assert eng.stats.occupancy(3) == pytest.approx(1 / 3)


def _run_engine(cfg, params, rt, prompts, max_new=4, n_slots=2,
                max_len=64, **kw):
    eng = ServeEngine(params, cfg, rt, n_slots=n_slots, max_len=max_len,
                      **kw)
    for i, prompt in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=prompt, max_new_tokens=max_new))
    done = eng.run()
    return {r.rid: r.out_tokens for r in done}


@pytest.mark.parametrize("arch", ["minicpm-2b", "qwen2-moe-a2.7b"])
def test_serve_engine_pallas_policy_token_parity(arch):
    """End-to-end serving under the all-pallas KernelPolicy (interpret
    mode) must emit token-for-token identical output to the XLA policy:
    prefill, cache splice, continuous-batching decode, the full path."""
    cfg = smoke_config(ARCHS[arch])
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [
        (np.arange(5) % cfg.vocab_size).astype(np.int32),
        ((np.arange(3) + 7) % cfg.vocab_size).astype(np.int32),
        ((np.arange(4) + 11) % cfg.vocab_size).astype(np.int32),
    ]
    rt_xla = ModelRuntime(dtype="float32", remat="none", attn_chunk=16,
                          moe_dropless=True)
    rt_pallas = ModelRuntime(dtype="float32", remat="none", attn_chunk=16,
                             moe_dropless=True, use_kernels=True)
    got_xla = _run_engine(cfg, params, rt_xla, prompts)
    got_pallas = _run_engine(cfg, params, rt_pallas, prompts)
    assert got_xla.keys() == got_pallas.keys()
    for rid in got_xla:
        assert got_xla[rid] == got_pallas[rid], (
            f"{arch} rid={rid}: xla {got_xla[rid]} != "
            f"pallas {got_pallas[rid]}")


def test_mid_flight_admission_preserves_neighbors(params):
    """Admitting into a freed slot must not disturb the sequence still
    decoding in the other slot (slot isolation across refill)."""
    long_prompt = (np.arange(5) % CFG.vocab_size).astype(np.int32)
    solo = ServeEngine(params, CFG, RT, n_slots=1, max_len=64)
    solo.submit(Request(rid=0, prompt=long_prompt, max_new_tokens=8))
    ref = solo.run()[0].out_tokens

    eng = ServeEngine(params, CFG, RT, n_slots=2, max_len=64)
    eng.submit(Request(rid=0, prompt=long_prompt, max_new_tokens=8))
    # short request finishes early; rid=2 is admitted mid-flight
    eng.submit(Request(rid=1,
                       prompt=np.array([4, 5], np.int32),
                       max_new_tokens=2))
    eng.submit(Request(rid=2,
                       prompt=np.array([6, 7, 8], np.int32),
                       max_new_tokens=3))
    done = eng.run()
    got = [r for r in done if r.rid == 0][0].out_tokens
    assert got == ref
    assert sorted(r.rid for r in done) == [0, 1, 2]


def test_batched_admission_width_parity(params):
    """admit_width > 1 (multi-slot batched prefill + multi-slot splice)
    serves the same tokens as width-1 admission."""
    prompts = [((np.arange(5) + 3 * i) % CFG.vocab_size)
               .astype(np.int32) for i in range(6)]
    w1 = _run_engine(CFG, params, RT, prompts, n_slots=4,
                     scheduler=Scheduler(cfg=CFG, max_len=64,
                                         admit_width=1))
    w4 = _run_engine(CFG, params, RT, prompts, n_slots=4,
                     scheduler=Scheduler(cfg=CFG, max_len=64,
                                         admit_width=4))
    assert w1 == w4
