"""A model of window and full attention layers with routed experts, of
which the device holds a share (mellum2-12b-a2.5b's architecture at a
small size): served through the launcher's paged engine and compared
with the plain float32 reference of the chip benchmark
(``benchmarks/chip/configs/moe_window.py``); YaRN's frequencies; the
held-expert shares; grouped dispatch against the capacity einsum; the
two cache groups' pages."""
import dataclasses
import importlib.util
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, MoEConfig, get_arch
from repro.kernels.dispatch import KernelPolicy
from repro.launch.serve import build_engine, init_serving_params, \
    serving_runtime
from repro.models import init_params, page_count, paged_cache_spec
from repro.models.layers import yarn_inv_freq
from repro.models.moe import moe_ffn
from repro.serve import Request, Sampler

ROOT = Path(__file__).resolve().parents[1]
SEED = 7
MAX_LEN, PAGE, WINDOW = 256, 8, 32
# 2 periods of (3 window + 1 full); 8 of 16 experts held, 4 per token
CFG = get_arch("mellum2-12b-a2.5b").replace(
    name="tiny-mellum", n_layers=8, d_model=64, n_heads=4, n_kv_heads=2,
    d_head=16, d_ff=128, vocab_size=256, sliding_window=WINDOW,
    moe=MoEConfig(n_experts=16, experts_per_token=4, d_expert=32,
                  n_held=8))
# prompts past the window (every ring wraps) and one inside it; buckets
# past the window, so pad mode fills the ring from the real lengths
LENGTHS = [(50, 40), (10, 30), (100, 50), (70, 20), (190, 24)]
BUCKETS = (16, 64, 128, 192)
RAGGED = KernelPolicy(moe_gemm="ragged")


def _reference():
    path = ROOT / "benchmarks" / "chip" / "configs" / "moe_window.py"
    spec = importlib.util.spec_from_file_location("moe_window_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _model_dict(cfg=CFG):
    """The reference's ``model`` for ``cfg``, in ``config.json`` keys."""
    y, m = cfg.yarn, cfg.moe
    kinds = {"window": "sliding_attention", "full": "full_attention"}
    return {
        "num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "vocab_size": cfg.vocab_size, "sliding_window": cfg.sliding_window,
        "layer_types": [kinds[cfg.layer_kind(i)]
                        for i in range(cfg.n_layers)],
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": cfg.rope_theta,
                "factor": y.factor,
                "original_max_position_embeddings": y.original_max_position,
                "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                "attention_factor": y.attention_factor},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": cfg.rope_theta}},
        "rms_norm_eps": 1e-6, "norm_topk_prob": True,
        "router_experts": m.n_experts, "num_experts": m.held,
        "num_experts_per_tok": m.experts_per_token,
        "moe_intermediate_size": m.d_expert, "intermediate_size": cfg.d_ff,
        "tie_word_embeddings": False}


@pytest.fixture(scope="module")
def served():
    """Every request of ``LENGTHS`` served greedily by the paged engine,
    with the decode logits of each served token after the first (the
    sampler takes its argmax on the host, so the logits come back)."""
    params = init_serving_params(CFG, SEED, "float32")
    eng = build_engine(params, CFG, serving_runtime("float32",
                                                    kernels=RAGGED),
                       n_slots=3, max_len=MAX_LEN, buckets=BUCKETS,
                       page_size=PAGE, sampler=Sampler(host=True))
    rows = {}
    fetch = eng._fetch

    def keep(logits):
        out = fetch(logits)
        for slot, req in enumerate(eng.slots):
            if req is not None:
                rows.setdefault(req.rid, []).append(out[slot].copy())
        return out

    eng._fetch = keep
    rng = np.random.default_rng(SEED)
    reqs = [Request(rid=i, prompt=rng.integers(0, CFG.vocab_size, n)
                    .astype(np.int32), max_new_tokens=m)
            for i, (n, m) in enumerate(LENGTHS)]
    for r in reqs:
        eng.submit(r)
    eng.run(max_iters=4000)
    return eng, reqs, rows


@pytest.fixture(scope="module")
def reference_logits(served):
    """The reference's logits at every decoded position, and those of
    the reference with its weights rounded to bfloat16."""
    _, reqs, _ = served
    m = _model_dict()
    w = REF.make_weights(m, SEED, "float32")
    w16 = {k: v.astype(jnp.bfloat16) for k, v in w.items()}
    out = {}
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1],
                                                   np.int32)])
        first = len(r.prompt)
        out[r.rid] = (REF.logits_at(m, w, seq, first),
                      REF.logits_at(m, w16, seq, first))
    return out


# float32 program against float32 reference: the only differences are
# the order of sums (bucketed padding, grouped rows, paged blocks), worth
# 1e-5 of logits of size 1; a weight rounded to bfloat16 (8 bits of
# mantissa) moves them by 1e-2, so 2e-3 sits between with room both ways
LOGIT_TOL = 2e-3


def test_paged_engine_matches_the_reference_past_the_window(
        served, reference_logits):
    eng, reqs, rows = served
    for r in reqs:
        assert r.finish_reason == "length"
        got = np.stack(rows[r.rid])               # decode steps only
        ref, _ = reference_logits[r.rid]
        assert got.shape == ref.shape
        assert np.abs(got - ref).max() < LOGIT_TOL, r.rid
        # greedy: each served token is the reference's best there
        served_logit = ref[np.arange(len(ref)), r.out_tokens[1:]]
        assert (ref.max(-1) - served_logit).max() < LOGIT_TOL


def test_a_bfloat16_operand_fails_the_tolerance(served, reference_logits):
    _, reqs, _ = served
    worst = max(np.abs(a - b).max() for a, b in reference_logits.values())
    assert worst > 2 * LOGIT_TOL


def test_engine_counts_expert_rows_and_both_cache_groups(served):
    eng, reqs, _ = served
    st = eng.stats
    # rows routed to held experts, over layers and decode steps: at most
    # every (token, k) row of every layer, and some of them
    assert 0 < st.expert_rows <= st.occupancy_sum * 4 * CFG.n_layers
    assert 0 < st.experts_hit <= st.steps * 8 * CFG.n_layers
    assert st.alloc_token_steps > st.ring_alloc_token_steps > 0


def test_cache_groups_reserve_and_release():
    """The full layers' pages are reserved at admission and freed at
    release; each slot owns a fixed ring of the window layers' pool
    (page 0 is the null page), written by its prefill scatter."""
    params = init_serving_params(CFG, SEED, "float32")
    eng = build_engine(params, CFG, serving_runtime("float32"), n_slots=2,
                       max_len=MAX_LEN, buckets=BUCKETS, page_size=PAGE)
    npp, npw = page_count(MAX_LEN, PAGE), page_count(WINDOW, PAGE)
    rings = [[1, 2, 3, 4], [5, 6, 7, 8]]
    assert eng.pages.capacity == 2 * npp
    assert eng.cache["pt"].shape == (2, npp)
    assert eng.cache["kpw"].shape[:2] == (6, 2 * npw + 1)
    assert np.asarray(eng.cache["ptw"]).tolist() == rings
    long_, short = (100, 20), (10, 5)
    for i, (n, m) in enumerate((long_, short)):
        eng.submit(Request(rid=i, prompt=np.arange(n, dtype=np.int32) % 97,
                           max_new_tokens=m))
    eng.step()
    held = [eng._slot_pages[s][1] for s in (0, 1)]
    # full group: every row the request can reach
    assert len(held[0]) == page_count(sum(long_), PAGE) == 15
    assert len(held[1]) == page_count(sum(short), PAGE) == 2
    assert eng.pages.live_pages == 17
    # the prefill scatter wrote each slot's own ring
    kpw = np.asarray(eng.cache["kpw"])
    assert all(kpw[:, page].any() for page in rings[0] + rings[1][:2])
    eng.run(max_iters=500)
    assert eng.pages.live_pages == 0
    assert not np.asarray(eng.cache["pt"]).any()
    assert np.asarray(eng.cache["ptw"]).tolist() == rings


@pytest.mark.parametrize("arch", ["starcoder2-3b", "minicpm-2b"])
def test_models_of_one_kind_keep_their_cache(arch):
    """One attention kind and no experts: the paged cache is the one
    group it always was, with no counters; its rows are the decode
    kernel's (one merged vector a row for minicpm-2b's 36 query and kv
    heads of 64, kv heads apart for starcoder2-3b's groups)."""
    cfg = ARCHS[arch]
    spec = paged_cache_spec(cfg, 16, 4097, 16, 4096)
    assert set(spec) == {"pos", "pt", "kp", "vp"}
    assert spec["pt"][0] == (16, 256)
    row = {"starcoder2-3b": (2, 128), "minicpm-2b": (36 * 64,)}[arch]
    assert spec["kp"][0] == (cfg.n_layers, 4097, 16) + row


# ------------------------------------------------------------------- rope
def test_yarn_frequencies_closed_form():
    """Mellum2's full layers: head 128, theta 500,000, 8,192 original
    positions, beta 32 / 1. The correction dimensions are 128 ln(8192 /
    (2 pi r)) / (2 ln 500000): 18.08 at r = 32 (floor 18) and 34.98 at
    r = 1 (ceil 35), so pairs 0-17 keep theta^(-i/64), pairs 35-63 are
    divided by 16, and pair i between mixes them with ramp (i - 18) /
    17."""
    cfg = ARCHS["mellum2-12b-a2.5b"]
    got = np.asarray(yarn_inv_freq(128, cfg.rope_theta, cfg.yarn),
                     np.float64)
    base = 500000.0 ** (-np.arange(64) / 64)
    ramp = np.clip((np.arange(64) - 18) / 17, 0, 1)
    assert got[:18] == pytest.approx(base[:18], rel=1e-6)
    assert got[35:] == pytest.approx(base[35:] / 16, rel=1e-6)
    assert got[26] == pytest.approx(base[26] * (1 - 8 / 17 + 8 / 17 / 16),
                                    rel=1e-6)
    assert got == pytest.approx(base / 16 * ramp + base * (1 - ramp),
                                rel=1e-6)
    # the reference writes the same rule out on its own
    ref = REF.yarn_inv_freq(_model_dict(cfg))
    assert got == pytest.approx(ref, rel=1e-6)


# ---------------------------------------------------------------- experts
def _moe_params(n_held=0, first=0):
    """Layer 0's expert layer of all 16 experts; with ``n_held``, the
    share of a device that holds experts ``first .. first + n_held - 1``:
    their weights, and the router's columns rotated to put them first
    (a layer holds experts 0 onward; routing is the same under any
    order of the experts)."""
    cfg = CFG.replace(moe=dataclasses.replace(CFG.moe, n_held=16))
    p = init_params(jax.random.PRNGKey(3), cfg)["blocks"]["moe"]
    p = jax.tree.map(lambda a: a[0], p)                     # layer 0
    if n_held:
        p = dict(p, router=jnp.roll(p["router"], -first, axis=-1),
                 **{k: p[k][first:first + n_held]
                    for k in ("wg", "wi", "wo")})
    return p


def _moe_cfg(n_held=0):
    return CFG.replace(moe=dataclasses.replace(CFG.moe, n_held=n_held))


X = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 64), jnp.float32)


@pytest.mark.parametrize("policy", [None, RAGGED])
def test_four_shares_sum_to_the_uncut_layer(policy):
    """Four devices holding 4 of 16 experts each: their partial outputs
    add up to the layer that holds all 16, and their row counts to its."""
    whole, _, n_whole = moe_ffn(_moe_params(), X, _moe_cfg(), dropless=True,
                                policy=policy)
    parts = [moe_ffn(_moe_params(4, s), X, _moe_cfg(4), dropless=True,
                     policy=policy) for s in (0, 4, 8, 12)]
    total = sum(out for out, _, _ in parts)
    assert np.abs(np.asarray(total - whole)).max() < 1e-5
    assert sum(int(n[0]) for _, _, n in parts) == int(n_whole[0]) \
        == X.shape[0] * X.shape[1] * 4
    assert sum(int(n[1]) for _, _, n in parts) == int(n_whole[1])


@pytest.mark.parametrize("n_held,first", [(8, 4), (16, 0)])
def test_grouped_dispatch_equals_the_capacity_einsum(n_held, first):
    p, cfg = _moe_params(n_held, first), _moe_cfg(n_held)
    ein, aux, n_ein = moe_ffn(p, X, cfg, dropless=True)
    grp, aux_g, n_grp = moe_ffn(p, X, cfg, dropless=True, policy=RAGGED)
    assert np.abs(np.asarray(grp - ein)).max() < 1e-5
    assert float(aux) == pytest.approx(float(aux_g))
    assert list(np.asarray(n_grp)) == list(np.asarray(n_ein))


def test_unsorted_rows_through_the_ragged_gemm():
    """The ``ragged`` impl groups unsorted rows itself: each row meets
    its own expert's weights, as the gather reference does."""
    from repro.kernels.dispatch import implementations
    impl = implementations("moe_gemm")
    k = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(k[0], (40, 16), jnp.float32)
    w = jax.random.normal(k[1], (5, 16, 8), jnp.float32)
    e = jax.random.randint(k[2], (40,), 0, 5)
    got = impl["ragged"](x, w, e, n_experts=5)
    want = impl["xla"](x, w, e, n_experts=5)
    assert np.abs(np.asarray(got - want)).max() < 1e-5


def test_contiguous_engine_serves_the_same_tokens(served):
    """The fixed-slot engine (contiguous caches: full layers at
    ``max_len`` rows, window layers in their ring) serves the paged
    engine's tokens."""
    _, reqs, _ = served
    params = init_serving_params(CFG, SEED, "float32")
    eng = build_engine(params, CFG, serving_runtime("float32"), n_slots=3,
                       max_len=MAX_LEN, buckets=BUCKETS)
    assert eng.cache["kw"].shape[:3] == (6, 3, WINDOW)
    assert eng.cache["k"].shape[:3] == (2, 3, MAX_LEN)
    for r in reqs:
        eng.submit(Request(rid=r.rid, prompt=r.prompt,
                           max_new_tokens=r.max_new_tokens))
    done = {r.rid: r.out_tokens for r in eng.run(max_iters=4000)}
    assert done == {r.rid: r.out_tokens for r in reqs}
