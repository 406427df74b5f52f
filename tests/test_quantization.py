"""Quantization as a precision axis: scheme round-trips, kernel parity,
logit-deviation-bounded serving parity across every attention family,
the paged-vs-contiguous bit-identity invariant under int8 KV, and the
byte accounting (equal-HBM page budgets, scale side-bands, preflight ==
engine).

The acceptance contract for accuracy is the *logit deviation bound*
(``QUANT_PARITY_TOL``), never bit-exact tokens vs bf16: per-row int8 KV
keeps logits within a small envelope, but a near-tie argmax can flip a
greedy token below any useful tolerance. Between the two int8 engines
(paged vs contiguous) tokens ARE asserted identical — rows quantize
exactly once at write time, so both engines attend over bit-identical
payloads.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, smoke_config
from repro.kernels.quant import (QUANT_PARITY_TOL, dequantize_rows,
                                 quant_decode_attention_xla,
                                 quant_matmul_xla,
                                 quant_paged_decode_attention_xla,
                                 quantize_channels, quantize_rows)
from repro.models import init_params
from repro.models.model import ModelRuntime, page_count

CFG = smoke_config(ARCHS["minicpm-2b"])
RT_INT8 = ModelRuntime(dtype="float32", remat="none", attn_chunk=16,
                       moe_dropless=True, kv_dtype="int8")

#: one arch per attention family the quantized cache must serve
PARITY_ARCHS = ("minicpm-2b",        # dense GQA
                "qwen2-moe-a2.7b",   # MoE
                "starcoder2-3b",     # sliding window
                "zamba2-2.7b")       # hybrid (SSM + shared attn)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


# ===================================================================
# Scheme round-trips
# ===================================================================
def test_quantize_rows_roundtrip_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 7, 16)) * 3.0, jnp.float32)
    q, s = quantize_rows(x)
    assert q.dtype == jnp.int8 and s.shape == x.shape[:-1]
    assert int(jnp.max(jnp.abs(q.astype(jnp.int32)))) <= 127
    # symmetric round-to-nearest: scale/2 per element from the int8
    # rounding, plus ~2^-8 relative from the bf16-stored scale itself
    err = jnp.abs(dequantize_rows(q, s) - x)
    bound = s.astype(jnp.float32)[..., None] * 0.5 + jnp.abs(x) * 2**-7
    assert bool(jnp.all(err <= bound + 1e-6))


def test_quantize_rows_zero_rows():
    x = jnp.zeros((3, 8), jnp.float32)
    q, s = quantize_rows(x)
    assert bool(jnp.all(q == 0)) and bool(jnp.all(s == 0))
    assert bool(jnp.all(dequantize_rows(q, s) == 0))


def test_quantize_rows_clips_outliers():
    # one huge element sets the scale; everything stays within ±127
    x = jnp.asarray([[1.0, -1000.0, 0.5, 2.0]], jnp.float32)
    q, s = quantize_rows(x)
    assert int(q[0, 1]) == -127
    assert float(s[0]) == pytest.approx(1000.0 / 127.0, rel=1e-2)


def test_quantize_channels_roundtrip():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=(32, 12)), jnp.float32)
    w_q, s = quantize_channels(w)
    assert w_q.dtype == jnp.int8 and s.shape == (12,)
    err = jnp.abs(w_q.astype(jnp.float32) * s[None, :] - w)
    assert bool(jnp.all(err <= s[None, :] * 0.5 + 1e-6))
    # zero channel -> zero scale, zero payload
    wz = w.at[:, 3].set(0.0)
    qz, sz = quantize_channels(wz)
    assert float(sz[3]) == 0.0 and bool(jnp.all(qz[:, 3] == 0))


# ===================================================================
# Kernel parity (pallas interpret vs xla reference)
# ===================================================================
def test_quant_matmul_pallas_matches_xla():
    from repro.kernels.quant import quant_matmul_pallas
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(48, 64)), jnp.float32)
    w_q, s = quantize_channels(jnp.asarray(rng.normal(size=(64, 40)),
                                           jnp.float32))
    ref = quant_matmul_xla(x, w_q, s)
    out = quant_matmul_pallas(x, w_q, s, block_t=32, block_n=16,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_quant_decode_attention_pallas_matches_xla():
    from repro.kernels.quant import quant_decode_attention_splitkv
    rng = np.random.default_rng(3)
    B, Hq, Hkv, W, D = 2, 4, 2, 40, 16
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
    k_q, ks = quantize_rows(jnp.asarray(
        rng.normal(size=(B, W, Hkv, D)), jnp.float32))
    v_q, vs = quantize_rows(jnp.asarray(
        rng.normal(size=(B, W, Hkv, D)), jnp.float32))
    mask = jnp.arange(W)[None, :] < jnp.asarray([[17], [40]])
    ref = quant_decode_attention_xla(q, k_q, v_q, ks, vs, mask)
    out = quant_decode_attention_splitkv(q, k_q, v_q, ks, vs, mask,
                                         block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_quant_paged_decode_attention_pallas_matches_xla():
    from repro.kernels.quant import quant_paged_decode_attention_splitkv
    rng = np.random.default_rng(4)
    B, Hq, Hkv, D, ps, NP, P = 2, 4, 2, 16, 8, 4, 11
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
    kp, ks = quantize_rows(jnp.asarray(
        rng.normal(size=(P, ps, Hkv, D)), jnp.float32))
    vp, vs = quantize_rows(jnp.asarray(
        rng.normal(size=(P, ps, Hkv, D)), jnp.float32))
    pt = jnp.asarray(rng.choice(np.arange(1, P), size=(B, NP),
                                replace=False), jnp.int32)
    mask = jnp.arange(NP * ps)[None, :] < jnp.asarray([[13], [32]])
    ref = quant_paged_decode_attention_xla(q, kp, vp, ks, vs, pt, mask)
    out = quant_paged_decode_attention_splitkv(
        q, kp, vp, ks, vs, pt, mask, pages_per_block=2, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# ===================================================================
# Teacher-forced logit parity, every attention family
# ===================================================================
@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_logit_parity_within_tol(arch):
    from repro.serve.parity import logit_parity
    cfg = smoke_config(ARCHS[arch])
    pr = init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in (6, 11, 17)]
    rep = logit_parity(pr, cfg, prompts,
                       rt_ref=ModelRuntime(dtype="float32", remat="none",
                                           attn_chunk=16,
                                           moe_dropless=True),
                       rt_test=RT_INT8, max_new_tokens=4)
    assert rep.within_tol, (arch, rep.to_json())
    assert rep.n_tokens == 3 * 5
    # the report is the benchmark's accuracy sidebar: schema must hold
    j = rep.to_json()
    assert set(j) == {"max_logit_dev", "token_match_frac", "n_tokens",
                      "tol", "within_tol"}
    assert j["tol"] == QUANT_PARITY_TOL


@pytest.mark.parametrize("bad", [np.nan, np.inf])
@pytest.mark.parametrize("side", ["ref", "test"])
def test_compare_logits_non_finite_is_never_within_tol(bad, side):
    """One non-finite logit at one decode step, after finite prefill
    logits, must not vanish from the deviation."""
    from repro.serve.parity import compare_logits
    rng = np.random.default_rng(0)
    ref = [rng.normal(size=(2, 8)).astype(np.float32) for _ in range(4)]
    test = [r.copy() for r in ref]
    (ref if side == "ref" else test)[2][1, 3] = bad
    rep = compare_logits(ref, test)
    assert not np.isfinite(rep.max_logit_dev)
    assert not rep.within_tol


# ===================================================================
# Paged vs contiguous int8: bit-identical token streams
# ===================================================================
def test_int8_paged_matches_int8_contiguous(params):
    from repro.serve import PagedServeEngine, Request, ServeEngine
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, CFG.vocab_size,
                            int(rng.integers(4, 24))).astype(np.int32)
               for _ in range(6)]
    outs = {}
    for name, cls, kw in (("contig", ServeEngine, {}),
                          ("paged", PagedServeEngine,
                           {"page_size": 8, "prefix_cache": False})):
        eng = cls(params, CFG, RT_INT8, n_slots=3, max_len=64, **kw)
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid=rid, prompt=p.copy(),
                               max_new_tokens=8))
        eng.run(max_iters=2000)
        assert len(eng.finished) == len(prompts) and not eng.rejected
        outs[name] = {r.rid: list(r.out_tokens) for r in eng.finished}
    # rows quantize once at write time: both engines attend over
    # bit-identical int8 payloads, so the streams match exactly
    assert outs["paged"] == outs["contig"]


# ===================================================================
# Byte accounting: side-bands, equal-HBM budgets, preflight == engine
# ===================================================================
def test_cache_spec_int8_side_bands():
    from repro.models.model import cache_spec
    spec = cache_spec(CFG, 2, 64, "bfloat16", kv_dtype="int8")
    assert str(spec["k"][1]) == "int8" and str(spec["v"][1]) == "int8"
    assert spec["ks"][0] == spec["k"][0][:-1]          # one scale per row
    assert str(spec["ks"][1]) == "bfloat16"
    # int8 + bf16 scales beat bf16 payload bytes per token:
    # D + 2 < 2D for every D > 2
    hd = CFG.head_dim
    assert hd + 2 < 2 * hd


def test_engine_kv_bytes_include_scales(params):
    from repro.serve import ServeEngine
    eng = ServeEngine(params, CFG, RT_INT8, n_slots=2, max_len=32)
    total = sum(int(np.prod(v.shape)) * v.dtype.itemsize
                for k, v in eng.cache.items()
                if k in ("k", "v", "ks", "vs"))
    assert eng.kv_cache_bytes() == total
    scales = sum(int(np.prod(v.shape)) * v.dtype.itemsize
                 for k, v in eng.cache.items() if k in ("ks", "vs"))
    assert scales > 0


def test_paged_budget_int8_rescales_equal_bytes(params):
    """Same byte budget, ~2x pages: D=16 -> (2*16)/(16+2) = 1.78x."""
    from repro.serve import PagedServeEngine
    rt_bf = ModelRuntime(dtype="bfloat16", remat="none", attn_chunk=16,
                         moe_dropless=True)
    rt_q8 = ModelRuntime(dtype="bfloat16", remat="none", attn_chunk=16,
                         moe_dropless=True, kv_dtype="int8")
    kw = dict(n_slots=4, max_len=64, page_size=8, prefix_cache=False)
    bf = PagedServeEngine(params, CFG, rt_bf, **kw)
    q8 = PagedServeEngine(params, CFG, rt_q8, **kw)
    npp = page_count(64, 8)
    assert bf.pages.n_pages == 4 * npp + 1                     # 33
    hd = CFG.head_dim
    assert q8.pages.n_pages == 4 * npp * (2 * hd) // (hd + 2) + 1   # 57
    # the rescaled pool lands at (just under) the bf16 pool's bytes
    assert q8.kv_cache_bytes() <= bf.kv_cache_bytes()
    assert q8.kv_cache_bytes() >= bf.kv_cache_bytes() * 0.9


def test_serve_preflight_matches_engine_budget(params):
    """The capacity gate derives the same pool the engine allocates."""
    from repro.analysis.capacity import serve_preflight
    from repro.serve import PagedServeEngine
    eng = PagedServeEngine(params, CFG, RT_INT8, n_slots=4, max_len=64,
                           page_size=8, prefix_cache=False)
    derived = serve_preflight(CFG, n_slots=4, max_len=64, page_size=8,
                              kv_dtype="int8", dtype="float32")
    pinned = serve_preflight(CFG, n_slots=4, max_len=64, page_size=8,
                              page_budget=eng.pages.n_pages,
                              kv_dtype="int8", dtype="float32")
    assert derived.cache_bytes == pinned.cache_bytes
    assert any("kv_dtype=int8" in n for n in derived.notes)


def test_stale_calibration_rejected(tmp_path):
    """A version-1 table (no quant-op grids) fails loudly, with the
    regeneration command in the message."""
    import json

    from repro.core.analytical.measured import (CalibrationMissing,
                                                load_calibration)
    p = tmp_path / "calibration.json"
    p.write_text(json.dumps({"version": 1, "preset": "ci",
                             "entries": [{"op": "rmsnorm"}]}))
    with pytest.raises(CalibrationMissing, match="schema version 1"):
        load_calibration(str(p))
