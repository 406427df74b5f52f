"""Kernel dispatch layer: KernelPolicy semantics, the use_kernels
regression (pallas path provably taken), XLA-vs-pallas forward/decode/
grad parity on every model family, and eps threading through rmsnorm.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, smoke_config
from repro.kernels import dispatch as D
from repro.kernels.dispatch import (
    KERNEL_OPS,
    KernelPolicy,
    PALLAS_POLICY,
    XLA_POLICY,
    dispatch,
    implementations,
)
from repro.models import decode_step, forward, init_cache, init_params
from repro.models import layers as L
from repro.models.model import ModelRuntime

KEY = jax.random.PRNGKey(0)
B, S = 2, 16

RT_XLA = ModelRuntime(dtype="float32", remat="none", attn_chunk=8,
                      moe_dropless=True)
RT_PALLAS = ModelRuntime(dtype="float32", remat="none", attn_chunk=8,
                         moe_dropless=True, use_kernels=True)


def _params_and_batch(arch):
    cfg = smoke_config(ARCHS[arch])
    params = init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    return cfg, params, toks


# ===========================================================================
# Policy semantics
# ===========================================================================
def test_use_kernels_maps_onto_policy():
    assert RT_XLA.kernel_policy() == XLA_POLICY
    assert RT_PALLAS.kernel_policy() == PALLAS_POLICY
    for op in KERNEL_OPS:
        assert PALLAS_POLICY.impl_for(op) == "pallas"
        assert XLA_POLICY.impl_for(op) == "xla"


def test_explicit_policy_overrides_flag():
    pol = KernelPolicy(rmsnorm="pallas")
    rt = ModelRuntime(use_kernels=True, kernels=pol)
    assert rt.kernel_policy() is pol
    assert rt.kernel_policy().impl_for("prefill_attention") == "xla"


@pytest.mark.parametrize("backend,mesh,given,want", [
    ("tpu", None, None, KernelPolicy(paged_decode_attention="pallas",
                                     moe_gemm="ragged")),
    ("cpu", None, None, XLA_POLICY),
    ("tpu", "mesh", None, XLA_POLICY),
    ("tpu", None, XLA_POLICY, XLA_POLICY),
    ("cpu", None, PALLAS_POLICY, PALLAS_POLICY),
])
def test_serving_runtime_default_policy(monkeypatch, backend, mesh, given,
                                        want):
    """On a TPU with the pool on one device the launcher serves paged
    decode attention with the Pallas kernel, expert layers with grouped
    GEMMs over their rows (``ragged``) and every other op with XLA;
    the CPU and a mesh stay all-XLA; a policy given wins."""
    from repro.launch.serve import serving_runtime
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    rt = serving_runtime("float32", kernels=given, mesh=mesh)
    assert rt.kernel_policy() == want


def test_policy_params_merge_and_hash():
    pol = PALLAS_POLICY.with_params("prefill_attention", block_q=32)
    assert pol.params_for("prefill_attention") == {"block_q": 32}
    pol2 = pol.with_params("prefill_attention", block_k=64)
    assert pol2.params_for("prefill_attention") == {"block_q": 32,
                                                   "block_k": 64}
    hash(pol2)                       # stays usable inside frozen Runtime
    assert pol.params_for("rmsnorm") == {}


def test_policy_from_calibration():
    calib = {"policy": {
        "prefill_attention": {"impl": "pallas",
                              "params": {"block_q": 64, "block_k": 128}},
        "rmsnorm": {"impl": "pallas", "params": {}},
    }}
    pol = KernelPolicy.from_calibration(calib)
    assert pol.prefill_attention == "pallas"
    assert pol.rmsnorm == "pallas"
    assert pol.ssd_scan == "xla"     # unnamed ops default to xla
    assert pol.params_for("prefill_attention") == {"block_q": 64,
                                                   "block_k": 128}


def test_dispatch_unknown_op_and_impl():
    x = jnp.ones((4, 8))
    s = jnp.ones((8,))
    with pytest.raises(KeyError):
        dispatch("not_an_op", None, x, s)
    with pytest.raises(KeyError):
        dispatch("rmsnorm", KernelPolicy(rmsnorm="cuda"), x, s)


# ===========================================================================
# The use_kernels regression: the pallas path is provably taken
# ===========================================================================
@pytest.fixture
def pallas_counters(monkeypatch):
    """Wrap every pallas dispatch-table entry with a call counter."""
    counters = {}
    for op in KERNEL_OPS:
        table = implementations(op)
        orig = table["pallas"]
        c = {"n": 0}

        def make(orig=orig, c=c):
            def counted(*a, **k):
                c["n"] += 1
                return orig(*a, **k)
            return counted

        monkeypatch.setitem(table, "pallas", make())
        counters[op] = c
    return counters


def test_pallas_path_taken_end_to_end(pallas_counters):
    """use_kernels=True must route every hot spot through the pallas
    implementations — the seed's flag was silently ignored."""
    # dense: prefill attention + rmsnorm
    cfg, params, toks = _params_and_batch("minicpm-2b")
    forward(params, cfg, {"tokens": toks}, RT_PALLAS)
    assert pallas_counters["prefill_attention"]["n"] > 0
    assert pallas_counters["rmsnorm"]["n"] > 0
    # dense decode: split-KV decode attention
    cache = init_cache(cfg, B, S, "float32")
    decode_step(params, cfg, cache, toks[:, 0], RT_PALLAS)
    assert pallas_counters["decode_attention"]["n"] > 0
    # ssm: SSD scan
    cfg, params, toks = _params_and_batch("mamba2-1.3b")
    forward(params, cfg, {"tokens": toks}, RT_PALLAS)
    assert pallas_counters["ssd_scan"]["n"] > 0
    # moe (dropless): grouped expert GEMM (three per layer: wg/wi/wo)
    cfg, params, toks = _params_and_batch("qwen2-moe-a2.7b")
    forward(params, cfg, {"tokens": toks}, RT_PALLAS)
    assert pallas_counters["moe_gemm"]["n"] >= 3


def test_xla_policy_never_touches_pallas(pallas_counters):
    for arch in ("minicpm-2b", "mamba2-1.3b", "qwen2-moe-a2.7b"):
        cfg, params, toks = _params_and_batch(arch)
        forward(params, cfg, {"tokens": toks}, RT_XLA)
        cache = init_cache(cfg, B, S, "float32")
        decode_step(params, cfg, cache, toks[:, 0], RT_XLA)
    assert all(c["n"] == 0 for c in pallas_counters.values()), \
        {op: c["n"] for op, c in pallas_counters.items()}


# ===========================================================================
# XLA vs pallas parity (interpret mode) per family
# ===========================================================================
@pytest.mark.parametrize("arch", ["minicpm-2b", "mamba2-1.3b",
                                  "qwen2-moe-a2.7b", "zamba2-2.7b"])
def test_forward_parity(arch):
    cfg, params, toks = _params_and_batch(arch)
    lx, ax = forward(params, cfg, {"tokens": toks}, RT_XLA)
    lp, ap = forward(params, cfg, {"tokens": toks}, RT_PALLAS)
    rel = float(jnp.max(jnp.abs(lx - lp)) / jnp.max(jnp.abs(lx)))
    assert rel < 1e-3, f"{arch}: xla/pallas forward mismatch rel={rel}"
    assert abs(float(ax - ap)) < 1e-5


@pytest.mark.parametrize("arch", ["minicpm-2b", "mamba2-1.3b",
                                  "qwen2-moe-a2.7b"])
def test_decode_parity(arch):
    cfg, params, toks = _params_and_batch(arch)
    cache = init_cache(cfg, B, S, "float32")
    cx, gx = decode_step(params, cfg, cache, toks[:, 0], RT_XLA)
    cp, gp = decode_step(params, cfg, cache, toks[:, 0], RT_PALLAS)
    rel = float(jnp.max(jnp.abs(gx - gp)) / jnp.max(jnp.abs(gx)))
    assert rel < 1e-3, f"{arch}: xla/pallas decode mismatch rel={rel}"


def test_train_grad_parity_through_ref_backward():
    """The pallas kernels are forward-only; dispatch pairs them with the
    xla implementation's VJP, so use_kernels reaches the train path."""
    from repro.models import loss_fn

    cfg, params, toks = _params_and_batch("minicpm-2b")
    batch = {"tokens": toks, "labels": toks}
    rt_x = ModelRuntime(dtype="float32", remat="dots", attn_chunk=8,
                        moe_dropless=True)
    rt_p = ModelRuntime(dtype="float32", remat="dots", attn_chunk=8,
                        moe_dropless=True, use_kernels=True)
    gx = jax.grad(lambda p: loss_fn(p, cfg, batch, rt_x)[0])(params)
    gp = jax.grad(lambda p: loss_fn(p, cfg, batch, rt_p)[0])(params)
    gmax = max(float(jnp.max(jnp.abs(g))) for g in jax.tree.leaves(gx))
    dmax = max(float(jnp.max(jnp.abs(a - b)))
               for a, b in zip(jax.tree.leaves(gx), jax.tree.leaves(gp)))
    assert dmax / gmax < 1e-3, (dmax, gmax)


# ===========================================================================
# eps threading (satellite): one eps, both implementations
# ===========================================================================
@pytest.mark.parametrize("policy", [None, XLA_POLICY, PALLAS_POLICY])
def test_rmsnorm_eps_threads_through_dispatch(policy):
    from repro.kernels import ref

    x = jax.random.normal(KEY, (12, 32), jnp.float32) * 0.01
    s = jax.random.normal(jax.random.fold_in(KEY, 1), (32,), jnp.float32)
    eps = 0.05                        # large enough to dominate tiny x
    out = L.rmsnorm(x, s, eps=eps, policy=policy)
    want = ref.rmsnorm_ref(x, s, eps=eps)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    # the eps genuinely reached the implementation: the default-eps
    # output must differ materially at this magnitude
    default = L.rmsnorm(x, s, policy=policy)
    assert float(jnp.max(jnp.abs(out - default))) > 1e-3
