"""Compile the Pallas kernels and the paged decode step for a described
TPU v5e chip, at real model widths.

Nothing runs: the chip's compiler is installed here and compiles for a
chip that is described, not attached, so what it refuses (block tiling,
VMEM, HBM fit) is caught without one. The topology is described inside
a module fixture — never at import — because only one process at a time
may load the TPU library.
"""
from __future__ import annotations

import math
import re

import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.kernels import ops
from repro.kernels.dispatch import KernelPolicy
from repro.models import abstract_params, decode_step_paged, page_count
from repro.models.model import ModelRuntime, paged_cache_spec

HBM_BYTES = 16 * 2**30                  # one TPU v5e chip

# minicpm-2b serving widths (the chip smoke's configuration)
SLOTS, MAX_LEN, PAGE = 8, 1024, 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-chip compile is written to the persistent cache
        # but cannot be read back without the chip: keep it out
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        # the ops wrappers pick interpret mode from the (CPU) backend;
        # steer them to Mosaic, and drop traces made under either mode
        mp.setattr(ops, "_interpret", lambda: False)
        jax.clear_caches()
        yield SingleDeviceSharding(topo.devices[0])
        jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", was)


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=sharding)
            for s, d in specs]


@jax.jit
def _ragged(x, w, expert_of_row):
    from repro.kernels.dispatch import implementations
    return implementations("moe_gemm")["ragged"](
        x, w, expert_of_row, n_experts=w.shape[0], sorted_rows=True)


def _kernel_case(name):
    """(jitted op, operand specs, static kwargs) at real widths."""
    bf, i8, f32, i32 = "bfloat16", "int8", "float32", "int32"
    # minicpm-2b: 36 heads (MHA), head_dim 64, d_model 2304
    B, H, D, W = SLOTS, 36, 64, MAX_LEN
    NP = page_count(W, PAGE)
    P = SLOTS * NP + 1
    # one query per kv head: the pool's rows are merged, H * D
    paged = [((B, H, D), bf), ((P, PAGE, H * D), bf),
             ((P, PAGE, H * D), bf), ((B, NP), i32),
             ((B, NP * PAGE), "bool")]
    cases = {
        "paged_decode_attention": (ops.paged_decode_attention, paged, {}),
        # the benchmark cells' pools: starcoder2-3b.code (GQA, 16 slots)
        # and minicpm-2b.longdoc (MHA, 2 slots), 16-token pages, 4096 max
        "paged_decode_attention-starcoder2-3b": (
            ops.paged_decode_attention,
            [((16, 24, 128), bf), ((4097, PAGE, 2, 128), bf),
             ((4097, PAGE, 2, 128), bf), ((16, 256), i32),
             ((16, 256 * PAGE), "bool")], {}),
        "paged_decode_attention-minicpm-2b": (
            ops.paged_decode_attention,
            [((2, H, D), bf), ((385, PAGE, H * D), bf),
             ((385, PAGE, H * D), bf), ((2, 256), i32),
             ((2, 256 * PAGE), "bool")], {}),
        "quant_paged_decode_attention": (
            ops.quant_paged_decode_attention,
            [paged[0], ((P, PAGE, H, D), i8), ((P, PAGE, H, D), i8),
             ((P, PAGE, H), bf), ((P, PAGE, H), bf), paged[3], paged[4]],
            {}),
        "decode_attention": (
            ops.decode_attention,
            [((B, H, D), bf), ((B, W, H, D), bf), ((B, W, H, D), bf),
             ((B, W), "bool")], {}),
        "quant_decode_attention": (
            ops.quant_decode_attention,
            [((B, H, D), bf), ((B, W, H, D), i8), ((B, W, H, D), i8),
             ((B, W, H), bf), ((B, W, H), bf), ((B, W), "bool")], {}),
        "flash_attention": (
            ops.flash_attention,
            [((1, 512, H, D), bf)] * 3, {}),
        "rmsnorm": (ops.rmsnorm, [((B * 512, 2304), bf), ((2304,), f32)],
                    {}),
        "quant_matmul": (
            ops.quant_matmul,
            [((512, 2304), bf), ((2304, 5760), i8), ((5760,), f32)], {}),
        # mamba2-1.3b: 64 SSD heads of 64, d_state 128, chunk 256
        "ssd_scan": (
            ops.ssd_scan,
            [((1, 512, 64, 64), bf), ((1, 512, 64), f32), ((64,), f32),
             ((1, 512, 64, 128), bf), ((1, 512, 64, 128), bf)],
            {"chunk": 256}),
        # qwen2-moe-a2.7b: 60 experts of width 1408 over d_model 2048
        "moe_gemm": (
            ops.moe_grouped_matmul,
            [((512, 2048), bf), ((60, 2048, 1408), bf), ((512,), i32)],
            {"n_experts": 60}),
        # the mellum2-12b-a2.5b.agentcode cell: 32 slots, 32 query and 4
        # kv heads of 128; the window layers' ring of 64 pages a slot
        # (the kernel reads min(context, 1024) rows), the full layers'
        # table of 288
        "paged_decode_attention-mellum2-ring": (
            ops.paged_decode_attention,
            [((32, 32, 128), bf), ((2049, PAGE, 4, 128), bf),
             ((2049, PAGE, 4, 128), bf), ((32, 64), i32),
             ((32, 64 * PAGE), "bool")], {}),
        "paged_decode_attention-mellum2-full": (
            ops.paged_decode_attention,
            [((32, 32, 128), bf), ((9217, PAGE, 4, 128), bf),
             ((9217, PAGE, 4, 128), bf), ((32, 288), i32),
             ((32, 288 * PAGE), "bool")], {}),
        # its 16 held experts of 896 over d_model 2304, rows sorted by
        # expert: a decode step's 32 x 8 rows and a 3584 prefill's
        "moe_gemm-ragged-decode": (
            _ragged, [((256, 2304), bf), ((16, 2304, 896), bf),
                      ((256,), i32)], {}),
        "moe_gemm-ragged-prefill": (
            _ragged, [((28672, 2304), bf), ((16, 2304, 896), bf),
                      ((28672,), i32)], {}),
    }
    return cases[name]


@pytest.mark.parametrize("name", [
    "paged_decode_attention", "paged_decode_attention-starcoder2-3b",
    "paged_decode_attention-minicpm-2b", "quant_paged_decode_attention",
    "decode_attention", "quant_decode_attention", "flash_attention",
    "rmsnorm", "quant_matmul", "ssd_scan", "moe_gemm",
    "paged_decode_attention-mellum2-ring",
    "paged_decode_attention-mellum2-full", "moe_gemm-ragged-decode",
    "moe_gemm-ragged-prefill"])
def test_kernel_lowers_to_mosaic_for_v5e(one_chip, name):
    fn, specs, kw = _kernel_case(name)
    compiled = fn.lower(*_shapes(one_chip, *specs), **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("policy", ["xla", "pallas"])
def test_paged_decode_step_fits_one_v5e(one_chip, policy):
    """One bf16 decode step of full-width minicpm-2b over the chip
    smoke's paged pool fits the chip's HBM, under either kernel policy
    the smoke serves with."""
    cfg = get_arch("minicpm-2b")
    kernels = None
    if policy == "pallas":
        kernels = KernelPolicy(prefill_attention="pallas",
                               paged_decode_attention="pallas",
                               rmsnorm="pallas")
    rt = ModelRuntime(dtype="bfloat16", remat="none", kernels=kernels)
    n_pages = SLOTS * page_count(MAX_LEN, PAGE) + 1
    spec = paged_cache_spec(cfg, SLOTS, n_pages, PAGE, MAX_LEN, "bfloat16")
    cache = {k: jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=one_chip)
             for k, (s, d) in spec.items()}
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        abstract_params(cfg, "bfloat16"))
    tokens = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, c, t: decode_step_paged(
        p, cfg, c, t, rt, page_size=PAGE, window=MAX_LEN))
    compiled = step.lower(params, cache, tokens).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 2**30:.2f} GiB"
    assert ("tpu_custom_call" in compiled.as_text()) == (policy == "pallas")


#: what a loop's plumbing and the kernel's view of the stacked pool do
#: with a pool-sized value: move no bytes
PLUMBING = ("parameter", "get-tuple-element", "tuple", "while", "bitcast")


def _pool_copies(hlo: str, pool) -> list:
    """Instructions of a compiled program that copy, slice or update a
    whole layer's pool (``pool[1:]``, operand or result) or copy the
    whole stack ``pool``: anything but the in-place row writes, the
    kernel's view of the stack and the loop's plumbing."""
    layer = r"\[(1,)?" + ",".join(map(str, pool[1:])) + r"\]"
    stack = "[" + ",".join(map(str, pool)) + "]"
    bad = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+) ([\w-]+)\(", line)
        if m is None or m.group(3) in PLUMBING:
            continue
        name, shape, op = m.groups()
        whole = stack in shape and (op == "copy"
                                    or "dynamic-update-slice" in name)
        if whole or re.search(layer, line):
            bad.append(name)
    return bad


def _donated_step_hlo(one_chip, cfg, slots, n_pages, max_len):
    """The launcher's TPU default decode step of ``cfg`` over a pool of
    ``n_pages`` pages of 16, its cache donated as the engine donates it,
    compiled: (memory analysis, HLO text, the pool's shape)."""
    from repro.launch import serve
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        rt = serve.serving_runtime("bfloat16")
    assert rt.kernel_policy() == KernelPolicy(
        paged_decode_attention="pallas", moe_gemm="ragged")
    spec = paged_cache_spec(cfg, slots, n_pages, PAGE, max_len, "bfloat16")
    cache = {k: jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=one_chip)
             for k, (s, d) in spec.items()}
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        abstract_params(cfg, "bfloat16"))
    tokens = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, c, t: decode_step_paged(
        p, cfg, c, t, rt, page_size=PAGE, window=max_len),
        donate_argnums=(1,))
    compiled = step.lower(params, cache, tokens).compile()
    return compiled.memory_analysis(), compiled.as_text(), spec["kp"][0]


def test_paged_decode_step_fits_one_v5e_by_default(one_chip):
    """The launcher's TPU default (the live-page kernel for paged decode
    attention, XLA elsewhere) compiles one bf16 decode step of
    minicpm-2b over the ``.longdoc`` cell's pool — 2 slots, 385 pages
    of 16, 4096 tokens — within the chip's HBM, reading and writing its
    pools of merged rows in place: no layer's pool is copied."""
    mem, hlo, pool = _donated_step_hlo(one_chip, get_arch("minicpm-2b"),
                                       2, 385, 4096)
    assert pool == (40, 385, PAGE, 36 * 64)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 2**30:.2f} GiB"
    # both pools, bf16, in place
    assert mem.alias_size_in_bytes >= 2 * 2 * math.prod(pool)
    assert "tpu_custom_call" in hlo
    assert _pool_copies(hlo, pool) == []


def test_grouped_decode_step_reads_its_pool_in_place(one_chip):
    """starcoder2-3b over the ``.codegen`` cell's pool (16 slots, 4,097
    pages, grouped heads: rows of kv heads apart) compiles with no copy,
    slice or update of a layer's pool."""
    mem, hlo, pool = _donated_step_hlo(one_chip, get_arch("starcoder2-3b"),
                                       16, 4097, 4096)
    assert pool == (30, 4097, PAGE, 2, 128)
    assert mem.alias_size_in_bytes >= 2 * 2 * math.prod(pool)
    assert _pool_copies(hlo, pool) == []


def test_mixed_kinds_decode_step_fits_one_v5e_by_default(one_chip):
    """The launcher's TPU default compiles one bf16 decode step of
    mellum2-12b-a2.5b, 16 of its 64 experts held, over the
    ``.agentcode`` cell's two cache groups (32 slots, 4608 tokens: 9,217
    pages for the full layers, 2,049 for the window layers' rings), its
    cache donated as the engine donates it, within the chip's HBM."""
    import dataclasses
    from repro.launch import serve
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        rt = serve.serving_runtime("bfloat16")
    cfg = get_arch("mellum2-12b-a2.5b")
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, n_held=16))
    slots, max_len = 32, 4608
    spec = paged_cache_spec(cfg, slots, slots * 288 + 1, PAGE, max_len,
                            "bfloat16")
    assert spec["kpw"][0] == (21, 2049, PAGE, 4, 128)
    assert spec["kp"][0] == (7, 9217, PAGE, 4, 128)
    cache = {k: jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=one_chip)
             for k, (s, d) in spec.items()}
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        abstract_params(cfg, "bfloat16"))
    tokens = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, c, t: decode_step_paged(
        p, cfg, c, t, rt, page_size=PAGE, window=max_len),
        donate_argnums=(1,))
    compiled = step.lower(params, cache, tokens).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 14 * 2**30, f"{total / 2**30:.2f} GiB"
    assert mem.alias_size_in_bytes > 3 * 2**30      # the pools, in place
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert _pool_copies(hlo, spec["kp"][0]) == []
    assert _pool_copies(hlo, spec["kpw"][0]) == []
