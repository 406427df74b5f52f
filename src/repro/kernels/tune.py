"""Kernel microbenchmark / autotuner.

    PYTHONPATH=src python -m repro.kernels.tune --preset ci
    PYTHONPATH=src python -m repro.kernels.tune --preset full   # TPU host

For every (arch, shape) cell of the preset, the tuner

1. builds the cell's Workload IR (the same analytic LM front-end the
   DSE consumes) and derives one microbenchmark *case* per dispatch op
   that workload actually contains — the attention / scan / expert-GEMM
   op records supply the per-layer FLOP and byte counts, the
   ModelConfig supplies the geometry;
2. sweeps every registered implementation of that op over the preset's
   block-size grid (``repro.kernels.dispatch.implementations`` — the
   same live table the models dispatch through), timing compiled
   steady-state calls;
3. persists winners + all timings to ``artifacts/kernels/
   calibration.json`` (``repro.artifacts.calibration_path``, honors
   ``REPRO_ARTIFACT_DIR``).

The calibration file closes the analytic<->measured loop: the
``policy`` block maps straight onto a :class:`KernelPolicy`
(``KernelPolicy.from_calibration``), and the per-entry timings feed the
measured accelerator model (``repro.core.analytical.measured``) and the
``kernel_model_error`` benchmark.

Presets mirror the dry-run artifact subsystem: ``ci`` is a smoke grid
over ``smoke_config`` archs with shrunken shapes (minutes, CPU
interpret mode — the *schema/plumbing* check), ``full`` is the
MXU-aligned grid at paper-scale shapes for a real TPU host, where the
timings mean what they say.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.artifacts import calibration_path
from repro.configs import get_arch, get_shape, smoke_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.analytical.measured import (CALIBRATION_VERSION,  # noqa: F401
                                            ENTRY_FIELDS)
from repro.core.workload import Workload, lm_workload
from repro.kernels.dispatch import KERNEL_OPS, implementations


# ===========================================================================
# Presets
# ===========================================================================
@dataclass(frozen=True)
class TunePreset:
    """One scale point of the microbenchmark sweep."""

    name: str
    cells: Tuple[Tuple[str, str], ...]       # (arch, shape) pairs
    shapes: Mapping[str, ShapeConfig]        # possibly shrunken
    grids: Mapping[str, Mapping[str, Tuple[Dict[str, int], ...]]]
    shrink_archs: bool = False
    reps: int = 3
    warmup: int = 1
    # cap on the benchmarked batch (0 = the shape's global batch). The
    # microbench runs on ONE device, so paper-scale cells must time a
    # per-chip batch slice; IR-derived FLOP/byte counts are scaled to
    # the slice so calibration entries stay self-consistent.
    bench_batch: int = 0
    # KV page sizes swept for the paged decode-attention op (one case
    # per size; the block dimension rides the pallas grid's
    # ``pages_per_block``)
    paged_page_sizes: Tuple[int, ...] = (16,)
    description: str = ""

    def arch(self, name: str) -> ModelConfig:
        cfg = get_arch(name)
        return smoke_config(cfg) if self.shrink_archs else cfg

    def shape(self, name: str) -> ShapeConfig:
        return self.shapes[name]

    def grid(self, op: str, impl: str) -> Tuple[Dict[str, int], ...]:
        return tuple(self.grids.get(op, {}).get(impl, ({},)))


CI = TunePreset(
    name="ci",
    cells=(
        ("minicpm-2b", "prefill_32k"),       # dense attention + rmsnorm
        ("minicpm-2b", "decode_32k"),        # split-KV decode attention
        ("mamba2-1.3b", "prefill_32k"),      # SSD scan
        ("qwen2-moe-a2.7b", "prefill_32k"),  # grouped expert GEMM
    ),
    shapes={
        "prefill_32k": ShapeConfig("prefill_32k", 128, 2, "prefill"),
        "decode_32k": ShapeConfig("decode_32k", 128, 4, "decode"),
    },
    grids={
        "prefill_attention": {
            "xla": ({"chunk": 64}, {"chunk": 128}),
            "pallas": ({"block_q": 32, "block_k": 64},
                       {"block_q": 64, "block_k": 128}),
        },
        "decode_attention": {
            "xla": ({},),
            "pallas": ({"block_k": 32}, {"block_k": 64}),
        },
        "paged_decode_attention": {
            "xla": ({},),
            "pallas": ({"pages_per_block": 1}, {"pages_per_block": 2}),
        },
        "rmsnorm": {
            "xla": ({},),
            "pallas": ({"block_rows": 64}, {"block_rows": 128}),
        },
        "ssd_scan": {
            "xla": ({"chunk": 32}, {"chunk": 64}),
            "pallas": ({"chunk": 32}, {"chunk": 64}),
        },
        "moe_gemm": {
            "xla": ({},),
            "pallas": ({"block_m": 16, "block_f": 32},
                       {"block_m": 32, "block_f": 32}),
            "ragged": ({},),
        },
        "quant_matmul": {
            "xla": ({},),
            "pallas": ({"block_t": 32, "block_n": 64},
                       {"block_t": 64, "block_n": 128}),
        },
        "quant_decode_attention": {
            "xla": ({},),
            "pallas": ({"block_k": 32}, {"block_k": 64}),
        },
        "quant_paged_decode_attention": {
            "xla": ({},),
            "pallas": ({"pages_per_block": 1}, {"pages_per_block": 2}),
        },
    },
    shrink_archs=True,
    reps=3,
    warmup=1,
    paged_page_sizes=(8, 16),
    description="smoke grid, smoke archs, shrunken shapes (CPU interpret "
                "mode, minutes) — validates schema + plumbing",
)

FULL = TunePreset(
    name="full",
    cells=(
        ("minicpm-2b", "prefill_32k"),
        ("minicpm-2b", "decode_32k"),
        ("stablelm-12b", "prefill_32k"),
        ("mamba2-1.3b", "prefill_32k"),
        ("qwen2-moe-a2.7b", "prefill_32k"),
        ("mixtral-8x22b", "decode_32k"),
    ),
    shapes={
        "prefill_32k": get_shape("prefill_32k"),
        "decode_32k": get_shape("decode_32k"),
    },
    grids={
        "prefill_attention": {
            "xla": ({"chunk": 512}, {"chunk": 1024}),
            "pallas": ({"block_q": 128, "block_k": 256},
                       {"block_q": 128, "block_k": 512},
                       {"block_q": 256, "block_k": 512}),
        },
        "decode_attention": {
            "xla": ({},),
            "pallas": ({"block_k": 256}, {"block_k": 512},
                       {"block_k": 1024}),
        },
        "paged_decode_attention": {
            "xla": ({},),
            "pallas": ({"pages_per_block": 2}, {"pages_per_block": 4},
                       {"pages_per_block": 8}),
        },
        "rmsnorm": {
            "xla": ({},),
            "pallas": ({"block_rows": 128}, {"block_rows": 256},
                       {"block_rows": 512}),
        },
        "ssd_scan": {
            "xla": ({"chunk": 128}, {"chunk": 256}),
            "pallas": ({"chunk": 128}, {"chunk": 256}),
        },
        "moe_gemm": {
            "xla": ({},),
            "pallas": ({"block_m": 128, "block_f": 512},
                       {"block_m": 256, "block_f": 512}),
            "ragged": ({},),
        },
        "quant_matmul": {
            "xla": ({},),
            "pallas": ({"block_t": 128, "block_n": 256},
                       {"block_t": 128, "block_n": 512},
                       {"block_t": 256, "block_n": 512}),
        },
        "quant_decode_attention": {
            "xla": ({},),
            "pallas": ({"block_k": 256}, {"block_k": 512},
                       {"block_k": 1024}),
        },
        "quant_paged_decode_attention": {
            "xla": ({},),
            "pallas": ({"pages_per_block": 2}, {"pages_per_block": 4},
                       {"pages_per_block": 8}),
        },
    },
    shrink_archs=False,
    reps=10,
    warmup=3,
    bench_batch=4,       # per-chip slice: a 32k-seq global batch of 32
                         # in f32 would blow a single chip's HBM
    paged_page_sizes=(16, 64),
    description="MXU-aligned grid at paper-scale shapes (real TPU host)",
)

TUNE_PRESETS: Dict[str, TunePreset] = {p.name: p for p in (CI, FULL)}


# ===========================================================================
# Case derivation (Workload IR -> microbenchmark shapes)
# ===========================================================================
@dataclass
class BenchCase:
    """One (op, shape) microbenchmark derived from a workload cell."""

    op: str
    arch: str
    shape: str
    kind: str                       # train | prefill | decode
    source_op: Optional[str]        # IR op name the numbers come from
    case: Dict[str, Any]            # geometry (JSON-serializable)
    flops: float                    # per-layer work the timing covers
    bytes: float
    make_args: Callable[[], Tuple[jax.Array, ...]] = field(repr=False,
                                                           default=None)
    # fixed call-site kwargs (causal, n_experts, ...) the grid params
    # are merged over
    kwargs: Dict[str, Any] = field(default_factory=dict)


def _find_op(wl: Workload, pred) -> Optional[Any]:
    for op in wl.ops:
        if pred(op):
            return op
    return None


def cases_for_cell(cfg: ModelConfig, shape: ShapeConfig,
                   bench_batch: int = 0,
                   page_sizes: Sequence[int] = (16,)) -> List[BenchCase]:
    """Derive the microbenchmark cases one workload cell implies.

    The Workload IR decides *which* ops exist (a pure-SSM model yields
    no attention case; a dense model no scan case) and supplies the
    per-layer FLOP/byte counts; the ModelConfig supplies the geometry
    the kernels are invoked at. RMSNorm has no IR op record (the
    analytic profile folds norms into the epilogue), so its counts are
    computed directly from the row geometry.

    ``bench_batch`` caps the benchmarked batch (single-device reality:
    a paper-scale global batch will not fit one chip); the IR op's
    global-batch FLOP/byte counts are scaled by the slice fraction so
    entries stay (work, time)-consistent.
    """
    wl = lm_workload(cfg, shape)
    # int8 twin of the same cell: its op records carry the reduced
    # weight/KV byte counts the quantized cases must be priced at
    wl_q = lm_workload(cfg, shape, weight_dtype="int8", kv_dtype="int8")
    key = jax.random.PRNGKey(0)
    B_wl = shape.global_batch
    B = min(B_wl, bench_batch) if bench_batch else B_wl
    frac = B / B_wl                 # IR counts cover the global batch
    S = shape.seq_len
    d = cfg.d_model
    hd, nq, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    decode = shape.kind == "decode"
    q_tokens = B if decode else B * S
    cases: List[BenchCase] = []

    attn_op = _find_op(wl, lambda o: o.kind == "attention")
    if attn_op is not None and not decode:
        def mk_attn(key=key):
            ks = jax.random.split(key, 3)
            q = jax.random.normal(ks[0], (B, S, nq, hd), jnp.float32)
            k = jax.random.normal(ks[1], (B, S, nkv, hd), jnp.float32)
            v = jax.random.normal(ks[2], (B, S, nkv, hd), jnp.float32)
            return q, k, v

        cases.append(BenchCase(
            "prefill_attention", cfg.name, shape.name, shape.kind,
            attn_op.name,
            {"B": B, "S": S, "Hq": nq, "Hkv": nkv, "D": hd,
             "causal": cfg.causal, "window": cfg.sliding_window},
            attn_op.flops * frac, attn_op.total_bytes * frac, mk_attn,
            kwargs={"causal": cfg.causal, "window": cfg.sliding_window}))

    if attn_op is not None and decode:
        W = shape.kv_len or S
        if cfg.sliding_window:
            W = min(W, cfg.sliding_window)

        def mk_dec(key=key, W=W):
            ks = jax.random.split(key, 3)
            q = jax.random.normal(ks[0], (B, nq, hd), jnp.float32)
            kc = jax.random.normal(ks[1], (B, W, nkv, hd), jnp.float32)
            vc = jax.random.normal(ks[2], (B, W, nkv, hd), jnp.float32)
            mask = jnp.ones((B, W), bool)
            return q, kc, vc, mask

        cases.append(BenchCase(
            "decode_attention", cfg.name, shape.name, shape.kind,
            attn_op.name,
            {"B": B, "W": W, "Hq": nq, "Hkv": nkv, "D": hd},
            attn_op.flops * frac, attn_op.total_bytes * frac, mk_dec))

        # paged twin: same attention work gathered through a page table
        # over a shuffled pool (the serving engine's layout), one case
        # per preset page size — the extra gather indirection is exactly
        # what the measured model must price against contiguous decode
        for ps in page_sizes:
            npp = -(-W // ps)
            n_pool = B * npp + 1          # + the engine's null page 0

            def mk_paged(key=key, ps=ps, npp=npp, n_pool=n_pool, W=W):
                ks = jax.random.split(key, 4)
                q = jax.random.normal(ks[0], (B, nq, hd), jnp.float32)
                kp = jax.random.normal(ks[1], (n_pool, ps, nkv, hd),
                                       jnp.float32)
                vp = jax.random.normal(ks[2], (n_pool, ps, nkv, hd),
                                       jnp.float32)
                pt = jax.random.permutation(
                    ks[3], jnp.arange(1, n_pool, dtype=jnp.int32)
                ).reshape(B, npp)
                mask = jnp.broadcast_to(
                    jnp.arange(npp * ps)[None, :] < W, (B, npp * ps))
                return q, kp, vp, pt, mask

            cases.append(BenchCase(
                "paged_decode_attention", cfg.name, shape.name, shape.kind,
                attn_op.name,
                {"B": B, "W": W, "Hq": nq, "Hkv": nkv, "D": hd,
                 "page_size": ps, "n_pages": n_pool},
                attn_op.flops * frac, attn_op.total_bytes * frac, mk_paged))

        # quantized twins: the same decode attention read from an int8
        # KV cache with per-row bf16 scales — byte counts come from the
        # int8-annotated workload (payload + scale side-band)
        attn_op_q = _find_op(wl_q, lambda o: o.kind == "attention")

        def mk_qdec(key=key, W=W):
            from repro.kernels.quant import quantize_rows
            ks = jax.random.split(key, 3)
            q = jax.random.normal(ks[0], (B, nq, hd), jnp.float32)
            kc = jax.random.normal(ks[1], (B, W, nkv, hd), jnp.float32)
            vc = jax.random.normal(ks[2], (B, W, nkv, hd), jnp.float32)
            k_q, k_s = quantize_rows(kc)
            v_q, v_s = quantize_rows(vc)
            mask = jnp.ones((B, W), bool)
            return q, k_q, v_q, k_s, v_s, mask

        cases.append(BenchCase(
            "quant_decode_attention", cfg.name, shape.name, shape.kind,
            attn_op_q.name,
            {"B": B, "W": W, "Hq": nq, "Hkv": nkv, "D": hd,
             "kv_dtype": "int8"},
            attn_op_q.flops * frac, attn_op_q.total_bytes * frac, mk_qdec))

        ps_q = page_sizes[0]
        npp_q = -(-W // ps_q)
        n_pool_q = B * npp_q + 1

        def mk_qpaged(key=key, ps=ps_q, npp=npp_q, n_pool=n_pool_q, W=W):
            from repro.kernels.quant import quantize_rows
            ks = jax.random.split(key, 4)
            q = jax.random.normal(ks[0], (B, nq, hd), jnp.float32)
            kp = jax.random.normal(ks[1], (n_pool, ps, nkv, hd),
                                   jnp.float32)
            vp = jax.random.normal(ks[2], (n_pool, ps, nkv, hd),
                                   jnp.float32)
            kp_q, kp_s = quantize_rows(kp)
            vp_q, vp_s = quantize_rows(vp)
            pt = jax.random.permutation(
                ks[3], jnp.arange(1, n_pool, dtype=jnp.int32)
            ).reshape(B, npp)
            mask = jnp.broadcast_to(
                jnp.arange(npp * ps)[None, :] < W, (B, npp * ps))
            return q, kp_q, vp_q, kp_s, vp_s, pt, mask

        cases.append(BenchCase(
            "quant_paged_decode_attention", cfg.name, shape.name,
            shape.kind, attn_op_q.name,
            {"B": B, "W": W, "Hq": nq, "Hkv": nkv, "D": hd,
             "page_size": ps_q, "n_pages": n_pool_q, "kv_dtype": "int8"},
            attn_op_q.flops * frac, attn_op_q.total_bytes * frac,
            mk_qpaged))

    scan_op = _find_op(wl, lambda o: o.kind == "scan")
    if scan_op is not None and not decode:
        from repro.models.ssm import ssm_dims
        dims = ssm_dims(cfg)
        nh, hp, N = dims["nh"], dims["hp"], dims["N"]

        def mk_ssd(key=key, nh=nh, hp=hp, N=N):
            ks = jax.random.split(key, 5)
            x = jax.random.normal(ks[0], (B, S, nh, hp), jnp.float32)
            dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
            A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.5)
            Bm = jax.random.normal(ks[3], (B, S, nh, N), jnp.float32)
            Cm = jax.random.normal(ks[4], (B, S, nh, N), jnp.float32)
            return x, dt, A, Bm, Cm

        cases.append(BenchCase(
            "ssd_scan", cfg.name, shape.name, shape.kind, scan_op.name,
            {"B": B, "S": S, "nh": nh, "hp": hp, "N": N,
             "chunk": cfg.ssm.chunk_size},
            scan_op.flops * frac, scan_op.total_bytes * frac, mk_ssd))

    moe_op = _find_op(
        wl, lambda o: o.kind == "matmul" and o.weight_axis == "experts")
    if moe_op is not None and cfg.moe is not None:
        m = cfg.moe
        E, K, f = m.n_experts, m.experts_per_token, m.d_expert
        T = q_tokens * K                       # one row per (token, k) pair

        def mk_moe(key=key, T=T, E=E, f=f):
            ks = jax.random.split(key, 3)
            x = jax.random.normal(ks[0], (T, d), jnp.float32)
            w = jax.random.normal(ks[1], (E, d, f), jnp.float32)
            eor = jax.random.randint(ks[2], (T,), 0, E)
            return x, w, eor

        cases.append(BenchCase(
            "moe_gemm", cfg.name, shape.name, shape.kind, moe_op.name,
            {"T": T, "d": d, "f": f, "E": E},
            # the IR op covers all three expert matmuls (wg/wi/wo); the
            # bench times one grouped GEMM, so it carries a third.
            # Weights are batch-independent — only the activation share
            # scales with the benched batch slice.
            moe_op.flops * frac / 3.0,
            (moe_op.weight_bytes
             + (moe_op.act_in_bytes + moe_op.act_out_bytes) * frac) / 3.0,
            mk_moe,
            kwargs={"n_experts": E}))

    # quant_matmul: the cell's largest non-expert weight matmul with the
    # weight stored int8 + per-output-channel f32 scales. The int8
    # workload's op record supplies the reduced weight bytes; N is
    # recovered from them (int8 => 1 byte/element), so the bench GEMM
    # moves exactly the bytes the entry claims.
    qmm_op = _find_op(
        wl_q, lambda o: o.kind == "matmul" and o.weight_axis == "ffn") \
        or _find_op(
            wl_q, lambda o: o.kind == "matmul" and o.weight_axis == "heads"
            and o.weight_bytes > 0)
    if qmm_op is not None:
        N = max(1, int(round(qmm_op.weight_bytes / d)))

        def mk_qmm(key=key, N=N):
            from repro.kernels.quant import quantize_channels
            ks = jax.random.split(key, 2)
            x = jax.random.normal(ks[0], (q_tokens, d), jnp.float32)
            w = jax.random.normal(ks[1], (d, N), jnp.float32)
            w_q, scale = quantize_channels(w)
            return x, w_q, scale

        cases.append(BenchCase(
            "quant_matmul", cfg.name, shape.name, shape.kind, qmm_op.name,
            {"T": q_tokens, "K": d, "N": N, "weight_dtype": "int8"},
            qmm_op.flops * frac,
            # weights are batch-independent; activations scale with the
            # benched slice (same convention as the moe_gemm case)
            qmm_op.weight_bytes
            + (qmm_op.act_in_bytes + qmm_op.act_out_bytes) * frac,
            mk_qmm))

    # rmsnorm: every model norms q_tokens rows of d — not an IR op
    # (norm FLOPs are folded into the analytic epilogue), so counts are
    # analytic: ~4 flops/element, read + write + scale bytes in f32.
    def mk_norm(key=key):
        ks = jax.random.split(key, 2)
        x = jax.random.normal(ks[0], (q_tokens, d), jnp.float32)
        s = jax.random.normal(ks[1], (d,), jnp.float32)
        return x, s

    cases.append(BenchCase(
        "rmsnorm", cfg.name, shape.name, shape.kind, None,
        {"rows": q_tokens, "d": d},
        4.0 * q_tokens * d, (2.0 * q_tokens * d + d) * 4.0, mk_norm))
    if frac < 1.0:
        # provenance: IR-sourced counts were scaled to the batch slice
        for c in cases:
            if c.source_op is not None:
                c.case["global_batch"] = B_wl
                c.case["batch_scale"] = frac
    return cases


# ===========================================================================
# Timing
# ===========================================================================
def time_impl(fn: Callable, args: Tuple[jax.Array, ...],
              params: Dict[str, int], reps: int, warmup: int,
              fixed_kwargs: Optional[Dict[str, Any]] = None,
              ) -> Dict[str, Any]:
    """Steady-state wall time of one (implementation, params) pair.

    jit-compiles ``fn`` with ``params`` + the case's fixed kwargs closed
    over (static), runs ``warmup`` untimed calls (compile + cache), then
    reports the min / mean over ``reps`` block-until-ready timed calls.
    Only the *tuning* params are recorded — fixed call-site kwargs
    (causal, n_experts, ...) must never leak into a calibrated policy.
    """
    f = jax.jit(functools.partial(fn, **{**(fixed_kwargs or {}), **params}))
    for _ in range(max(1, warmup)):
        jax.block_until_ready(f(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        times.append(time.perf_counter() - t0)
    return {"params": params, "best_s": min(times),
            "mean_s": sum(times) / len(times), "times": times}


def run_case(case: BenchCase, preset: TunePreset) -> Dict[str, Any]:
    """Sweep every implementation x grid point of one case."""
    args = case.make_args()
    impls_out: Dict[str, Any] = {}
    for impl, fn in sorted(implementations(case.op).items()):
        timings = []
        for params in preset.grid(case.op, impl):
            timings.append(time_impl(fn, args, dict(params),
                                     preset.reps, preset.warmup,
                                     fixed_kwargs=case.kwargs))
        best = min(timings, key=lambda t: t["best_s"])
        impls_out[impl] = {"best_params": best["params"],
                           "best_s": best["best_s"], "timings": timings}
    winner = min(impls_out, key=lambda i: impls_out[i]["best_s"])
    return {
        "op": case.op, "arch": case.arch, "shape": case.shape,
        "kind": case.kind, "source_op": case.source_op, "case": case.case,
        "flops": case.flops, "bytes": case.bytes,
        "impls": impls_out, "winner": winner,
        "best_s": impls_out[winner]["best_s"],
    }


def aggregate_policy(entries: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Per-op winning implementation + params, minimizing total time
    across every case the op appeared in (the ``policy`` block
    ``KernelPolicy.from_calibration`` consumes)."""
    policy: Dict[str, Any] = {}
    for op in KERNEL_OPS:
        op_entries = [e for e in entries if e["op"] == op]
        if not op_entries:
            continue
        impls = set.intersection(*(set(e["impls"]) for e in op_entries))
        totals = {i: sum(e["impls"][i]["best_s"] for e in op_entries)
                  for i in impls}
        best = min(totals, key=totals.get)
        # params: from the single slowest case (the one that matters)
        anchor = max(op_entries, key=lambda e: e["impls"][best]["best_s"])
        policy[op] = {"impl": best,
                      "params": anchor["impls"][best]["best_params"],
                      "total_s": totals[best]}
    return policy


# ===========================================================================
# Driver
# ===========================================================================
def run_tuning(preset: TunePreset,
               cells: Optional[Sequence[Tuple[str, str]]] = None,
               reps: Optional[int] = None,
               validate: bool = False) -> Dict[str, Any]:
    """Run the full sweep; returns the calibration payload (not yet
    written).

    ``validate=True`` first runs the static kernel validator
    (``repro.analysis.kernel_validator``) over the same cells x grids
    about to be timed — a calibration that blesses a racy or
    budget-busting block size is worse than none. Findings land in the
    payload's ``validation`` block; error findings raise
    :class:`~repro.kernels.dispatch.KernelValidationError` before any
    timing runs. The CLI turns this on by default (``--no-validate``
    opts out); library callers opt in.
    """
    if reps is not None:
        preset = dataclasses.replace(preset, reps=reps)
    validation: Optional[Dict[str, Any]] = None
    if validate:
        from repro.analysis.kernel_validator import validate_preset
        from repro.kernels.dispatch import KernelValidationError
        findings = validate_preset(preset, cells=cells)
        for f in findings:
            print(f"[tune/{preset.name}] {f.describe()}", file=sys.stderr)
        errors = [f for f in findings if f.severity == "error"]
        validation = {"findings": len(findings), "errors": len(errors),
                      "rules": sorted({f.rule_id for f in findings})}
        if errors:
            raise KernelValidationError(
                f"{len(errors)} kernel-validator errors over the "
                f"{preset.name} grid; not timing broken kernels")
    entries: List[Dict[str, Any]] = []
    for arch_name, shape_name in (cells or preset.cells):
        cfg = preset.arch(arch_name)
        shape = preset.shape(shape_name)
        for case in cases_for_cell(cfg, shape,
                                   bench_batch=preset.bench_batch,
                                   page_sizes=preset.paged_page_sizes):
            t0 = time.time()
            entry = run_case(case, preset)
            entries.append(entry)
            print(f"[tune/{preset.name}] {case.arch}/{case.shape} "
                  f"{case.op}: winner={entry['winner']} "
                  f"best={entry['best_s'] * 1e3:.3f} ms "
                  f"({time.time() - t0:.1f}s sweep)")
    return {
        "version": CALIBRATION_VERSION,
        "preset": preset.name,
        "backend": jax.default_backend(),
        "interpret": jax.default_backend() != "tpu",
        "generated_unix": time.time(),
        "cells": [list(c) for c in (cells or preset.cells)],
        "entries": entries,
        "policy": aggregate_policy(entries),
        "validation": validation,
    }


def write_calibration(payload: Dict[str, Any],
                      out: Optional[str] = None) -> str:
    path = out or calibration_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro.kernels.tune",
                                 description=__doc__.split("\n")[0])
    ap.add_argument("--preset", default="ci", choices=sorted(TUNE_PRESETS),
                    help="ci: smoke grid / smoke shapes (CPU, minutes); "
                         "full: MXU grid at paper scale (TPU host)")
    ap.add_argument("--cells", default=None,
                    help="comma-separated arch/shape overrides, e.g. "
                         "minicpm-2b/prefill_32k,mamba2-1.3b/prefill_32k")
    ap.add_argument("--reps", type=int, default=None,
                    help="override timing repetitions")
    ap.add_argument("--out", default=None,
                    help=f"output path (default {calibration_path()})")
    ap.add_argument("--no-validate", action="store_true",
                    help="skip the static kernel validator that runs "
                         "before timing")
    args = ap.parse_args(argv)

    preset = TUNE_PRESETS[args.preset]
    cells = None
    if args.cells:
        cells = []
        for spec in args.cells.split(","):
            if "/" not in spec:
                print(f"error: cell spec {spec!r} must be arch/shape",
                      file=sys.stderr)
                return 2
            arch, shape = spec.split("/", 1)
            try:
                preset.arch(arch)
            except KeyError as e:
                print(f"error: {e.args[0]}", file=sys.stderr)
                return 2
            if shape not in preset.shapes:
                print(f"error: unknown shape {shape!r} for tune preset "
                      f"{preset.name!r}; available: "
                      f"{sorted(preset.shapes)}", file=sys.stderr)
                return 2
            cells.append((arch, shape))

    from repro.kernels.dispatch import KernelValidationError
    try:
        payload = run_tuning(preset, cells=cells, reps=args.reps,
                             validate=not args.no_validate)
    except KernelValidationError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    path = write_calibration(payload, args.out)
    pol = payload["policy"]
    print(f"\n[tune/{preset.name}] {len(payload['entries'])} entries -> "
          f"{path}")
    for op, choice in sorted(pol.items()):
        print(f"  {op:20s} -> {choice['impl']}"
              f" {choice['params'] or ''}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
