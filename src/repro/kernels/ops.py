"""Public jit'd wrappers for the Pallas kernels.

On a TPU backend the kernels compile through Mosaic and run natively;
on the CPU backend they run in the Pallas interpreter (the correctness
path the tests take). Any other backend is refused rather than
interpreted, so a kernel policy can never run slow Python-level kernels
on an accelerator unnoticed. These wrappers are the registered
``pallas`` implementations in ``repro.kernels.dispatch`` — a
:class:`~repro.kernels.dispatch.KernelPolicy` (``ModelRuntime.
use_kernels`` / ``ModelRuntime.kernels``) selects them over the
pure-XLA model paths per op.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import decode_attention_splitkv
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.paged_attention import paged_decode_attention_splitkv
from repro.kernels.moe_gemm import grouped_gemm_padded, sort_by_expert
from repro.kernels.quant import (quant_decode_attention_splitkv,
                                 quant_matmul_pallas,
                                 quant_paged_decode_attention_splitkv)
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas TPU kernels cannot run on backend "
                       f"{backend!r}: select the xla kernel policy")


def _fit_block(n: int, block: int, align: int = 128) -> int:
    """Largest ``align``-multiple <= ``block`` dividing ``n`` (the TPU
    block tiling's lane rule), or ``n`` itself when none does."""
    for b in range(min(block, n) // align * align, 0, -align):
        if n % b == 0:
            return b
    return n


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 512) -> jax.Array:
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_k",))
def decode_attention(q, k_cache, v_cache, kv_mask, *,
                     block_k: int = 512) -> jax.Array:
    return decode_attention_splitkv(q, k_cache, v_cache, kv_mask,
                                    block_k=block_k,
                                    interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("pages_per_block",))
def paged_decode_attention(q, k_pages, v_pages, page_table, kv_mask, *,
                           pages_per_block: Optional[int] = None
                           ) -> jax.Array:
    return paged_decode_attention_splitkv(q, k_pages, v_pages, page_table,
                                          kv_mask,
                                          pages_per_block=pages_per_block,
                                          interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 128):
    return ssd_scan_pallas(x, dt, A, B, C, chunk=chunk,
                           interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("n_experts", "block_m",
                                             "block_f"))
def moe_grouped_matmul(x, w, expert_of_row, *, n_experts: int,
                       block_m: int = 128, block_f: int = 512) -> jax.Array:
    """x: (T, d); w: (E, d, f); expert_of_row: (T,) -> (T, f)."""
    x_pad, block_expert, inv, _ = sort_by_expert(
        x, expert_of_row, n_experts, block_m)
    out = grouped_gemm_padded(x_pad, w, block_expert,
                              block_f=_fit_block(w.shape[-1], block_f),
                              interpret=_interpret())
    return out[inv]


@functools.partial(jax.jit, static_argnames=("eps", "block_rows"))
def rmsnorm(x, scale, *, eps: float = 1e-6,
            block_rows: int = 256) -> jax.Array:
    return rmsnorm_pallas(x, scale, eps=eps, block_rows=block_rows,
                          interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_t", "block_n"))
def quant_matmul(x, w_q, scale, *, block_t: int = 128,
                 block_n: int = 256) -> jax.Array:
    return quant_matmul_pallas(x, w_q, scale, block_t=block_t,
                               block_n=block_n, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_k",))
def quant_decode_attention(q, k_q, v_q, k_scale, v_scale, kv_mask, *,
                           block_k: int = 512) -> jax.Array:
    return quant_decode_attention_splitkv(
        q, k_q, v_q, k_scale, v_scale, kv_mask, block_k=block_k,
        interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("pages_per_block",))
def quant_paged_decode_attention(q, k_pages, v_pages, k_scales, v_scales,
                                 page_table, kv_mask, *,
                                 pages_per_block: int = 1) -> jax.Array:
    return quant_paged_decode_attention_splitkv(
        q, k_pages, v_pages, k_scales, v_scales, page_table, kv_mask,
        pages_per_block=pages_per_block, interpret=_interpret())
