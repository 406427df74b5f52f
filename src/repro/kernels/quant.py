"""Quantized kernels: int8 weights and int8 KV with float scale side-bands.

Quantization scheme (one scheme everywhere, so buffers round-trip
between the serving engine, the kernels, and the tests):

* **Per-row symmetric int8** for KV rows: each (token, kv-head) row of
  ``D`` elements gets one scale ``absmax / 127`` (stored bf16 in the
  cache side-bands ``ks``/``vs``). Rows are quantized exactly once, at
  write time — decode never re-quantizes, so paged and contiguous
  caches hold bit-identical payloads for the same tokens.
* **Per-output-channel symmetric int8** for weights: a ``(K, N)``
  weight gets an ``(N,)`` float32 scale vector.

Dequantization is ``q.astype(f32) * scale`` in both cases.

This module hosts the scheme helpers, the XLA reference
implementations, and the Pallas kernels for the three quantized
dispatch ops (``quant_matmul``, ``quant_decode_attention``,
``quant_paged_decode_attention``). The paged reference deliberately
dequantizes the *gathered* pages, never the whole pool — the
``jaxpr-int8-upcast`` static-analysis rule flags implementations that
upcast an entire int8 page pool to f32 inside a decode step.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.decode_attention import (merge_partials,
                                            split_partials_specs)
from repro.kernels.paged_attention import (init_partials, paged_epilogue,
                                           paged_grid_spec,
                                           paged_partials_shape,
                                           paged_prologue, page_rows_valid,
                                           softmax_update)

NEG_INF = -1e30

#: Declared tolerance for max abs logit deviation of the int8-KV path
#: vs the bf16 reference on the smoke-scale parity configs (greedy
#: decode stays token-identical well inside this bound).
QUANT_PARITY_TOL = 0.25


# ===========================================================================
# Scheme helpers
# ===========================================================================
def quantize_rows(x, scale_dtype=jnp.bfloat16):
    """Per-row symmetric int8 over the last axis.

    x: (..., D) float -> (q int8 (..., D), scale ``scale_dtype`` (...,)).
    ``scale`` is ``absmax / 127`` per row; all-zero rows get scale 0.
    """
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = absmax / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    q = jnp.clip(jnp.round(xf * inv[..., None]), -127, 127).astype(jnp.int8)
    return q, scale.astype(scale_dtype)


def dequantize_rows(q, scale):
    """Inverse of :func:`quantize_rows` -> float32 (..., D)."""
    return q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]


def quantize_channels(w):
    """Per-output-channel symmetric int8 for a (K, N) weight.

    Returns (w_q int8 (K, N), scale float32 (N,)).
    """
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=0)
    scale = absmax / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    q = jnp.clip(jnp.round(wf * inv[None, :]), -127, 127).astype(jnp.int8)
    return q, scale


# ===========================================================================
# XLA reference implementations
# ===========================================================================
def quant_matmul_xla(x, w_q, scale, **_):
    """x: (T, K) float; w_q: (K, N) int8; scale: (N,) -> (T, N) x.dtype."""
    acc = jax.lax.dot_general(
        x.astype(jnp.float32), w_q.astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return (acc * scale.astype(jnp.float32)[None, :]).astype(x.dtype)


def quant_decode_attention_xla(q, k_q, v_q, k_scale, v_scale, kv_mask, **_):
    """One-token decode over an int8 contiguous cache.

    q: (B, Hq, D); k_q/v_q: (B, W, Hkv, D) int8;
    k_scale/v_scale: (B, W, Hkv); kv_mask: (B, W) bool.
    """
    from repro.models.attention import decode_attention
    k = dequantize_rows(k_q, k_scale)
    v = dequantize_rows(v_q, v_scale)
    return decode_attention(q, k, v, kv_mask).astype(q.dtype)


def quant_paged_decode_attention_xla(q, k_pages, v_pages, k_scales, v_scales,
                                     page_table, kv_mask, **_):
    """One-token decode through an int8 page pool (gather-then-dequant).

    q: (B, Hq, D); k/v_pages: (P, ps, Hkv, D) int8 pooled buffers;
    k/v_scales: (P, ps, Hkv) per-row scales; page_table: (B, NP) int32;
    kv_mask: (B, NP * ps) bool. Only the *gathered* logical pages are
    dequantized — never the whole pool.
    """
    from repro.models.attention import decode_attention
    B = q.shape[0]
    ps, Hkv, D = k_pages.shape[1:]
    NP = page_table.shape[1]
    k = dequantize_rows(k_pages[page_table],
                        k_scales[page_table]).reshape(B, NP * ps, Hkv, D)
    v = dequantize_rows(v_pages[page_table],
                        v_scales[page_table]).reshape(B, NP * ps, Hkv, D)
    return decode_attention(q, k, v, kv_mask).astype(q.dtype)


# ===========================================================================
# Pallas: quantized matmul
# ===========================================================================
def _quant_matmul_kernel(x_ref, w_ref, s_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)                 # (bt, K)
    w = w_ref[...].astype(jnp.float32)                 # (K, bn)
    s = s_ref[...].astype(jnp.float32)                 # (1, bn)
    acc = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[...] = (acc * s).astype(o_ref.dtype)


def quant_matmul_pallas(x, w_q, scale, *, block_t: int = 128,
                        block_n: int = 256,
                        interpret: bool = True) -> jax.Array:
    """int8-weight matmul; dequant happens per output tile in VMEM."""
    T, K = x.shape
    N = w_q.shape[1]
    block_t = min(block_t, T)
    block_n = min(block_n, N)
    Tp = -(-T // block_t) * block_t
    Np = -(-N // block_n) * block_n
    if Tp != T:
        x = jnp.pad(x, ((0, Tp - T), (0, 0)))
    if Np != N:
        w_q = jnp.pad(w_q, ((0, 0), (0, Np - N)))
        scale = jnp.pad(scale, (0, Np - N))
    out = pl.pallas_call(
        _quant_matmul_kernel,
        grid=(Tp // block_t, Np // block_n),
        in_specs=[
            pl.BlockSpec((block_t, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_t, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Tp, Np), x.dtype),
        interpret=interpret,
    )(x, w_q, scale.astype(jnp.float32).reshape(1, Np))
    return out[:T, :N]


# ===========================================================================
# Pallas: quantized split-KV decode attention (contiguous cache)
# ===========================================================================
def _quant_decode_kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, mask_ref,
                         o_ref, m_ref, l_ref, *, sm_scale: float):
    q = q_ref[0].astype(jnp.float32)                   # (G, D)
    k = k_ref[0].astype(jnp.float32) * ks_ref[0].astype(jnp.float32).T
    v = v_ref[0].astype(jnp.float32) * vs_ref[0].astype(jnp.float32).T
    valid = mask_ref[0]                                # (1, bk) int32
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(valid > 0, s, NEG_INF)               # (G, bk)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[0, 0] = acc.astype(o_ref.dtype)
    m_ref[0, 0] = m
    l_ref[0, 0] = l


def quant_decode_attention_splitkv(q, k_q, v_q, k_scale, v_scale, kv_mask,
                                   *, block_k: int = 512,
                                   interpret: bool = True) -> jax.Array:
    """q: (B, Hq, D); k_q/v_q: (B, W, Hkv, D) int8;
    k_scale/v_scale: (B, W, Hkv); kv_mask: (B, W) bool."""
    B, Hq, D = q.shape
    W, Hkv = k_q.shape[1], k_q.shape[2]
    G = Hq // Hkv
    block_k = min(block_k, W)
    Wp = -(-W // block_k) * block_k
    ns = Wp // block_k

    qg = q.reshape(B, Hkv, G, D).reshape(B * Hkv, G, D)
    kt = k_q.transpose(0, 2, 1, 3).reshape(B * Hkv, W, D)
    vt = v_q.transpose(0, 2, 1, 3).reshape(B * Hkv, W, D)
    kst = k_scale.transpose(0, 2, 1).reshape(B * Hkv, 1, W)
    vst = v_scale.transpose(0, 2, 1).reshape(B * Hkv, 1, W)
    mk = jnp.broadcast_to(kv_mask[:, None, :], (B, Hkv, W)) \
        .reshape(B * Hkv, 1, W).astype(jnp.int32)
    if Wp != W:
        kt = jnp.pad(kt, ((0, 0), (0, Wp - W), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, Wp - W), (0, 0)))
        kst = jnp.pad(kst, ((0, 0), (0, 0), (0, Wp - W)))
        vst = jnp.pad(vst, ((0, 0), (0, 0), (0, Wp - W)))
        mk = jnp.pad(mk, ((0, 0), (0, 0), (0, Wp - W)))

    kern = functools.partial(_quant_decode_kernel,
                             sm_scale=1.0 / math.sqrt(D))
    out_specs, out_shape = split_partials_specs(G, D)
    o, m, l = pl.pallas_call(
        kern,
        grid=(B * Hkv, ns),
        in_specs=[
            pl.BlockSpec((1, G, D), lambda bh, s: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, s: (bh, s, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, s: (bh, s, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bh, s: (bh, 0, s)),
            pl.BlockSpec((1, 1, block_k), lambda bh, s: (bh, 0, s)),
            pl.BlockSpec((1, 1, block_k), lambda bh, s: (bh, 0, s)),
        ],
        out_specs=out_specs,
        out_shape=out_shape(B * Hkv, ns),
        interpret=interpret,
    )(qg, kt, vt, kst, vst, mk)
    return merge_partials(o, m, l).reshape(B, Hq, D).astype(q.dtype)


# ===========================================================================
# Pallas: quantized paged split-KV decode attention
# ===========================================================================
def _quant_paged_decode_kernel(pt_ref, words_ref, q_ref, k_ref, v_ref,
                               ks_ref, vs_ref, o_ref, m_ref, l_ref, *,
                               sm_scale: float, pages_per_block: int,
                               page_size: int):
    b, s, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        init_partials(o_ref, m_ref, l_ref)

    valid = page_rows_valid(words_ref, b, s * pages_per_block + j,
                            page_size)
    # dequantize the gathered page: (ps, Hkv, D) payload * per-row scale
    k = k_ref[0].astype(jnp.float32) \
        * ks_ref[0].astype(jnp.float32)[:, :, None]
    v = v_ref[0].astype(jnp.float32) \
        * vs_ref[0].astype(jnp.float32)[:, :, None]
    for g in range(q_ref.shape[1]):
        o_ref[0, 0, g], m_ref[0, 0, g], l_ref[0, 0, g] = softmax_update(
            q_ref[0, g], k, v, valid, o_ref[0, 0, g], m_ref[0, 0, g],
            l_ref[0, 0, g], sm_scale=sm_scale)


def quant_paged_decode_attention_splitkv(q, k_pages, v_pages, k_scales,
                                         v_scales, page_table, kv_mask, *,
                                         pages_per_block: int = 1,
                                         interpret: bool = True
                                         ) -> jax.Array:
    """q: (B, Hq, D); k/v_pages: (P, ps, Hkv, D) int8 pooled buffers;
    k/v_scales: (P, ps, Hkv); page_table: (B, NP) int32;
    kv_mask: (B, NP * ps) bool. Each program dequantizes exactly one
    gathered physical page."""
    B, _, D = q.shape
    ps, Hkv = k_pages.shape[1], k_pages.shape[2]
    qg, pt, words, G, pb, ns = paged_prologue(q, page_table, kv_mask, ps,
                                              Hkv, pages_per_block)
    kern = functools.partial(_quant_paged_decode_kernel,
                             sm_scale=1.0 / math.sqrt(D),
                             pages_per_block=pb, page_size=ps)
    o, m, l = pl.pallas_call(
        kern,
        grid_spec=paged_grid_spec(B, G, Hkv, D, ps, ns, pb, 2, n_side=2),
        out_shape=paged_partials_shape(B, ns, G, Hkv, D),
        interpret=interpret,
    )(pt, words, qg, k_pages, v_pages, k_scales, v_scales)
    return paged_epilogue(o, m, l, q)
