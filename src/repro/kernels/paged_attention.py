"""Paged split-KV decode attention — TPU Pallas.

The paged serving engine (``repro.serve.paged``) keeps each layer's KV
cache as one pooled ``(n_pages, page_size, Hkv, D)`` buffer plus a
per-sequence page table; a decode step must gather a sequence's pages
*through the table* while reducing them into one attention output.

This extends :func:`~repro.kernels.decode_attention.decode_attention_splitkv`
with a scalar-prefetched page-table gather: the table rides in SMEM
(``pltpu.PrefetchScalarGridSpec``) and every K/V BlockSpec index map
reads it to fetch *physical* pages, so the kernel never materializes a
contiguous copy of the sequence — the page indirection happens in the
block pipeline itself.

    grid = (B, n_splits, pages_per_block)
    per program: q (G, Hkv, D), one physical KV page (page_size, Hkv, D)

A page block spans every kv head, so its last two dims are the pool's
own ``(Hkv, D)`` — the only head-sliced layout the TPU block tiling
accepts without copying the pool. Scores are a VPU multiply + lane
reduction per page row (decode has one query row per head, so the MXU
would idle anyway). The KV mask rides in SMEM as one bit per row
(:func:`page_mask_words`): a per-row VMEM mask block would be padded to
a full tile per page.

The innermost grid dim revisits one (m, l, acc) partial per split
(online softmax across its ``pages_per_block`` pages); the tiny
cross-split merge runs as plain XLA in the wrapper, exactly like the
contiguous split-KV kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import merge_partials

NEG_INF = -1e30


def page_mask_words(kv_mask, n_pages: int, page_size: int) -> jax.Array:
    """Pack a (B, NP * ps) row mask into (B, n_pages * nw) int32 words,
    ``nw = ceil(ps / 32)`` words per page, bit ``r % 32`` of word
    ``r // 32`` set when page row ``r`` is valid. Pages past the mask's
    own are all-invalid."""
    B = kv_mask.shape[0]
    nw = -(-page_size // 32)
    m = kv_mask.reshape(B, -1)
    m = jnp.pad(m, ((0, 0), (0, n_pages * page_size - m.shape[1])))
    m = jnp.pad(m.reshape(B, n_pages, page_size),
                ((0, 0), (0, 0), (0, nw * 32 - page_size)))
    bits = m.reshape(B, n_pages, nw, 32).astype(jnp.uint32) \
        << jnp.arange(32, dtype=jnp.uint32)
    words = jnp.sum(bits, axis=-1, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32).reshape(B, -1)


def page_rows_valid(words_ref, b, page, page_size: int) -> jax.Array:
    """(ps, 1, 1) bool: which rows of ``page`` the packed mask keeps."""
    nw = -(-page_size // 32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (page_size, 1, 1), 0)
    valid = jnp.zeros((page_size, 1, 1), jnp.int32)
    for w in range(nw):
        bit = jnp.right_shift(words_ref[b, page * nw + w], rows & 31) & 1
        valid = jnp.where((rows >> 5) == w, bit, valid)
    return valid > 0


def softmax_update(q, k, v, valid, o, m, l, *, sm_scale: float):
    """Fold one page into a (acc, m, l) online-softmax partial.

    q: (Hkv, D) one query group; k/v: (ps, Hkv, D) f32; valid:
    (ps, 1, 1) bool; o: (Hkv, D), m/l: (Hkv, 1) f32."""
    q = q.astype(jnp.float32)
    s = jnp.sum(k * q[None], axis=-1, keepdims=True) * sm_scale
    s = jnp.where(valid, s, NEG_INF)                      # (ps, Hkv, 1)
    m_new = jnp.maximum(m, jnp.max(s, axis=0))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[None])
    return (o * alpha + jnp.sum(p * v, axis=0), m_new,
            l * alpha + jnp.sum(p, axis=0))


def init_partials(o_ref, m_ref, l_ref):
    o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)


def _paged_decode_kernel(pt_ref, words_ref, q_ref, k_ref, v_ref,
                         o_ref, m_ref, l_ref, *, sm_scale: float,
                         pages_per_block: int, page_size: int):
    b, s, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        init_partials(o_ref, m_ref, l_ref)

    valid = page_rows_valid(words_ref, b, s * pages_per_block + j,
                            page_size)
    k = k_ref[0].astype(jnp.float32)                      # (ps, Hkv, D)
    v = v_ref[0].astype(jnp.float32)
    for g in range(q_ref.shape[1]):
        o_ref[0, 0, g], m_ref[0, 0, g], l_ref[0, 0, g] = softmax_update(
            q_ref[0, g], k, v, valid, o_ref[0, 0, g], m_ref[0, 0, g],
            l_ref[0, 0, g], sm_scale=sm_scale)


def paged_grid_spec(B: int, G: int, Hkv: int, D: int, ps: int, ns: int,
                    pb: int, page_operands: int, n_side: int = 0):
    """Grid spec shared by the float and int8 paged kernels: scalar
    prefetch (page table, mask words), q, ``page_operands`` (ps, Hkv, D)
    page blocks, ``n_side`` (ps, Hkv) per-row side-band page blocks,
    then the (o, m, l) partials."""
    def page(b, s, j, pt, words):
        return (pt[b, s * pb + j], 0, 0, 0)

    def side(b, s, j, pt, words):
        return (pt[b, s * pb + j], 0, 0)

    def part(b, s, j, pt, words):
        return (b, s, 0, 0, 0)

    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, ns, pb),
        in_specs=[pl.BlockSpec((1, G, Hkv, D),
                               lambda b, s, j, pt, words: (b, 0, 0, 0))]
        + [pl.BlockSpec((1, ps, Hkv, D), page)] * page_operands
        + [pl.BlockSpec((1, ps, Hkv), side)] * n_side,
        out_specs=[pl.BlockSpec((1, 1, G, Hkv, D), part),
                   pl.BlockSpec((1, 1, G, Hkv, 1), part),
                   pl.BlockSpec((1, 1, G, Hkv, 1), part)],
    )


def paged_partials_shape(B: int, ns: int, G: int, Hkv: int, D: int):
    return [jax.ShapeDtypeStruct((B, ns, G, Hkv, D), jnp.float32),
            jax.ShapeDtypeStruct((B, ns, G, Hkv, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, ns, G, Hkv, 1), jnp.float32)]


def paged_prologue(q, page_table, kv_mask, ps: int, Hkv: int,
                   pages_per_block: int):
    """Split geometry + kernel-layout operands shared by both paged
    kernels: q as (B, G, Hkv, D), the null-padded table and mask words."""
    B, Hq, D = q.shape
    NP = page_table.shape[1]
    G = Hq // Hkv
    pb = max(1, min(pages_per_block, NP))
    NPp = -(-NP // pb) * pb
    qg = q.reshape(B, Hkv, G, D).transpose(0, 2, 1, 3)
    # pad the table with the reserved null page; its rows are masked
    pt = jnp.pad(page_table.astype(jnp.int32), ((0, 0), (0, NPp - NP)))
    words = page_mask_words(kv_mask, NPp, ps)
    return qg, pt, words, G, pb, NPp // pb


def paged_epilogue(o, m, l, q):
    """Merge split partials -> (B, Hq, D) in q's dtype."""
    out = merge_partials(o, m, l)                     # (B, G, Hkv, D)
    B, Hq, D = q.shape
    return out.transpose(0, 2, 1, 3).reshape(B, Hq, D).astype(q.dtype)


def paged_decode_attention_splitkv(q, k_pages, v_pages, page_table,
                                   kv_mask, *, pages_per_block: int = 1,
                                   interpret: bool = True) -> jax.Array:
    """q: (B, Hq, D); k/v_pages: (P, ps, Hkv, D) pooled page buffers;
    page_table: (B, NP) int32 physical page of each logical page;
    kv_mask: (B, NP * ps) bool over logical rows."""
    B, _, D = q.shape
    ps, Hkv = k_pages.shape[1], k_pages.shape[2]
    qg, pt, words, G, pb, ns = paged_prologue(q, page_table, kv_mask, ps,
                                              Hkv, pages_per_block)
    kern = functools.partial(_paged_decode_kernel,
                             sm_scale=1.0 / math.sqrt(D),
                             pages_per_block=pb, page_size=ps)
    o, m, l = pl.pallas_call(
        kern,
        grid_spec=paged_grid_spec(B, G, Hkv, D, ps, ns, pb, 2),
        out_shape=paged_partials_shape(B, ns, G, Hkv, D),
        interpret=interpret,
    )(pt, words, qg, k_pages, v_pages)
    return paged_epilogue(o, m, l, q)
