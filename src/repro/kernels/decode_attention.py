"""Split-KV decode attention (flash-decoding) — TPU Pallas.

One query token per sequence against a long KV cache. The cache is
split over the grid so every program reduces its own KV range into a
partial (m, l, acc) triple; the tiny cross-split softmax merge runs as
plain XLA in the wrapper. This mirrors the sharded-decode recipe
(kv_seq over `model`) at the single-chip level: parallelism over the
cache length instead of the (single) query.

    grid = (B * Hkv, n_splits)
    per program: q group tile (G, D), kv tile (block_k, D)

Partials come out as (B * Hkv, n_splits, G, ·) with one (G, ·) block
per program: the TPU block tiling takes a block's last two dims only
when they are (8, 128)-aligned or the array's own, so the split axis
stays out of them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref, *,
                   sm_scale: float):
    q = q_ref[0].astype(jnp.float32)                  # (G, D)
    k = k_ref[0].astype(jnp.float32)                  # (bk, D)
    v = v_ref[0].astype(jnp.float32)
    valid = mask_ref[0]                               # (1, bk) int32
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(valid > 0, s, NEG_INF)              # (G, bk)
    m = jnp.max(s, axis=-1, keepdims=True)            # (G, 1)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[0, 0] = acc.astype(o_ref.dtype)             # (G, D)
    m_ref[0, 0] = m
    l_ref[0, 0] = l


def merge_partials(o, m, l, axis: int = 1) -> jax.Array:
    """Combine per-split online-softmax partials (acc, max, sum) along
    the split ``axis`` -> normalized attention output (f32)."""
    m_all = jnp.max(m, axis=axis, keepdims=True)
    w = jnp.exp(m - m_all)
    l_all = jnp.sum(l * w, axis=axis)
    return jnp.sum(o * w, axis=axis) / jnp.maximum(l_all, 1e-30)


def split_partials_specs(G: int, D: int):
    """(out_specs, out_shape factory) of the contiguous split-KV kernels'
    (acc, m, l) partials over grid (B * Hkv, n_splits)."""
    specs = [pl.BlockSpec((1, 1, G, D), lambda bh, s: (bh, s, 0, 0)),
             pl.BlockSpec((1, 1, G, 1), lambda bh, s: (bh, s, 0, 0)),
             pl.BlockSpec((1, 1, G, 1), lambda bh, s: (bh, s, 0, 0))]

    def shapes(BH: int, ns: int):
        return [jax.ShapeDtypeStruct((BH, ns, G, D), jnp.float32),
                jax.ShapeDtypeStruct((BH, ns, G, 1), jnp.float32),
                jax.ShapeDtypeStruct((BH, ns, G, 1), jnp.float32)]

    return specs, shapes


def decode_attention_splitkv(q, k_cache, v_cache, kv_mask, *,
                             block_k: int = 512,
                             interpret: bool = True) -> jax.Array:
    """q: (B, Hq, D); caches (B, W, Hkv, D); kv_mask (B, W) bool."""
    B, Hq, D = q.shape
    W, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    block_k = min(block_k, W)
    Wp = -(-W // block_k) * block_k
    ns = Wp // block_k

    qg = q.reshape(B, Hkv, G, D).reshape(B * Hkv, G, D)
    kt = k_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, W, D)
    vt = v_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, W, D)
    mk = jnp.broadcast_to(kv_mask[:, None, :], (B, Hkv, W)) \
        .reshape(B * Hkv, 1, W).astype(jnp.int32)
    if Wp != W:
        kt = jnp.pad(kt, ((0, 0), (0, Wp - W), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, Wp - W), (0, 0)))
        mk = jnp.pad(mk, ((0, 0), (0, 0), (0, Wp - W)))

    kern = functools.partial(_decode_kernel, sm_scale=1.0 / math.sqrt(D))
    out_specs, out_shape = split_partials_specs(G, D)
    o, m, l = pl.pallas_call(
        kern,
        grid=(B * Hkv, ns),
        in_specs=[
            pl.BlockSpec((1, G, D), lambda bh, s: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, s: (bh, s, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, s: (bh, s, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bh, s: (bh, 0, s)),
        ],
        out_specs=out_specs,
        out_shape=out_shape(B * Hkv, ns),
        interpret=interpret,
    )(qg, kt, vt, mk)

    # merge partials across splits (tiny, plain XLA)
    out = merge_partials(o, m, l)
    return out.reshape(B, Hq, D).astype(q.dtype)
