"""Attention: GQA with causal / sliding-window / bidirectional masking.

Two execution paths:

* ``chunked_attention`` — pure-JAX online-softmax over KV chunks
  (``lax.scan``). This is the XLA path used on CPU and in the dry-run:
  peak memory is O(S * chunk) instead of O(S^2), which is what lets the
  prefill_32k cells compile with sane per-device byte counts. It is the
  same tiling the Pallas ``flash_attention`` kernel implements in VMEM.
* ``decode_attention`` — one query token against a (possibly circular
  sliding-window) KV cache.

Both are the registered ``xla`` implementations of the
``prefill_attention`` / ``decode_attention`` dispatch ops
(``repro.kernels.dispatch``); the models call through the dispatch
layer, and a :class:`~repro.kernels.dispatch.KernelPolicy` (e.g. from
``ModelRuntime.use_kernels``) flips them to the Pallas kernels.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def naive_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0) -> jax.Array:
    """O(S^2) reference. q: (B,S,Hq,D), k/v: (B,T,Hkv,D) -> (B,S,Hq,D)."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, D)
    scores = jnp.einsum("bshgd,bthd->bhgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(D).astype(jnp.float32)
    qpos = jnp.arange(S) + q_offset
    kpos = jnp.arange(T)
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", p, v.astype(jnp.float32))
    return out.reshape(B, S, Hq, D).astype(q.dtype)


def chunked_attention(q, k, v, *, causal: bool = True, window: int = 0,
                      chunk: int = 512, q_offset: int = 0) -> jax.Array:
    """Online-softmax attention, scanning KV in chunks.

    q: (B, S, Hq, D); k, v: (B, T, Hkv, D). GQA by broadcasting KV heads
    to the full Hq inside each chunk (cheap: one chunk at a time), which
    keeps a single ``heads_full`` dim that recipes can shard; recipes for
    odd head counts shard ``q_seq`` instead (sequence-parallel attention
    — each chip owns its query rows and scans all KV chunks).
    ``q_offset``: absolute position of q[0] (prefill continuation).
    """
    from repro.dist.sharding import constrain

    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    chunk = min(chunk, T)
    n_chunks = -(-T // chunk)
    pad = n_chunks * chunk - T
    if pad:
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        kp, vp = k, v
    kc = kp.reshape(B, n_chunks, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    vc = vp.reshape(B, n_chunks, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)

    qh = constrain(q.astype(jnp.float32),
                   ("batch", "q_seq", "heads_full", None))
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    qpos = jnp.arange(S) + q_offset

    m0 = jnp.full((B, Hq, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hq, S), jnp.float32)
    a0 = jnp.zeros((B, Hq, S, D), jnp.float32)
    m0 = constrain(m0, ("batch", "heads_full", "q_seq"))
    a0 = constrain(a0, ("batch", "heads_full", "q_seq", None))

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, c_idx = xs
        kb = jnp.repeat(kb.astype(jnp.float32), G, axis=2)   # -> Hq heads
        vb = jnp.repeat(vb.astype(jnp.float32), G, axis=2)
        kpos = c_idx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bshd,bthd->bhst", qh, kb) * scale
        s = constrain(s, ("batch", "heads_full", "q_seq", None))
        mask = kpos[None, :] < T            # padding
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhst,bthd->bhsd", p, vb)
        return (m_new, l, acc), None

    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kc, vc, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 2, 1, 3)                          # (B,S,Hq,D)
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, kv_mask) -> jax.Array:
    """One-token decode. q: (B, Hq, D); caches: (B, W, Hkv, D);
    kv_mask: (B, W) bool — which cache slots are valid."""
    B, Hq, D = q.shape
    W, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bwhd->bhgw", qg,
                   k_cache.astype(jnp.float32)) / jnp.sqrt(D)
    s = jnp.where(kv_mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgw,bwhd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, Hq, D).astype(q.dtype)


def paged_decode_attention(q, k_pages, v_pages, page_table,
                           kv_mask) -> jax.Array:
    """One-token decode through a paged KV pool (XLA gather reference).

    q: (B, Hq, D); k/v_pages: (P, ps, Hkv, D) pooled page buffers, or
    (P, ps, Hkv * D) with rows merged (``kernels.paged_attention.
    page_row``); page_table: (B, NP) int32 maps each sequence's logical
    page to a physical pool page; kv_mask: (B, NP * ps) bool over
    logical rows. Rows pointing at unowned pages MUST be masked off by
    the caller — the gather itself reads whatever the table says.
    """
    B, _, D = q.shape
    ps = k_pages.shape[1]
    NP = page_table.shape[1]
    k = k_pages[page_table].reshape(B, NP * ps, -1, D)
    v = v_pages[page_table].reshape(B, NP * ps, -1, D)
    return decode_attention(q, k, v, kv_mask)
