"""Paged split-KV decode attention — TPU Pallas.

The paged serving engine (``repro.serve.paged``) keeps each layer's KV
cache as one pooled ``(n_pages, page_size, Hkv, D)`` buffer plus a
per-sequence page table; a decode step must read a sequence's pages
*through the table* while reducing them into one attention output.

The float kernel (:func:`paged_decode_attention_splitkv`) reads only a
slot's live pages — those up to its last valid row — straight from the
pool in HBM, several pages per block, with one DMA per page into a
double-buffered VMEM scratch: block i+1's copies are in flight while
block i is scored, and the last block of a slot starts the first of
the next. No page is gathered into HBM and nothing is upcast there.

    grid = (B,)            one slot per step, blocks in a fori_loop
    scalar prefetch        page table, packed row-mask words, live pages

Pages per block follow the page's bytes (:func:`block_pages`). Scores
and the online softmax are float32; probabilities enter P·V unrounded
(:func:`dot_f32`). Two block layouts, by head width:

* grouped heads (``G = Hq / Hkv > 1``): a block is the pool's own
  ``(pages, ps, Hkv, D)``, read as ``(rows * Hkv, D)``; all Hq queries
  score it in one MXU tile ``(Hq, rows * Hkv)`` whose entries across
  heads are masked off, the row mask along lanes (slicing one head's
  rows out of a page costs more than the Hkv-fold MXU work);
* one query per kv head (``G == 1``): a page is read as one
  ``(ps, Hkv * D)`` slab — narrow heads (D 64) would leave a page's
  ``(Hkv, D)`` tiles unaligned — and scored against a block-diagonal
  query, ``(rows, Hkv)``, the row mask along sublanes.

The int8 kernel (``repro.kernels.quant``) keeps the older geometry
shared below (:func:`paged_grid_spec`): grid ``(B, n_splits,
pages_per_block)``, one page per grid step through a BlockSpec whose
index map reads the table, every page of the table visited. The KV mask
rides in SMEM as one bit per row (:func:`page_mask_words`) for both.
The tiny cross-split merge runs as plain XLA in the wrapper.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

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


def paged_grid_spec(B: int, G: int, Hkv: int, D: int, ps: int, ns: int,
                    pb: int, page_operands: int, n_side: int = 0):
    """Grid spec of the int8 paged kernel: scalar prefetch (page table,
    mask words), q, ``page_operands`` (ps, Hkv, D) page blocks,
    ``n_side`` (ps, Hkv) per-row side-band page blocks, then the
    (o, m, l) partials."""
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
    """Split geometry + kernel-layout operands of the int8 paged kernel:
    q as (B, G, Hkv, D), the null-padded table and mask words."""
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




# ===========================================================================
# Float kernel: live pages only, several pages per DMA block
# ===========================================================================
#: Bytes of K (and as many of V) that one block aims to read: pages per
#: block follow the page's size, so a narrow pool and a wide one both
#: move about this much per round of DMAs.
BLOCK_BYTES = 512 * 1024
#: Each page of a block is its own pair of DMAs, unrolled in the kernel:
#: past this many the code and its compile grow and the step slows (on
#: a TPU v5e, starcoder2-3b's 8 KB pages ran 16 to a block faster than
#: 32 or 64).
MAX_BLOCK_PAGES = 16


def block_pages(page_bytes: int, n_pages: int) -> int:
    """Pages per DMA block: the power of two nearest ``BLOCK_BYTES /
    page_bytes``, at least 1 and at most ``MAX_BLOCK_PAGES`` and the
    table's ``n_pages``."""
    want = max(1.0, BLOCK_BYTES / page_bytes)
    return max(1, min(1 << round(math.log2(want)), MAX_BLOCK_PAGES,
                      n_pages))


def live_pages(kv_mask, page_size: int) -> jax.Array:
    """(B,) int32: one past the last logical page of ``kv_mask`` (B,
    NP * ps) that holds a valid row, and at least 1. Every page after it
    is fully masked, so the kernel need not read it."""
    B = kv_mask.shape[0]
    used = jnp.any(kv_mask.reshape(B, -1, page_size), axis=-1)
    n = used.shape[1]
    last = jnp.max(jnp.where(used, jnp.arange(1, n + 1, dtype=jnp.int32),
                             0), axis=1)
    return jnp.maximum(last, 1).astype(jnp.int32)


def _split3(x, dtype):
    """float32 ``x`` as three ``dtype`` terms whose sum is ``x``."""
    hi = x.astype(dtype)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(dtype)
    return hi, mid, (r - mid.astype(jnp.float32)).astype(dtype)


def dot_f32(a, b, contract):
    """``lax.dot_general`` contracting ``contract`` with a float32
    result and no float32 operand rounded: when the dtypes differ, the
    float32 operand is split into three terms of the other's dtype, each
    of whose products is exact; two float32 operands run at HIGHEST."""
    def dot(x, y, precision=None):
        return jax.lax.dot_general(x, y, (contract, ((), ())),
                                   precision=precision,
                                   preferred_element_type=jnp.float32)
    if a.dtype == b.dtype:
        return dot(a, b, jax.lax.Precision.HIGHEST
                   if a.dtype == jnp.float32 else None)
    if a.dtype == jnp.float32:
        hi, mid, lo = _split3(a, b.dtype)
        return dot(hi, b) + dot(mid, b) + dot(lo, b)
    hi, mid, lo = _split3(b, a.dtype)
    return dot(a, hi) + dot(a, mid) + dot(a, lo)


def block_rows_valid(words_ref, b, first, n_pages: int, page_size: int,
                     axis: int) -> jax.Array:
    """Which of the ``n_pages * page_size`` logical rows from page
    ``first`` of slot ``b`` the packed mask words keep, as bool laid
    along ``axis``: (rows, 1) for 0, (1, rows) for 1."""
    nw = -(-page_size // 32)
    R = n_pages * page_size
    shape = (R, 1) if axis == 0 else (1, R)
    r = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    word = jnp.zeros(shape, jnp.int32)
    pos = jnp.zeros(shape, jnp.int32)             # row within its page
    for j in range(n_pages):
        lo = j * page_size
        in_page = (r >= lo) & (r < lo + page_size)
        pos = jnp.where(in_page, r - lo, pos)
        for w in range(nw):
            sel = in_page if nw == 1 else \
                in_page & ((r - lo) >> 5 == w)
            word = jnp.where(sel, words_ref[b, (first + j) * nw + w], word)
    return (jnp.right_shift(word, pos & 31) & 1) > 0


def _online_softmax(s, carry, axis: int):
    """Fold masked f32 scores ``s`` (rows along ``axis``) into (m, l);
    returns the probabilities, the rescale of the old partials and the
    new (m, l)."""
    m, l = carry
    m_new = jnp.maximum(m, jnp.max(s, axis=axis, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    return p, alpha, m_new, l * alpha + jnp.sum(p, axis=axis, keepdims=True)


def _live_paged_kernel(pt_ref, words_ref, live_ref, q_ref, k_hbm, v_hbm,
                       o_ref, m_ref, l_ref, kbuf, vbuf, sems, buf_ref, *,
                       sm_scale: float, pages_per_block: int,
                       page_size: int, grouped: bool):
    b, n_slots = pl.program_id(0), pl.num_programs(0)
    pb, ps = pages_per_block, page_size
    R = pb * ps

    def copies(slot, blk, buf):
        """(live, K copy, V copy) for each page of one block."""
        first = blk * pb
        return [(first + j < live_ref[slot],
                 pltpu.make_async_copy(k_hbm.at[pt_ref[slot, first + j]],
                                       kbuf.at[buf, j], sems.at[0, buf]),
                 pltpu.make_async_copy(v_hbm.at[pt_ref[slot, first + j]],
                                       vbuf.at[buf, j], sems.at[1, buf]))
                for j in range(pb)]

    def start(slot, blk, buf):
        for live, ck, cv in copies(slot, blk, buf):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(slot, blk, buf):
        for live, ck, cv in copies(slot, blk, buf):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    @pl.when(b == 0)
    def _first():
        # a block's pages past the slot's last live page are not copied:
        # their rows are masked, and zeroed scratch keeps them finite
        kbuf[...] = jnp.zeros(kbuf.shape, kbuf.dtype)
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)
        buf_ref[0] = 0
        start(0, 0, 0)

    n_blocks = (live_ref[b] + pb - 1) // pb
    base = buf_ref[0]

    def rows_block(cur, blk, carry, same_head):
        valid = block_rows_valid(words_ref, b, blk * pb, pb, ps, axis=1)
        Hkv = kbuf.shape[-2]
        # lane c of the scores is page row c // Hkv of kv head c % Hkv
        valid = jnp.broadcast_to(valid.astype(jnp.int32)[:, :, None],
                                 (1, R, Hkv)).reshape(1, R * Hkv) > 0
        k = kbuf[cur].reshape(R * Hkv, -1)                # (R * Hkv, D)
        v = vbuf[cur].reshape(R * Hkv, -1)
        s = dot_f32(q_ref[0], k, ((1,), (1,))) * sm_scale
        s = jnp.where(same_head & valid, s, NEG_INF)      # (Hq, R * Hkv)
        o, m, l = carry
        p, alpha, m, l = _online_softmax(s, (m, l), axis=1)
        return o * alpha + dot_f32(p, v, ((1,), (0,))), m, l

    def flat_block(cur, blk, carry, expand):
        valid = block_rows_valid(words_ref, b, blk * pb, pb, ps, axis=0)
        k = kbuf[cur].reshape(R, -1)                      # (R, Hkv * D)
        v = vbuf[cur].reshape(R, -1)
        s = dot_f32(k, q_ref[0], ((1,), (0,))) * sm_scale
        s = jnp.where(valid, s, NEG_INF)                  # (R, Hkv)
        o, m, l = carry
        p, alpha, m, l = _online_softmax(s, (m, l), axis=0)
        # each head's probability over its D lanes, then P·V on the VPU
        pv = dot_f32(p, expand, ((1,), (0,))) * v.astype(jnp.float32)
        o = o * dot_f32(alpha, expand, ((1,), (0,))) \
            + jnp.sum(pv, axis=0, keepdims=True)
        return o, m, l

    if grouped:
        (Hq, D), Hkv = q_ref.shape[1:], kbuf.shape[-2]
        G = Hq // Hkv
        lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, R, Hkv), 2) \
            .reshape(1, R * Hkv)
        row = jax.lax.broadcasted_iota(jnp.int32, (Hq, R * Hkv), 0)
        same_head = (row >= lane_head * G) & (row < lane_head * G + G)
        init = (jnp.zeros((Hq, D), jnp.float32),
                jnp.full((Hq, 1), NEG_INF, jnp.float32),
                jnp.zeros((Hq, 1), jnp.float32))
        compute = functools.partial(rows_block, same_head=same_head)
    else:
        HD, Hkv = q_ref.shape[1], q_ref.shape[2]
        D = HD // Hkv
        head = jax.lax.broadcasted_iota(jnp.int32, (Hkv, HD), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (Hkv, HD), 1)
        expand = ((lane >= head * D) & (lane < head * D + D)) \
            .astype(kbuf.dtype)                           # (Hkv, HD)
        init = (jnp.zeros((1, HD), jnp.float32),
                jnp.full((1, Hkv), NEG_INF, jnp.float32),
                jnp.zeros((1, Hkv), jnp.float32))
        compute = functools.partial(flat_block, expand=expand)

    def body(i, carry):
        cur = jax.lax.rem(base + i, 2)
        nxt = 1 - cur

        @pl.when(i + 1 < n_blocks)
        def _():
            start(b, i + 1, nxt)

        @pl.when((i + 1 == n_blocks) & (b + 1 < n_slots))
        def _():
            start(b + 1, 0, nxt)

        wait(b, i, cur)
        return compute(cur, i, carry)

    o_ref[0], m_ref[0], l_ref[0] = jax.lax.fori_loop(0, n_blocks, body,
                                                     init)
    buf_ref[0] = jax.lax.rem(base + n_blocks, 2)


def paged_decode_attention_splitkv(q, k_pages, v_pages, page_table,
                                   kv_mask, *,
                                   pages_per_block: Optional[int] = None,
                                   interpret: bool = True) -> jax.Array:
    """q: (B, Hq, D); k/v_pages: (P, ps, Hkv, D) pooled page buffers;
    page_table: (B, NP) int32 physical page of each logical page;
    kv_mask: (B, NP * ps) bool over logical rows. ``pages_per_block``
    overrides :func:`block_pages`."""
    B, Hq, D = q.shape
    P, ps, Hkv = k_pages.shape[:3]
    G = Hq // Hkv
    NP = page_table.shape[1]
    pb = pages_per_block or block_pages(
        ps * Hkv * D * k_pages.dtype.itemsize, NP)
    pb = max(1, min(pb, NP))
    NPp = -(-NP // pb) * pb
    # pad the table with the reserved null page; its rows are masked
    pt = jnp.pad(page_table.astype(jnp.int32), ((0, 0), (0, NPp - NP)))
    words = page_mask_words(kv_mask, NPp, ps)
    live = live_pages(kv_mask, ps)
    grouped = G > 1
    if grouped:
        qk = q
        parts = [(B, Hq, D), (B, Hq, 1), (B, Hq, 1)]
        page = (ps, Hkv, D)
    else:
        # block-diagonal query: column h holds head h's q on its D rows
        eye = jnp.eye(Hkv, dtype=q.dtype)
        qk = (q[:, :, :, None] * eye[None, :, None, :]) \
            .reshape(B, Hkv * D, Hkv)
        parts = [(B, 1, Hkv * D), (B, 1, Hkv), (B, 1, Hkv)]
        page = (ps, Hkv * D)
        k_pages = k_pages.reshape(P, ps, Hkv * D)
        v_pages = v_pages.reshape(P, ps, Hkv * D)

    def block(shape):
        return pl.BlockSpec((1,) + shape[1:],
                            lambda b, *_: (b,) + (0,) * (len(shape) - 1))

    kern = functools.partial(_live_paged_kernel,
                             sm_scale=1.0 / math.sqrt(D),
                             pages_per_block=pb, page_size=ps,
                             grouped=grouped)
    o, m, l = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[block(qk.shape), pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[block(s) for s in parts],
            scratch_shapes=[pltpu.VMEM((2, pb) + page, k_pages.dtype),
                            pltpu.VMEM((2, pb) + page, v_pages.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(s, jnp.float32) for s in parts],
        # one slot's last block prefetches the next slot's first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(pt, words, live, qk, k_pages, v_pages)
    # one split: the partials as (B, 1, Hq, ·)
    o, m, l = (x.reshape(B, 1, Hq, -1) for x in (o, m, l))
    return merge_partials(o, m, l).astype(q.dtype)
