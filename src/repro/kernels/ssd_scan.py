"""Chunked SSD (state-space duality) scan — TPU Pallas.

Mamba-2's chunked algorithm maps naturally onto the MXU: the
intra-chunk term is a masked (L x L) matmul (the "duality" — attention
with a decay mask), and the inter-chunk term is a tiny recurrence over
chunk summaries. Tiling:

    grid = (B * NH, S / chunk)      (chunks sequential)

Per program: x (L, hp), dt (L, 1), B/C (L, N) tiles in VMEM; the
per-head decay A is read from SMEM (a (1, 1) VMEM block of it would
break the TPU block tiling); the running state h (hp, N) lives in f32
VMEM scratch and is carried across the sequential chunk dim — the TPU
analogue of the accumulation buffer in the paper's generic architecture
(intermediate results stay on-chip until all associated calculations
finish).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref,
                h_scr, *, chunk: int, seq_len: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)                  # (L, hp)
    dt = dt_ref[0].astype(jnp.float32)                # (L, 1)
    A = a_ref[pl.program_id(0)]                       # scalar (negative)
    Bm = b_ref[0].astype(jnp.float32)                 # (L, N)
    Cm = c_ref[0].astype(jnp.float32)                 # (L, N)

    # zero padded tail positions via dt -> 0 (decay 1, contribution 0)
    pos = ic * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    dt = jnp.where(pos < seq_len, dt, 0.0)

    dA = dt * A                                       # (L, 1)
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sum as a lower-triangular matmul (Mosaic has no
    # cumsum), at full f32 precision: the decays below exponentiate it
    a_cs = jax.lax.dot_general(tri.astype(jnp.float32), dA,
                               (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)  # (L, 1)

    # intra-chunk: masked decay attention  M[t,s] = C_t.B_s e^{a_t-a_s} dt_s
    diff = a_cs - a_cs.T                              # (L, L)
    decay = jnp.where(tri, jnp.exp(diff), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    M = scores * decay * dt.T                         # (L, L)
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state
    #   y_t += C_t h_prev^T e^{a_t};  (L,N)x(N,hp)
    h = h_scr[...]                                    # (hp, N)
    y += jax.lax.dot_general(Cm * jnp.exp(a_cs), h,
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)

    # state update: h = e^{sum dA} h + sum_s e^{a_L - a_s} dt_s x_s B_s^T
    a_end = jax.lax.slice(a_cs, (chunk - 1, 0), (chunk, 1))    # (1, 1)
    decay_end = jnp.exp(a_end - a_cs)                 # (L, 1)
    xw = x * (dt * decay_end)                         # (L, hp)
    hupd = jax.lax.dot_general(xw, Bm, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    # chunk decay e^{a_L} as a (1, N) row, taken from a_cs's last row so
    # it matches decay_end exactly: Mosaic cannot broadcast a (1, 1)
    # across sublanes and lanes at once
    last = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    a_end_row = jnp.sum(jnp.where(last, a_cs, 0.0) * jnp.ones_like(Bm),
                        axis=0, keepdims=True)
    h_scr[...] = h * jnp.exp(a_end_row) + hupd        # (hp, N)

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ic == pl.num_programs(1) - 1)
    def _finish():
        hout_ref[0] = h_scr[...].astype(hout_ref.dtype)


def ssd_scan_pallas(x, dt, A, B, C, *, chunk: int = 128,
                    interpret: bool = True):
    """x: (b, S, nh, hp); dt: (b, S, nh); A: (nh,); B, C: (b, S, nh, N).
    Returns (y (b, S, nh, hp), final state (b, nh, hp, N))."""
    b, S, nh, hp = x.shape
    N = B.shape[-1]
    chunk = min(chunk, S)
    Sp = -(-S // chunk) * chunk
    nc = Sp // chunk

    def bh(t):                              # (b,S,nh,...) -> (b*nh, S, ...)
        t = jnp.moveaxis(t, 2, 1)
        return t.reshape((b * nh, S) + t.shape[3:])

    xt, Bt, Ct = bh(x), bh(B), bh(C)
    dtt = bh(dt[..., None])
    At = jnp.broadcast_to(A[None, :], (b, nh)).reshape(b * nh) \
        .astype(jnp.float32)
    if Sp != S:
        pad = ((0, 0), (0, Sp - S)) + ((0, 0),)
        xt = jnp.pad(xt, pad)
        Bt, Ct, dtt = (jnp.pad(t, pad) for t in (Bt, Ct, dtt))

    kern = functools.partial(_ssd_kernel, chunk=chunk, seq_len=S)
    y, hout = pl.pallas_call(
        kern,
        grid=(b * nh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hp), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda i, c: (i, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, N), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda i, c: (i, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hp), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, hp, N), lambda i, c: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * nh, Sp, hp), x.dtype),
            jax.ShapeDtypeStruct((b * nh, hp, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hp, N), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, At, Bt, Ct)

    y = y[:, :S].reshape(b, nh, S, hp)
    y = jnp.moveaxis(y, 1, 2)
    h = hout.reshape(b, nh, hp, N)
    return y, h
