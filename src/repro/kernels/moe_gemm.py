"""Grouped (ragged) expert GEMM — TPU Pallas, megablocks-style.

Tokens are pre-sorted by expert and each expert's row group is padded to
a block multiple, so every (block_m) row tile belongs to exactly one
expert. A scalar-prefetch array maps row-block -> expert id; the index
map uses it to stream that expert's weight tile — the paper's
paradigm-1 idea (dedicated compute per layer/expert) expressed through
the grid rather than dedicated silicon.

    grid = (n_row_blocks, f / block_f)
    per program: x tile (block_m, d), w tile (d, block_f)
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gemm_kernel(be_ref, x_ref, w_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)                # (bm, d)
    w = w_ref[0].astype(jnp.float32)                  # (d, bf)
    o_ref[...] = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def grouped_gemm_padded(x_pad, w, block_expert, *, block_f: int = 512,
                        interpret: bool = True) -> jax.Array:
    """x_pad: (Tp, d) — rows grouped by expert, groups padded to block_m
    multiples; w: (E, d, f); block_expert: (n_blocks,) int32 mapping each
    row block to its expert. Returns (Tp, f)."""
    Tp, d = x_pad.shape
    E, _, f = w.shape
    nb = block_expert.shape[0]
    block_m = Tp // nb
    block_f = min(block_f, f)
    nf = -(-f // block_f)
    assert nf * block_f == f, "pad f to a block multiple upstream"

    out = pl.pallas_call(
        _gemm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb, nf),
            in_specs=[
                pl.BlockSpec((block_m, d), lambda i, j, be: (i, 0)),
                pl.BlockSpec((1, d, block_f),
                             lambda i, j, be: (be[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((block_m, block_f),
                                   lambda i, j, be: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((Tp, f), x_pad.dtype),
        interpret=interpret,
    )(block_expert, x_pad, w)
    return out


def sort_by_expert(x, expert_of_row, n_experts: int, block_m: int,
                   ) -> Tuple[jax.Array, jax.Array, jax.Array, int]:
    """Sort rows by expert and pad each group to a block_m multiple.

    Returns (x_padded (Tp, d), block_expert (nb,), inverse gather index
    (T,) mapping original row -> padded position, Tp)."""
    T = x.shape[0]
    order = jnp.argsort(expert_of_row)                 # stable
    sizes = jnp.bincount(expert_of_row, length=n_experts)
    padded = -(-sizes // block_m) * block_m            # per-expert slots
    pad_off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(padded).astype(jnp.int32)])
    # destination slot for each sorted row
    csizes = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(sizes).astype(jnp.int32)])
    e_sorted = expert_of_row[order]
    rank_in_e = jnp.arange(T) - csizes[e_sorted]
    dest = pad_off[e_sorted] + rank_in_e
    Tp = int(-(-T // block_m) * block_m + (n_experts - 1) * block_m)
    # static upper bound: every group wastes < block_m slots
    x_pad = jnp.zeros((Tp,) + x.shape[1:], x.dtype).at[dest].set(x[order])
    nb = Tp // block_m
    slot_expert = jnp.sum(
        (jnp.arange(Tp)[:, None] >= pad_off[None, 1:]).astype(jnp.int32),
        axis=-1)                                       # slot -> expert
    # the spare blocks past the last group hold no rows; point them at a
    # real expert, since the TPU bounds-checks the weight DMA (the
    # interpreter silently clamps)
    block_expert = jnp.minimum(slot_expert[::block_m], n_experts - 1)
    inv = jnp.zeros((T,), jnp.int32).at[order].set(dest)
    return x_pad, block_expert.astype(jnp.int32), inv, Tp
