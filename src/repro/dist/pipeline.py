"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

``stage_split`` reshapes a stacked-layer parameter tree ``(L, ...)`` to
``(n_stages, L/n_stages, ...)``; :func:`pipeline_apply` wraps a stage
function into a single-program pipelined schedule built on
``shard_map`` + ``ppermute`` (differentiable: the backward pass is the
reverse pipeline).

Schedule: ``T = n_micro + n_stages - 1`` ticks. At tick ``t`` stage 0
injects microbatch ``t`` (while ``t < n_micro``); every stage applies
its layers to its current activation and forwards the result to the
next stage; the last stage commits microbatch ``t - (n_stages-1)`` to
the output buffer. Bubble fraction = ``(n_stages-1)/T`` — the pipeline
"initial latency" term of the paper's Eq. 2, in pod form.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def stage_split(tree: Any, n_stages: int) -> Any:
    """Reshape every stacked-layer leaf (L, ...) -> (S, L/S, ...)."""

    def split(x):
        L = x.shape[0]
        assert L % n_stages == 0, \
            f"layer count {L} not divisible by {n_stages} stages"
        return x.reshape((n_stages, L // n_stages) + x.shape[1:])

    return jax.tree.map(split, tree)


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   mesh, n_stages: int) -> Callable:
    """Build ``fn(staged_params, x)`` with ``x: (n_micro, mb, ...)`` and
    staged params ``(n_stages, L/n_stages, ...)`` sharded over the
    ``stage`` mesh axis. Returns the pipelined outputs, replicated."""

    def inner(staged, x):
        s = jax.lax.axis_index("stage")
        local = jax.tree.map(lambda w: w[0], staged)   # drop stage dim
        n_micro = x.shape[0]
        ticks = n_micro + n_stages - 1
        state0 = jnp.zeros_like(x[0])
        ybuf0 = jnp.zeros_like(x)
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            state, ybuf = carry
            feed = jax.lax.dynamic_index_in_dim(
                x, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False)
            inp = jnp.where(s == 0, feed, state)
            out = stage_fn(local, inp)
            idx = t - (n_stages - 1)
            commit = jnp.logical_and(s == n_stages - 1, idx >= 0)
            updated = jax.lax.dynamic_update_index_in_dim(
                ybuf, out, jnp.clip(idx, 0, n_micro - 1), axis=0)
            ybuf = jnp.where(commit, updated, ybuf)
            nxt = jax.lax.ppermute(out, "stage", fwd)
            return (nxt, ybuf), None

        (_, ybuf), _ = jax.lax.scan(tick, (state0, ybuf0),
                                    jnp.arange(ticks))
        # only the last stage holds real outputs; psum replicates them
        mask = (s == n_stages - 1).astype(ybuf.dtype)
        return jax.lax.psum(ybuf * mask, "stage")

    return jax.shard_map(inner, mesh=mesh,
                         in_specs=(P("stage"), P()),
                         out_specs=P(), check_vma=False)
