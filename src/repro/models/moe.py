"""Mixture-of-Experts layer: top-k token-choice routing with GShard-style
capacity-bounded einsum dispatch, or grouped GEMMs over routed rows.

The dense dispatch/combine einsums shard cleanly over an ``experts``
logical axis (expert parallelism): per-expert weights live on their
chips and the dispatch einsum lowers to an all-to-all on the expert
axis. Capacity-dropped tokens fall through the residual connection.

A layer may hold only some experts (``MoEConfig.n_held``, expert
parallelism's share of one device): the router still scores all
``n_experts`` and renormalises over the top k, and the layer returns
the part of the result that its own experts give; rows routed to
experts it does not hold add nothing.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import constrain
from repro.kernels.dispatch import KernelPolicy, dispatch, resolve_policy
from repro.models.layers import ParamDef, swiglu


def moe_defs(cfg: ModelConfig, stack: Tuple[int, ...] = ()) -> Dict:
    """Parameter defs for one MoE FFN (optionally layer-stacked)."""
    m = cfg.moe
    d = cfg.d_model
    saxes = ("layers",) * len(stack)
    defs = {
        "router": ParamDef(stack + (d, m.n_experts),
                           saxes + (None, "experts")),
        "wi": ParamDef(stack + (m.held, d, m.d_expert),
                       saxes + ("experts", "embed", "expert_ffn")),
        "wg": ParamDef(stack + (m.held, d, m.d_expert),
                       saxes + ("experts", "embed", "expert_ffn")),
        "wo": ParamDef(stack + (m.held, m.d_expert, d),
                       saxes + ("experts", "expert_ffn", "embed")),
    }
    if m.n_shared_experts:
        ff_sh = m.n_shared_experts * (m.d_shared_expert or m.d_expert)
        defs["shared_wi"] = ParamDef(stack + (d, ff_sh),
                                     saxes + ("embed", "ffn"))
        defs["shared_wg"] = ParamDef(stack + (d, ff_sh),
                                     saxes + ("embed", "ffn"))
        defs["shared_wo"] = ParamDef(stack + (ff_sh, d),
                                     saxes + ("ffn", "embed"))
        defs["shared_gate"] = ParamDef(stack + (d, 1), saxes + (None, None))
    return defs


def moe_ffn(p: Dict[str, jax.Array], x: jax.Array,
            cfg: ModelConfig, dropless: bool = False,
            token_chunk: int = 0,
            policy: Optional[KernelPolicy] = None,
            layer: Optional[jax.Array] = None,
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar, counts (2,)).

    ``counts`` are int32 ``[rows, hit]``: (token, expert) rows routed to
    the held experts, and how many held experts got at least one.

    With ``layer``, ``p`` holds every layer's weights stacked and the
    layer's own are used: the ``ragged`` path reads them in place (its
    groups offset into the stack), the others slice them out.

    ``dropless=True`` sets capacity = T (no token ever dropped) — used
    for decode, where capacity-drop noise would corrupt generation.

    ``token_chunk=Tc > 0`` dispatches in groups of Tc tokens (GShard's
    token groups): the (T, E, C) dispatch einsum costs 2*T*E*C*d with
    C ~ K*T/E, i.e. O(T^2) in one shot — per-group dispatch makes it
    O(T * Tc). This is the §Perf beyond-baseline optimization for the
    MoE cells.

    ``policy`` selects the expert-GEMM implementation: a non-xla
    ``moe_gemm`` choice switches the *dropless* path from the dense
    (T, E, C) dispatch einsums to grouped GEMMs over the per-token top-k
    expert rows: ``ragged`` sorts the rows of held experts by expert and
    drops the others before any product (``_routed_held``); any other
    impl takes every row unsorted (``_routed_grouped``, all experts
    held). Capacity-dropping dispatch keeps the einsum structure
    regardless of policy (the grouped paths have no notion of dropping).
    """
    m = cfg.moe
    B, S, d = x.shape
    pol = resolve_policy(policy)
    impl = pol.impl_for("moe_gemm")
    if dropless and impl == "ragged":
        out, aux, counts = _routed_held(p, x.reshape(B * S, d), cfg, pol,
                                        layer)
        if layer is not None:
            p = {k: v[layer] for k, v in p.items() if k.startswith("shared")}
        return _add_shared(p, x, out.reshape(B, S, d), cfg), aux, counts
    if layer is not None:
        p = jax.tree.map(lambda a: a[layer], p)
    if dropless and impl != "xla":
        out, aux, counts = _routed_grouped(p, x.reshape(B * S, d), cfg, pol)
        return _add_shared(p, x, out.reshape(B, S, d), cfg), aux, counts
    if token_chunk and not dropless and S % token_chunk == 0 \
            and token_chunk < S:
        return _moe_ffn_grouped(p, x, cfg, token_chunk)
    T = B * S
    xt = x.reshape(T, d)
    E, K = m.held, m.experts_per_token
    if dropless:
        cap = T
    else:
        cap = int(math.ceil(K * T / E * m.capacity_factor))
        cap = max(K, min(cap, T))

    out, aux, counts = _routed_core(p, xt, cfg, cap)
    out = out.reshape(B, S, d)
    return _add_shared(p, x, out, cfg), aux, counts


def _route(p: Dict[str, jax.Array], xt: jax.Array, cfg: ModelConfig,
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k token-choice routing shared by every dispatch structure.

    xt: (T, d) -> (gate_vals (T, K) normalized, idx (T, K) int32,
    aux_loss scalar). Both the capacity einsum path and the grouped
    kernel path consume exactly this, so policy choice cannot change
    routing decisions."""
    m = cfg.moe
    E, K = m.n_experts, m.experts_per_token
    logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    gate_vals, idx = jax.lax.top_k(probs, K)                    # (T, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    # aux load-balancing loss (Switch/GShard form)
    me = jnp.mean(probs, axis=0)                                # (E,)
    one_hot_k = jax.nn.one_hot(idx, E, dtype=jnp.float32)       # (T, K, E)
    ce = jnp.mean(jnp.sum(one_hot_k, axis=1), axis=0) / K       # frac routed
    aux = E * jnp.sum(me * ce) * m.router_aux_loss
    return gate_vals, idx, aux


def _counts(sizes: jax.Array) -> jax.Array:
    """int32 [rows, experts hit] from per-held-expert row counts."""
    return jnp.stack([jnp.sum(sizes), jnp.count_nonzero(sizes)]
                     ).astype(jnp.int32)


def _routed_held(p: Dict[str, jax.Array], xt: jax.Array, cfg: ModelConfig,
                 policy: KernelPolicy, layer: Optional[jax.Array] = None,
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dropless expert compute over the held experts' rows only.

    Routing (``moe_route`` scope): softmax over all experts in float32,
    top k, renormalised; the (token, k) rows routed to held experts are
    sorted by expert, the others go last and belong to no group, so the
    three SwiGLU products (``moe_gemm``, grouped: work scales with the
    routed rows) never compute them. The combine gathers each token's
    rows back and sums them by gate weight. With ``layer`` the expert
    weights are every layer's, stacked: they are read as one set of
    groups (a free reshape) and this layer's rows keyed into its own,
    so no layer's weights are copied out of the stack."""
    m = cfg.moe
    T, d = xt.shape
    Eh, K = m.held, m.experts_per_token
    ws = [p[n].astype(xt.dtype) for n in ("wg", "wi", "wo")]
    first, groups = 0, Eh
    if layer is not None:
        p = dict(p, router=p["router"][layer])
        first, groups = layer * Eh, ws[0].shape[0] * Eh
        ws = [w.reshape((groups,) + w.shape[2:]) for w in ws]
    with jax.named_scope("moe_route"):
        gate_vals, idx, aux = _route(p, xt, cfg)
        local = idx.reshape(T * K)
        held = local < Eh
        key = jnp.where(held, local + first, groups).astype(jnp.int32)
        order = jnp.argsort(key, stable=True)                  # (T*K,)
        key_sorted = key[order]
        x_sorted = xt[order // K]
        sizes = jnp.bincount(jnp.where(held, local, Eh), length=Eh)
    kw = dict(n_experts=groups, sorted_rows=True)
    g = dispatch("moe_gemm", policy, x_sorted, ws[0], key_sorted, **kw)
    u = dispatch("moe_gemm", policy, x_sorted, ws[1], key_sorted, **kw)
    y = dispatch("moe_gemm", policy, swiglu(g, u), ws[2], key_sorted, **kw)
    with jax.named_scope("moe_route"):
        y = y[jnp.argsort(order)].reshape(T, K, d)             # unsort
        gates = jnp.where(held.reshape(T, K), gate_vals, 0.0)
        out = jnp.sum(y * gates[..., None].astype(y.dtype), axis=1)
    return constrain(out, ("tokens", "embed")), aux, _counts(sizes)


def _routed_grouped(p: Dict[str, jax.Array], xt: jax.Array,
                    cfg: ModelConfig, policy: KernelPolicy,
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dropless expert compute as grouped GEMMs over (token, k) rows.

    Each token is replicated K times (one row per chosen expert); the
    three expert matmuls (wg, wi, wo) run through the ``moe_gemm``
    dispatch op — the megablocks-style structure the Pallas grouped
    kernel implements — and the K partial outputs are gate-combined.
    Mathematically identical to the dropless capacity einsum. Every
    expert has to be held here."""
    m = cfg.moe
    T, d = xt.shape
    E, K = m.n_experts, m.experts_per_token
    if m.held != E:
        raise ValueError(
            f"{cfg.name}: holds {m.held} of {E} experts; the unsorted "
            f"grouped path needs them all (use moe_gemm='ragged')")

    gate_vals, idx, aux = _route(p, xt, cfg)
    x_rep = jnp.repeat(xt, K, axis=0)                          # (T*K, d)
    eor = idx.reshape(T * K).astype(jnp.int32)                 # row -> expert

    wg = p["wg"].astype(xt.dtype)
    wi = p["wi"].astype(xt.dtype)
    wo = p["wo"].astype(xt.dtype)
    g = dispatch("moe_gemm", policy, x_rep, wg, eor, n_experts=E)
    u = dispatch("moe_gemm", policy, x_rep, wi, eor, n_experts=E)
    h = swiglu(g, u)                                           # (T*K, f)
    y = dispatch("moe_gemm", policy, h, wo, eor, n_experts=E)  # (T*K, d)
    y = y.reshape(T, K, d) * gate_vals[..., None].astype(y.dtype)
    out = jnp.sum(y, axis=1)
    sizes = jnp.bincount(eor, length=E)
    return constrain(out, ("tokens", "embed")), aux, _counts(sizes)


def _routed_core(p: Dict[str, jax.Array], xt: jax.Array, cfg: ModelConfig,
                 cap: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Capacity-based dispatch for one token group. xt: (T, d).

    Experts are indexed among the held ones: a row routed to an expert
    held elsewhere has an all-zero one-hot, so it is neither dispatched
    nor combined."""
    m = cfg.moe
    T, d = xt.shape
    E, K = m.held, m.experts_per_token

    gate_vals, idx, aux = _route(p, xt, cfg)
    one_hot_k = jax.nn.one_hot(idx, E, dtype=jnp.float32)       # (T, K, E)

    # capacity-bounded positions: for each (token, k) slot, its position
    # within the chosen expert's buffer. For small token groups this is
    # a strictly-lower-triangular matmul (prior-slot count) — MXU work
    # instead of a sequential prefix scan; large single-group dispatch
    # keeps the O(T) cumsum.
    flat_choice = one_hot_k.reshape(T * K, E)
    if T * K <= 16384:
        tril = jnp.tril(jnp.ones((T * K, T * K), jnp.float32), k=-1)
        pos_in_e = tril @ flat_choice
    else:
        pos_in_e = (jnp.cumsum(flat_choice, axis=0) - flat_choice)
    pos_in_e = jnp.sum(pos_in_e * flat_choice, axis=-1).reshape(T, K)
    keep = pos_in_e < cap
    gate_vals = gate_vals * keep

    # dispatch (T, E, C) one-hot — built sparsely per k then summed
    pos_clip = jnp.minimum(pos_in_e, cap - 1).astype(jnp.int32)
    pos_oh = jax.nn.one_hot(pos_clip, cap, dtype=xt.dtype)      # (T, K, C)
    disp = jnp.einsum("tke,tkc->tec",
                      one_hot_k.astype(xt.dtype) * keep[..., None], pos_oh)
    disp = constrain(disp, ("tokens", "experts", "capacity"))
    comb = jnp.einsum("tke,tkc,tk->tec",
                      one_hot_k.astype(xt.dtype),
                      pos_oh, gate_vals.astype(xt.dtype))
    comb = constrain(comb, ("tokens", "experts", "capacity"))

    xe = jnp.einsum("tec,td->ecd", disp, xt)
    xe = constrain(xe, ("experts", "capacity", "embed"))
    h = swiglu(jnp.einsum("ecd,edf->ecf", xe, p["wg"].astype(xe.dtype)),
               jnp.einsum("ecd,edf->ecf", xe, p["wi"].astype(xe.dtype)))
    h = constrain(h, ("experts", "capacity", "expert_ffn"))
    ye = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(h.dtype))
    ye = constrain(ye, ("experts", "capacity", "embed"))
    out = jnp.einsum("tec,ecd->td", comb, ye)
    out = constrain(out, ("tokens", "embed"))
    sizes = jnp.sum(one_hot_k, axis=(0, 1))
    return out, aux, _counts(sizes)


def _moe_ffn_grouped(p: Dict[str, jax.Array], x: jax.Array,
                     cfg: ModelConfig, token_chunk: int,
                     ) -> Tuple[jax.Array, jax.Array]:
    """GShard token groups: dispatch each (Tc)-token group separately.

    Grouping along seq keeps the leading (batch-derived) dim sharded over
    data; the per-group capacity C = ceil(K*Tc/E * cf) shrinks the
    dispatch/combine einsums from O(T * (K*T/E) * d) to O(T * Tc * K * d).
    """
    m = cfg.moe
    B, S, d = x.shape
    E, K = m.held, m.experts_per_token
    cap = int(math.ceil(K * token_chunk / E * m.capacity_factor))
    cap = max(K, min(cap, token_chunk))
    xg = x.reshape(B * (S // token_chunk), token_chunk, d)
    out, aux, counts = jax.vmap(
        lambda xt: _routed_core(p, xt, cfg, cap))(xg)
    out = out.reshape(B, S, d)
    return (_add_shared(p, x, out, cfg), jnp.mean(aux),
            jnp.sum(counts, axis=0).astype(jnp.int32))


def _add_shared(p, x, out, cfg: ModelConfig) -> jax.Array:
    m = cfg.moe
    if not m.n_shared_experts:
        return out
    B, S, d = x.shape
    hs = swiglu(x @ p["shared_wg"].astype(x.dtype),
                x @ p["shared_wi"].astype(x.dtype))
    ys = hs @ p["shared_wo"].astype(x.dtype)
    sg = jax.nn.sigmoid(
        (x.astype(jnp.float32) @ p["shared_gate"].astype(jnp.float32))
    ).astype(x.dtype)
    return out + sg * ys
