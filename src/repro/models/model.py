"""Model assembly: one functional LM covering all assigned families.

* dense / moe / vlm / audio  — stacked transformer blocks (lax.scan)
* ssm                        — stacked Mamba-2 blocks
* hybrid (Zamba2)            — Mamba-2 backbone, a *shared* transformer
                               block applied every ``shared_attn_period``
                               layers, alternating between
                               ``n_shared_attn_blocks`` physical blocks

Parameters are a pytree of f32 master weights; per-layer weights are
stacked on a leading ``layers`` axis and scanned. Activations run in
``ModelRuntime.dtype``. Sharding is expressed through logical axis names
(``repro.dist.sharding``); the same code runs unsharded CPU smoke tests
and the 512-chip dry-run.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import constrain
from repro.kernels.dispatch import KernelPolicy, dispatch
from repro.kernels.paged_attention import page_row
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.layers import ParamDef, norm, norm_defs, swiglu


@dataclass(frozen=True)
class ModelRuntime:
    """Training/serving-time knobs (not part of the architecture)."""

    dtype: str = "bfloat16"
    remat: str = "dots"          # none | dots | full
    attn_chunk: int = 512
    use_kernels: bool = False    # all-Pallas shorthand (see kernel_policy)
    moe_dropless: bool = False   # capacity = T (prefill consistency/serving)
    moe_chunk: int = 0           # GShard token-group size (0 = one group)
    unroll_layers: bool = False  # fully unroll layer scans (cost probes)
    # KV-cache storage precision. None (default) stores KV at the
    # activation ``dtype``; a float dtype ("bfloat16" under a float32
    # runtime) halves KV bytes by plain casting; "int8" quantizes
    # per-(token, head) symmetric with bf16 scale side-bands "ks"/"vs"
    # (rows quantize once at write time).
    kv_dtype: Optional[str] = None
    # Per-op kernel selection. None defers to ``use_kernels``; an explicit
    # policy (e.g. tuned per-op winners from kernels/tune.py calibration)
    # overrides the bool entirely.
    kernels: Optional[KernelPolicy] = None

    def kernel_policy(self) -> KernelPolicy:
        """The resolved per-op implementation policy every model path
        dispatches through (``use_kernels`` maps onto all-pallas)."""
        if self.kernels is not None:
            return self.kernels
        return KernelPolicy.from_flag(self.use_kernels)


# ===========================================================================
# Parameter definitions
# ===========================================================================
def _attn_defs(cfg: ModelConfig, n: int) -> Dict[str, Any]:
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    s = (n,)
    sx = ("layers",)
    defs: Dict[str, Any] = {
        "ln1": {k: ParamDef(s + v.shape, sx + v.axes, v.init)
                for k, v in norm_defs(d, cfg.norm).items()},
        "wq": ParamDef(s + (d, nq * hd), sx + ("embed", "heads")),
        "wk": ParamDef(s + (d, nkv * hd), sx + ("embed", "kv_heads")),
        "wv": ParamDef(s + (d, nkv * hd), sx + ("embed", "kv_heads")),
        "wo": ParamDef(s + (nq * hd, d), sx + ("heads", "embed")),
        "ln2": {k: ParamDef(s + v.shape, sx + v.axes, v.init)
                for k, v in norm_defs(d, cfg.norm).items()},
    }
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef(s + (hd,), sx + (None,), "ones")
        defs["k_norm"] = ParamDef(s + (hd,), sx + (None,), "ones")
    if cfg.moe is not None:
        defs["moe"] = MOE.moe_defs(cfg, stack=s)
    elif cfg.d_ff:
        if cfg.mlp == "swiglu":
            defs["wg"] = ParamDef(s + (d, cfg.d_ff), sx + ("embed", "ffn"))
        defs["wi"] = ParamDef(s + (d, cfg.d_ff), sx + ("embed", "ffn"))
        defs["wo2"] = ParamDef(s + (cfg.d_ff, d), sx + ("ffn", "embed"))
    return defs


def param_defs(cfg: ModelConfig) -> Dict[str, Any]:
    d, v = cfg.d_model, cfg.vocab_size
    defs: Dict[str, Any] = {
        "embed": ParamDef((v, d), ("vocab", "embed"), "embed"),
        "final_norm": norm_defs(d, cfg.norm),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((d, v), ("embed", "vocab"))
    fam = cfg.family
    if fam == "ssm":
        defs["blocks"] = {
            "ssm": SSM.ssm_defs(cfg, stack=(cfg.n_layers,)),
            "ln": {k: ParamDef((cfg.n_layers,) + p.shape,
                               ("layers",) + p.axes, p.init)
                   for k, p in norm_defs(d, cfg.norm).items()},
        }
    elif fam == "hybrid":
        defs["blocks"] = {
            "ssm": SSM.ssm_defs(cfg, stack=(cfg.n_layers,)),
            "ln": {k: ParamDef((cfg.n_layers,) + p.shape,
                               ("layers",) + p.axes, p.init)
                   for k, p in norm_defs(d, cfg.norm).items()},
        }
        defs["shared"] = _attn_defs(cfg, cfg.n_shared_attn_blocks)
    else:
        defs["blocks"] = _attn_defs(cfg, cfg.n_layers)
    return defs


def init_params(key: jax.Array, cfg: ModelConfig):
    return L.init_from_defs(param_defs(cfg), key)


def axes_tree(cfg: ModelConfig):
    return L.axes_from_defs(param_defs(cfg))


def abstract_params(cfg: ModelConfig, dtype: Optional[str] = None):
    """ShapeDtypeStruct tree; dtype override casts everything (e.g. bf16
    inference weights for the serving dry-runs)."""
    tree = L.abstract_from_defs(param_defs(cfg))
    if dtype is not None:
        tree = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(dtype)), tree)
    return tree


# ===========================================================================
# Blocks
# ===========================================================================
@jax.named_scope("mlp")
def _mlp(p: Dict[str, jax.Array], h: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.mlp == "swiglu":
        z = swiglu(h @ p["wg"].astype(h.dtype), h @ p["wi"].astype(h.dtype))
    else:
        z = jax.nn.gelu(h @ p["wi"].astype(h.dtype))
    z = constrain(z, ("batch", "seq", "ffn"))
    return z @ p["wo2"].astype(h.dtype)


def _attn_proj(p, h, cfg, policy=None):
    B, S, _ = h.shape
    hd = cfg.head_dim
    q = (h @ p["wq"].astype(h.dtype)).reshape(B, S, cfg.n_heads, hd)
    k = (h @ p["wk"].astype(h.dtype)).reshape(B, S, cfg.n_kv_heads, hd)
    v = (h @ p["wv"].astype(h.dtype)).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = L.rmsnorm(q, p["q_norm"], policy=policy)
        k = L.rmsnorm(k, p["k_norm"], policy=policy)
    return q, k, v


def _kind_window(cfg: ModelConfig, kind: Optional[str]) -> int:
    """Attention window of a layer of ``kind`` (0 = full); ``None`` is a
    model of one kind, windowed when ``sliding_window`` is set."""
    if kind == "full":
        return 0
    return cfg.sliding_window


def attn_block(p: Dict[str, Any], x: jax.Array, positions: jax.Array,
               cfg: ModelConfig, rt: ModelRuntime,
               kind: Optional[str] = None,
               ) -> Tuple[jax.Array, jax.Array, Tuple[jax.Array, jax.Array]]:
    """Pre-norm attention + FFN block. Returns (x, aux_loss, (k, v)).

    k/v are post-RoPE — exactly what the decode cache stores; callers
    that don't prefill simply drop them (XLA dead-code-eliminates).
    ``kind`` ("window" / "full") is the layer's attention kind in a model
    of mixed kinds."""
    pol = rt.kernel_policy()
    h = norm(x, p["ln1"], cfg.norm, policy=pol)
    q, k, v = _attn_proj(p, h, cfg, policy=pol)
    q, k = L.apply_rope(q, k, positions, cfg, kind)
    q = constrain(q, ("batch", "seq", "heads", "head_dim"))
    k = constrain(k, ("batch", "seq", "kv_heads", "head_dim"))
    o = dispatch("prefill_attention", pol, q, k, v, causal=cfg.causal,
                 window=_kind_window(cfg, kind), chunk=rt.attn_chunk)
    o = o.reshape(x.shape[0], x.shape[1], -1)
    x = x + o @ p["wo"].astype(x.dtype)
    x = constrain(x, ("batch", "seq", "embed"))

    h2 = norm(x, p["ln2"], cfg.norm, policy=pol)
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe is not None:
        y, aux, _ = MOE.moe_ffn(p["moe"], h2, cfg, dropless=rt.moe_dropless,
                                token_chunk=rt.moe_chunk, policy=pol)
    else:
        y = _mlp(p, h2, cfg)
    x = x + y
    return constrain(x, ("batch", "seq", "embed")), aux, (k, v)


def mamba_block(p: Dict[str, Any], x: jax.Array, cfg: ModelConfig,
                rt: ModelRuntime,
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Returns (x, {'conv','ssm'} final states for prefill handoff)."""
    pol = rt.kernel_policy()
    h = norm(x, p["ln"], cfg.norm, policy=pol)
    y, state = SSM.ssm_block(p["ssm"], h, cfg, policy=pol)
    return constrain(x + y, ("batch", "seq", "embed")), state


# ===========================================================================
# Forward
# ===========================================================================
def _default_positions(cfg: ModelConfig, B: int, S: int,
                       offset: int = 0) -> jax.Array:
    pos = jnp.arange(S, dtype=jnp.int32)[None, :] + offset
    pos = jnp.broadcast_to(pos, (B, S))
    if cfg.rope == "mrope":
        return jnp.broadcast_to(pos[None], (3, B, S))
    return pos


def _embed_in(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
              rt: ModelRuntime) -> jax.Array:
    if "embeds" in batch:
        x = batch["embeds"].astype(rt.dtype)
    else:
        x = params["embed"].astype(rt.dtype)[batch["tokens"]]
    return constrain(x, ("batch", "seq", "embed"))


def _unembed(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        w = params["embed"].astype(x.dtype).T
    else:
        w = params["lm_head"].astype(x.dtype)
    logits = x @ w
    return constrain(logits, ("batch", "seq", "vocab"))


def _maybe_remat(fn, rt: ModelRuntime):
    if rt.remat == "none":
        return fn
    if rt.remat == "full":
        return jax.checkpoint(fn,
                              policy=jax.checkpoint_policies.nothing_saveable)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)


def _scan_blocks(params, cfg: ModelConfig, x, positions, rt: ModelRuntime):
    """Scan the layer stack; returns (x, aux, per-layer cache material)."""
    fam = cfg.family
    zero = jnp.zeros((), jnp.float32)
    if fam == "ssm":
        def body_fn(xp, xs):
            x2, state = mamba_block(xs, xp, cfg, rt)
            return x2, zero, state

        body = _maybe_remat(body_fn, rt)

        def body_scan(carry, xs):
            x_, aux_ = carry
            x2, a, state = body(x_, xs)
            return (x2, aux_ + a), state

        (x, aux), states = jax.lax.scan(body_scan, (x, zero),
                                        params["blocks"],
                                        unroll=rt.unroll_layers)
        return x, aux, states
    if fam == "hybrid":
        return _hybrid_scan(params, cfg, x, positions, rt)
    if cfg.mixed_attention:
        return _period_scan(params, cfg, x, positions, rt)

    def body_fn(xp, xs):
        return attn_block(xs, xp, positions, cfg, rt)

    body = _maybe_remat(body_fn, rt)

    def body_scan(carry, xs):
        x_, aux_ = carry
        x2, a, kv = body(x_, xs)
        return (x2, aux_ + a), kv

    (x, aux), kvs = jax.lax.scan(body_scan, (x, zero), params["blocks"],
                                 unroll=rt.unroll_layers)
    return x, aux, kvs


def _periods(cfg: ModelConfig, tree, n: Optional[int] = None):
    """Layer-stacked leaves ``(n_layers, ...)`` -> ``(n_periods, n, ...)``
    (``n`` layers of each period; default the whole period, of one
    layer in a model of one kind)."""
    P = len(cfg.layer_pattern) if cfg.mixed_attention else 1
    if cfg.n_layers % P:
        raise ValueError(f"{cfg.name}: {cfg.n_layers} layers are not whole "
                         f"periods of {cfg.layer_pattern}")
    n = P if n is None else n
    return jax.tree.map(
        lambda a: a.reshape((cfg.n_layers // P, n) + a.shape[1:]), tree)


def _period_scan(params, cfg: ModelConfig, x, positions, rt):
    """Mixed attention kinds: scan over periods of ``layer_pattern``, the
    layers of a period unrolled, each with its own window and rope."""
    zero = jnp.zeros((), jnp.float32)

    def period_fn(xp, lp):
        aux, ks, vs = zero, [], []
        for j, kind in enumerate(cfg.layer_pattern):
            pj = jax.tree.map(lambda a: a[j], lp)
            xp, a, (k, v) = attn_block(pj, xp, positions, cfg, rt, kind)
            aux, ks, vs = aux + a, ks + [k], vs + [v]
        return xp, aux, (jnp.stack(ks), jnp.stack(vs))

    body = _maybe_remat(period_fn, rt)

    def body_scan(carry, lp):
        x_, aux_ = carry
        x2, a, kv = body(x_, lp)
        return (x2, aux_ + a), kv

    (x, aux), (k, v) = jax.lax.scan(body_scan, (x, zero),
                                    _periods(cfg, params["blocks"]),
                                    unroll=rt.unroll_layers)
    L_ = cfg.n_layers
    return x, aux, (k.reshape((L_,) + k.shape[2:]),
                    v.reshape((L_,) + v.shape[2:]))


def _hybrid_scan(params, cfg: ModelConfig, x, positions, rt):
    """Zamba2: groups of ``shared_attn_period`` Mamba layers, each group
    followed by one of the alternating shared transformer blocks."""
    period = cfg.shared_attn_period
    n_groups = cfg.n_layers // period
    nshared = cfg.n_shared_attn_blocks
    zero = jnp.zeros((), jnp.float32)

    grouped = jax.tree.map(
        lambda a: a.reshape((n_groups, period) + a.shape[1:]),
        params["blocks"])
    shared = params["shared"]

    def group_fn(x_, xs):
        gparams, gidx = xs

        def inner(xc, lp):
            x2, state = mamba_block(lp, xc, cfg, rt)
            return x2, state

        x_, states = jax.lax.scan(inner, x_, gparams,
                                  unroll=rt.unroll_layers)
        sel = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, gidx % nshared, 0, keepdims=False), shared)
        x_, aux, kv = attn_block(sel, x_, positions, cfg, rt)
        return x_, aux, (states, kv)

    body = _maybe_remat(group_fn, rt)

    def scan_body(carry, xs):
        x_, aux_ = carry
        x2, a, cachemat = body(x_, xs)
        return (x2, aux_ + a), cachemat

    (x, aux), cachemat = jax.lax.scan(
        scan_body, (x, zero), (grouped, jnp.arange(n_groups)),
        unroll=rt.unroll_layers)
    return x, aux, cachemat


def forward(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            rt: ModelRuntime = ModelRuntime()) -> Tuple[jax.Array, jax.Array]:
    """-> (logits (B, S, V) in rt.dtype, aux_loss scalar f32)."""
    x = _embed_in(params, cfg, batch, rt)
    B, S, _ = x.shape
    positions = batch.get("positions")
    if positions is None:
        positions = _default_positions(cfg, B, S)
    x, aux, _ = _scan_blocks(params, cfg, x, positions, rt)
    x = norm(x, params["final_norm"], cfg.norm, policy=rt.kernel_policy())
    return _unembed(params, cfg, x), aux


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            rt: ModelRuntime = ModelRuntime()) -> Tuple[jax.Array, Dict]:
    logits, aux = forward(params, cfg, batch, rt)
    ce = L.cross_entropy(logits, batch["labels"])
    return ce + aux, {"ce": ce, "aux": aux}


def _kv_leaves(k, v, rt: ModelRuntime) -> Dict[str, jax.Array]:
    """Contiguous-cache KV leaves from windowed prefill rows.

    int8 KV quantizes here — at write time — so the cache leaves hand
    off to :func:`decode_step` (and splice into a serving engine's
    bigger cache) without any float->int8 ``astype`` ever touching the
    payload buffers.
    """
    if rt.kv_dtype == "int8":
        from repro.kernels.quant import quantize_rows
        kq, ks = quantize_rows(k)
        vq, vs = quantize_rows(v)
        return {"k": kq, "ks": ks, "v": vq, "vs": vs}
    return {"k": k.astype(rt.kv_dtype or rt.dtype),
            "v": v.astype(rt.kv_dtype or rt.dtype)}


def _fill_kv_window(k_full: jax.Array, W: int) -> jax.Array:
    """Place (B, S, Hkv, hd) prefill keys into a W-slot circular cache:
    key at absolute position p lives in slot p % W (last W kept)."""
    B, S = k_full.shape[:2]
    if S <= W:
        pad = W - S
        return jnp.pad(k_full, ((0, 0), (0, pad), (0, 0), (0, 0)))
    idx = jnp.arange(S - W, S) % W
    out = jnp.zeros((B, W) + k_full.shape[2:], k_full.dtype)
    return out.at[:, idx].set(k_full[:, -W:])


def _fill_kv_ring(rows: jax.Array, W: int, lengths: jax.Array) -> jax.Array:
    """Place (B, S, Hkv, hd) prefill rows into a W-row ring by each row's
    *real* length: ring row r holds the latest position p < length with
    p % W == r, so the ring ends at the last real token even when pad
    tokens follow it; rows no real position reaches hold rows the decode
    mask hides until they are written."""
    S = rows.shape[1]
    last = lengths[:, None] - 1
    p = last - jnp.mod(last - jnp.arange(W)[None, :], W)       # (B, W)
    p = jnp.clip(p, 0, S - 1)
    return jnp.take_along_axis(rows, p[:, :, None, None], axis=1)


def layers_of(cfg: ModelConfig, kind: str) -> List[int]:
    """Indices of the layers of attention ``kind``, in order."""
    return [i for i in range(cfg.n_layers) if cfg.layer_kind(i) == kind]


def _mixed_kv_leaves(kvs, cfg: ModelConfig, max_len: int, lengths,
                     rt: ModelRuntime) -> Dict[str, jax.Array]:
    """The two cache groups of a model of mixed kinds from the prefill's
    per-layer (k, v): full layers ``k``/``v`` at ``max_len`` rows, window
    layers ``kw``/``vw`` in their ring of ``_ring_window`` rows."""
    if rt.kv_dtype == "int8":
        raise NotImplementedError("int8 KV for mixed attention kinds")
    full = np.asarray(layers_of(cfg, "full"))
    win = np.asarray(layers_of(cfg, "window"))
    W = _ring_window(cfg, max_len)
    kvd = rt.kv_dtype or rt.dtype
    k, v = kvs
    fill = jax.vmap(lambda t: _fill_kv_window(t, max_len))
    ring = jax.vmap(lambda t: _fill_kv_ring(t, W, lengths))
    return {"k": fill(k[full]).astype(kvd), "v": fill(v[full]).astype(kvd),
            "kw": ring(k[win]).astype(kvd), "vw": ring(v[win]).astype(kvd)}


def prefill(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            max_len: int, rt: ModelRuntime = ModelRuntime(),
            lengths: Optional[jax.Array] = None,
            ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """One-pass prefill: returns (primed cache, last-token logits (B, V)).

    The cache hands off exactly to :func:`decode_step` — validated by
    tests/test_serve.py against token-by-token decoding.

    ``lengths`` (B,) int32 marks each row's *real* prompt length when
    ``batch['tokens']`` is right-padded to a bucketed length (the serve
    scheduler's anti-recompile path): the cache position is set to the
    real length and the returned logits are gathered at ``lengths - 1``
    instead of the padded tail. Rows padded this way are only valid for
    attention-family caches — the padded keys land at cache rows
    ``>= length`` where the decode mask hides them until they are
    overwritten. SSM/hybrid recurrent state would absorb the pad tokens,
    so callers must pass exact-length rows for those families (the
    scheduler's chunked-prefill mode does exactly that).
    """
    x = _embed_in(params, cfg, batch, rt)
    B, S, _ = x.shape
    positions = batch.get("positions")
    if positions is None:
        positions = _default_positions(cfg, B, S)
    x, _, cachemat = _scan_blocks(params, cfg, x, positions, rt)

    W = _cache_window(cfg, max_len)
    dtype = rt.dtype
    fam = cfg.family
    if lengths is None:
        pos = jnp.full((B,), S, jnp.int32)
    else:
        pos = jnp.asarray(lengths, jnp.int32)
    if fam in ("dense", "moe", "vlm", "audio") and cfg.mixed_attention:
        cache = {"pos": pos, **_mixed_kv_leaves(cachemat, cfg, max_len,
                                                pos, rt)}
    elif fam in ("dense", "moe", "vlm", "audio"):
        kvs = cachemat                      # (k, v): (nL, B, S, Hkv, hd)
        k = jax.vmap(lambda t: _fill_kv_window(t, W))(kvs[0])
        v = jax.vmap(lambda t: _fill_kv_window(t, W))(kvs[1])
        cache = {"pos": pos, **_kv_leaves(k, v, rt)}
    elif fam == "ssm":
        states = cachemat                   # {'conv': (nL,B,K-1,C), 'ssm':...}
        cache = {"pos": pos,
                 "conv": states["conv"].astype(dtype),
                 "ssm": states["ssm"].astype(jnp.float32)}
    else:                                   # hybrid
        states, kvs = cachemat
        # states leaves: (n_groups, period, B, ...) -> (n_layers, B, ...)
        conv = states["conv"].reshape((cfg.n_layers,)
                                      + states["conv"].shape[2:])
        ssm = states["ssm"].reshape((cfg.n_layers,) + states["ssm"].shape[2:])
        k = jax.vmap(lambda t: _fill_kv_window(t, W))(kvs[0])
        v = jax.vmap(lambda t: _fill_kv_window(t, W))(kvs[1])
        cache = {"pos": pos, "conv": conv.astype(dtype),
                 "ssm": ssm.astype(jnp.float32),
                 **_kv_leaves(k, v, rt)}

    if lengths is None:
        x_last = x[:, -1:, :]
    else:
        idx = jnp.clip(pos - 1, 0, S - 1)
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    x = norm(x_last, params["final_norm"], cfg.norm,
             policy=rt.kernel_policy())
    logits = _unembed(params, cfg, x)[:, 0]
    return cache, logits


# ===========================================================================
# Decode (KV / state caches)
# ===========================================================================
def _cache_window(cfg: ModelConfig, max_len: int) -> int:
    """Rows a slot's cache holds (of the full layers, in a model of mixed
    kinds)."""
    if cfg.sliding_window and not cfg.mixed_attention:
        return min(cfg.sliding_window, max_len)
    return max_len


def _ring_window(cfg: ModelConfig, max_len: int) -> int:
    """Rows of the window layers' ring in a model of mixed kinds."""
    return min(cfg.sliding_window, max_len)


def cache_token_budget(cfg: ModelConfig, max_len: int,
                       prompt_len: int) -> int:
    """How many *new* tokens a sequence of ``prompt_len`` may decode
    before its cache positions exceed ``max_len`` — the cache-bounds
    contract between the model and every serving caller.

    :func:`decode_step` writes the new key at ``pos % W`` and masks with
    ``slot <= pos``; for full-attention families ``W == max_len``, so a
    write at ``pos >= max_len`` wraps onto row 0 and destroys the oldest
    live context — silently. Sliding-window caches wrap by design, but
    RoPE positions and the serving budget are still counted against
    ``max_len``. Callers (the ServeEngine) must therefore never decode a
    sequence past ``prompt_len + budget`` tokens; a non-positive return
    means the prompt itself cannot be admitted.
    """
    return max_len - prompt_len


def cache_spec(cfg: ModelConfig, batch: int, max_len: int,
               dtype: str = "bfloat16",
               kv_dtype: Optional[str] = None) -> Dict[str, Tuple[Tuple, Any]]:
    """{name: (shape, dtype)} — single source for zeros + abstract trees.

    ``kv_dtype`` overrides the KV buffers' storage dtype (default: the
    activation ``dtype``). ``int8`` KV adds per-(token, head) scale
    side-band leaves ``ks``/``vs`` (bf16, one scale per cached row per
    kv head) — 1/head_dim the size of the payload buffers.
    """
    hd = cfg.head_dim
    W = _cache_window(cfg, max_len)
    kvd = kv_dtype or dtype
    spec: Dict[str, Tuple[Tuple, Any]] = {
        "pos": ((batch,), jnp.int32),    # per-sequence positions
    }
    fam = cfg.family
    if fam in ("dense", "moe", "vlm", "audio") and cfg.mixed_attention:
        # full layers at W = max_len rows, window layers in their ring
        nf, nw = len(layers_of(cfg, "full")), len(layers_of(cfg, "window"))
        Wr = _ring_window(cfg, max_len)
        spec["k"] = ((nf, batch, W, cfg.n_kv_heads, hd), kvd)
        spec["v"] = ((nf, batch, W, cfg.n_kv_heads, hd), kvd)
        spec["kw"] = ((nw, batch, Wr, cfg.n_kv_heads, hd), kvd)
        spec["vw"] = ((nw, batch, Wr, cfg.n_kv_heads, hd), kvd)
    elif fam in ("dense", "moe", "vlm", "audio"):
        spec["k"] = ((cfg.n_layers, batch, W, cfg.n_kv_heads, hd), kvd)
        spec["v"] = ((cfg.n_layers, batch, W, cfg.n_kv_heads, hd), kvd)
        if kvd == "int8":
            spec["ks"] = ((cfg.n_layers, batch, W, cfg.n_kv_heads),
                          "bfloat16")
            spec["vs"] = ((cfg.n_layers, batch, W, cfg.n_kv_heads),
                          "bfloat16")
    if fam in ("ssm", "hybrid"):
        cs = SSM.ssm_cache_shapes(cfg, batch)
        spec["conv"] = ((cfg.n_layers,) + cs["conv"], dtype)
        spec["ssm"] = ((cfg.n_layers,) + cs["ssm"], "float32")
    if fam == "hybrid":
        n_groups = cfg.n_layers // cfg.shared_attn_period
        spec["k"] = ((n_groups, batch, W, cfg.n_kv_heads, hd), kvd)
        spec["v"] = ((n_groups, batch, W, cfg.n_kv_heads, hd), kvd)
        if kvd == "int8":
            spec["ks"] = ((n_groups, batch, W, cfg.n_kv_heads), "bfloat16")
            spec["vs"] = ((n_groups, batch, W, cfg.n_kv_heads), "bfloat16")
    return spec


CACHE_AXES = {
    "pos": ("batch",),
    "k": (None, "batch", "kv_seq", "kv_heads", None),
    "v": (None, "batch", "kv_seq", "kv_heads", None),
    "kw": (None, "batch", "kv_seq", "kv_heads", None),
    "vw": (None, "batch", "kv_seq", "kv_heads", None),
    "ks": (None, "batch", "kv_seq", "kv_heads"),
    "vs": (None, "batch", "kv_seq", "kv_heads"),
    "conv": (None, "batch", None, "ssm_inner"),
    "ssm": (None, "batch", "ssm_heads", None, None),
}


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype: str = "bfloat16", kv_dtype: Optional[str] = None):
    return {k: jnp.zeros(s, d)
            for k, (s, d) in cache_spec(cfg, batch, max_len, dtype,
                                        kv_dtype).items()}


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype: str = "bfloat16", kv_dtype: Optional[str] = None):
    return {k: jax.ShapeDtypeStruct(s, jnp.dtype(d))
            for k, (s, d) in cache_spec(cfg, batch, max_len, dtype,
                                        kv_dtype).items()}


# ---------------------------------------------------------------------------
# Paged cache (block-paged KV pool + per-sequence page tables)
# ---------------------------------------------------------------------------
def page_count(tokens: int, page_size: int) -> int:
    """Pages needed to hold ``tokens`` cache rows (ceil division)."""
    return -(-int(tokens) // int(page_size))


def paged_cache_spec(cfg: ModelConfig, n_slots: int, n_pages: int,
                     page_size: int, max_len: int,
                     dtype: str = "bfloat16",
                     kv_dtype: Optional[str] = None
                     ) -> Dict[str, Tuple[Tuple, Any]]:
    """{name: (shape, dtype)} for the paged decode cache.

    ``kv_dtype='int8'`` stores the page pools quantized and adds pooled
    scale side-bands ``ks``/``vs``: ``(L, n_pages, page_size, Hkv)``
    bf16, one scale per cached row per kv head.

    KV lives in one pooled buffer per layer group — ``kp``/``vp``:
    ``(L, n_pages, page_size) + row`` — addressed through per-slot page
    tables ``pt: (n_slots, ceil(W / page_size))``. A float pool's
    ``row`` is the decode kernel's (``page_row``: ``(Hkv * hd,)`` with
    one query per kv head, else ``(Hkv, hd)``); an int8 pool's is
    ``(Hkv, hd)``. Physical page 0
    is reserved as the null page: unowned table entries point at it and
    retired slots write their (masked) decode rows into it, so stale
    slots can never corrupt pages that have been rebound to live
    requests. Recurrent state (``conv``/``ssm``) is O(1) per slot and
    stays contiguous; only the KV rows page.

    A model of mixed attention kinds keeps two groups: its full layers
    in ``kp``/``vp`` (``n_pages``) under ``pt``, ``ceil(max_len /
    page_size)`` pages a slot; its window layers in ``kpw``/``vpw``, a
    whole ring per slot and the null page, under ``ptw``, a ring of
    ``ceil(W / page_size)`` pages. An expert
    model adds ``expert_counts``: int32 ``[rows, experts hit]`` of the
    last decode step, summed over layers.
    """
    W = _cache_window(cfg, max_len)
    npp = page_count(W, page_size)
    kvd = kv_dtype or dtype
    row = (page_size,) + _pool_row(cfg, kvd)
    spec: Dict[str, Tuple[Tuple, Any]] = {
        "pos": ((n_slots,), jnp.int32),
        "pt": ((n_slots, npp), jnp.int32),
    }
    fam = cfg.family
    if cfg.moe is not None:
        spec["expert_counts"] = ((2,), jnp.int32)
    if fam in ("dense", "moe", "vlm", "audio") and cfg.mixed_attention:
        if kvd == "int8":
            raise NotImplementedError("int8 KV for mixed attention kinds")
        npw = page_count(_ring_window(cfg, max_len), page_size)
        n_ring_pages = n_slots * npw + 1
        nf, nw = len(layers_of(cfg, "full")), len(layers_of(cfg, "window"))
        spec["ptw"] = ((n_slots, npw), jnp.int32)
        spec["kp"] = ((nf, n_pages) + row, kvd)
        spec["vp"] = ((nf, n_pages) + row, kvd)
        spec["kpw"] = ((nw, n_ring_pages) + row, kvd)
        spec["vpw"] = ((nw, n_ring_pages) + row, kvd)
    elif fam in ("dense", "moe", "vlm", "audio"):
        shape = (cfg.n_layers, n_pages) + row
        spec["kp"] = (shape, kvd)
        spec["vp"] = (shape, kvd)
        if kvd == "int8":
            spec["ks"] = (shape[:-1], "bfloat16")
            spec["vs"] = (shape[:-1], "bfloat16")
    if fam in ("ssm", "hybrid"):
        cs = SSM.ssm_cache_shapes(cfg, n_slots)
        spec["conv"] = ((cfg.n_layers,) + cs["conv"], dtype)
        spec["ssm"] = ((cfg.n_layers,) + cs["ssm"], "float32")
    if fam == "hybrid":
        n_groups = cfg.n_layers // cfg.shared_attn_period
        shape = (n_groups, n_pages) + row
        spec["kp"] = (shape, kvd)
        spec["vp"] = (shape, kvd)
        if kvd == "int8":
            spec["ks"] = (shape[:-1], "bfloat16")
            spec["vs"] = (shape[:-1], "bfloat16")
    return spec


def _pool_row(cfg: ModelConfig, kv_dtype: str) -> Tuple[int, ...]:
    """A KV pool's row: the decode kernel's (``page_row``) for a float
    pool; ``(Hkv, hd)`` for an int8 one, beside its per-head scales."""
    if kv_dtype == "int8":
        return (cfg.n_kv_heads, cfg.head_dim)
    return page_row(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)


#: Logical axis names for the paged cache. The page pool has no batch
#: axis (slots share it through their tables) — it shards along
#: ``kv_heads``, the same name the contiguous cache uses, so the
#: existing decode recipes place it tensor-parallel unchanged. A pool of
#: merged rows has the axes of :func:`paged_cache_axes`.
PAGED_CACHE_AXES = {
    "pos": ("batch",),
    "pt": ("batch", None),
    "ptw": ("batch", None),
    "kp": (None, None, None, "kv_heads", None),
    "vp": (None, None, None, "kv_heads", None),
    "kpw": (None, None, None, "kv_heads", None),
    "vpw": (None, None, None, "kv_heads", None),
    "expert_counts": (None,),
    "ks": (None, None, None, "kv_heads"),
    "vs": (None, None, None, "kv_heads"),
    "conv": CACHE_AXES["conv"],
    "ssm": CACHE_AXES["ssm"],
}


def paged_cache_axes(cfg: ModelConfig, kv_dtype: Optional[str] = None
                     ) -> Dict[str, tuple]:
    """:data:`PAGED_CACHE_AXES` as ``cfg``'s paged cache lays its leaves
    out: a float pool of merged rows, ``(L, n_pages, page_size, Hkv *
    hd)``, shards its row axis (kv heads, head-major) as ``kv_heads``."""
    if len(_pool_row(cfg, kv_dtype)) == 2:
        return PAGED_CACHE_AXES
    merged = (None, None, None, "kv_heads")
    return dict(PAGED_CACHE_AXES,
                **{k: merged for k in ("kp", "vp", "kpw", "vpw")})


def init_paged_cache(cfg: ModelConfig, n_slots: int, n_pages: int,
                     page_size: int, max_len: int,
                     dtype: str = "bfloat16",
                     kv_dtype: Optional[str] = None):
    return {k: jnp.zeros(s, d)
            for k, (s, d) in paged_cache_spec(
                cfg, n_slots, n_pages, page_size, max_len, dtype,
                kv_dtype).items()}


def write_prefill_pages(kp, vp, k, v, page_ids, *, page_size: int):
    """Scatter contiguous prefill KV rows into the page pool.

    k/v: (L, width, S, Hkv, hd) — the ``prefill`` cache's contiguous
    rows (circular layout for windowed configs, which the page mapping
    preserves: logical row r lives at page ``r // page_size``), laid
    into the pool's own row shape.
    page_ids: (width, n_write) int32 — the physical destination of each
    row's first ``n_write`` logical pages; pad rows point at the null
    page (their garbage stays masked forever).
    """
    kp = _scatter_rows_to_pages(kp, k, page_ids, page_size)
    vp = _scatter_rows_to_pages(vp, v, page_ids, page_size)
    return kp, vp


def _scatter_rows_to_pages(pool, rows, page_ids, page_size: int):
    """Scatter (L, width, S, ...) contiguous rows into an
    (L, n_pages, page_size, ...) pool at ``page_ids`` — shared by the
    KV payload buffers and the int8 scale side-bands (which simply lack
    the trailing head_dim axis). Each row takes the pool's row shape (a
    merged pool's ``Hkv * hd``)."""
    L, width, S = rows.shape[:3]
    n_write = page_ids.shape[1]
    need = n_write * page_size
    if need > S:
        pad = ((0, 0), (0, 0), (0, need - S)) + ((0, 0),) * (rows.ndim - 3)
        rows = jnp.pad(rows, pad)
    blocks = rows[:, :, :need].reshape(
        (L, width * n_write, page_size) + pool.shape[3:])
    flat = page_ids.reshape(-1)
    return pool.at[:, flat].set(blocks.astype(pool.dtype))


def write_prefill_pages_quant(kp, vp, ks_pool, vs_pool, k, v, ks, vs,
                              page_ids, *, page_size: int):
    """int8 twin of :func:`write_prefill_pages`: scatters the already-
    quantized payload rows plus their (L, width, S, Hkv) scale rows into
    the pooled side-bands."""
    kp = _scatter_rows_to_pages(kp, k, page_ids, page_size)
    vp = _scatter_rows_to_pages(vp, v, page_ids, page_size)
    ks_pool = _scatter_rows_to_pages(ks_pool, ks, page_ids, page_size)
    vs_pool = _scatter_rows_to_pages(vs_pool, vs, page_ids, page_size)
    return kp, vp, ks_pool, vs_pool


def _ffn_decode(p, h2: jax.Array, cfg: ModelConfig, pol: KernelPolicy,
                moe_layer: Optional[jax.Array] = None):
    """The block's FFN on one token a row: (B, d) -> (y, expert counts,
    or None for a model without experts). With ``moe_layer``,
    ``p["moe"]`` is every layer's, stacked (``moe.moe_ffn``'s
    ``layer``)."""
    if cfg.moe is not None:
        y, _, counts = MOE.moe_ffn(p["moe"], h2[:, None, :], cfg,
                                   dropless=True, policy=pol,
                                   layer=moe_layer)
        return y[:, 0], counts
    return _mlp(p, h2[:, None, :], cfg)[:, 0], None


def _add_counts(a: Optional[jax.Array], b: Optional[jax.Array]):
    return None if b is None else a + b


def _attn_decode_one_paged(p, x, kp, vp, pt, pos, window: int,
                           page_size: int, cfg: ModelConfig,
                           rt: ModelRuntime, kind: Optional[str] = None,
                           layer: Optional[jax.Array] = None,
                           moe_layer: Optional[jax.Array] = None):
    """One-layer paged attention for one token. The new K/V row is
    written *through the page table* at physical page
    ``pt[b, (pos % W) // ps]``, then attention gathers every owned page
    via the ``paged_decode_attention`` dispatch op. With ``layer``,
    ``kp``/``vp`` are a group's stacked pools: the row is written in
    place into that layer's, and attention reads that layer's pages
    through the page table offset into the stack; ``moe_layer`` is
    :func:`_ffn_decode`'s. Returns (x, kp, vp, expert counts or None)."""
    B = x.shape[0]
    W, ps = window, page_size
    pol = rt.kernel_policy()
    h = norm(x, p["ln1"], cfg.norm, policy=pol)[:, None, :]   # (B,1,d)
    q, k, v = _attn_proj(p, h, cfg, policy=pol)
    posv = pos[:, None]                                  # (B, 1)
    if cfg.rope == "mrope":
        posv = jnp.broadcast_to(posv[None], (3, B, 1))
    q, k = L.apply_rope(q, k, posv, cfg, kind)
    row = (pos % W).astype(jnp.int32)                    # (B,)
    phys = jnp.take_along_axis(pt, (row // ps)[:, None], axis=1)[:, 0]
    at = (phys, row % ps) if layer is None else (layer, phys, row % ps)
    # the new rows in the pool's row shape (a merged row is free)
    new = lambda a, pool: a[:, 0].reshape(                # noqa: E731
        (B,) + pool.shape[len(at):]).astype(pool.dtype)
    with jax.named_scope("kv_write"):
        kp = kp.at[at].set(new(k, kp))
        vp = vp.at[at].set(new(v, vp))
    Wp = pt.shape[1] * ps
    ar = jnp.arange(Wp)[None, :]
    mask = (ar <= pos[:, None]) & (ar < W)               # (B, Wp)
    if layer is None:
        o = dispatch("paged_decode_attention", pol, q[:, 0], kp, vp, pt,
                     mask)
    else:
        # the group's pools read as one pool of all its layers' pages
        # (a free reshape), the table offset to this layer's: no copy
        flat = lambda a: a.reshape((-1,) + a.shape[2:])   # noqa: E731
        o = dispatch("paged_decode_attention", pol, q[:, 0], flat(kp),
                     flat(vp), pt + layer * kp.shape[1], mask)
    x = x + o.reshape(B, -1) @ p["wo"].astype(x.dtype)

    h2 = norm(x, p["ln2"], cfg.norm, policy=pol)
    y, counts = _ffn_decode(p, h2, cfg, pol, moe_layer)
    return x + y, kp, vp, counts


def _attn_decode_one_paged_q(p, x, kp, vp, ks, vs, pt, pos, window: int,
                             page_size: int, cfg: ModelConfig,
                             rt: ModelRuntime):
    """int8-KV twin of :func:`_attn_decode_one_paged`: the new row is
    quantized once at write time (payload into the int8 pools, per-head
    scale into the pooled ``ks``/``vs`` side-bands) and attention runs
    through the ``quant_paged_decode_attention`` dispatch op — which
    dequantizes only the gathered pages, never the whole pool."""
    from repro.kernels.quant import quantize_rows

    B = x.shape[0]
    W, ps = window, page_size
    pol = rt.kernel_policy()
    h = norm(x, p["ln1"], cfg.norm, policy=pol)[:, None, :]   # (B,1,d)
    q, k, v = _attn_proj(p, h, cfg, policy=pol)
    posv = pos[:, None]                                  # (B, 1)
    if cfg.rope == "mrope":
        posv = jnp.broadcast_to(posv[None], (3, B, 1))
    q, k = L.apply_rope(q, k, posv, cfg)
    row = (pos % W).astype(jnp.int32)                    # (B,)
    phys = jnp.take_along_axis(pt, (row // ps)[:, None], axis=1)[:, 0]
    with jax.named_scope("kv_write"):
        kq, ksc = quantize_rows(k[:, 0])                 # (B,Hkv,hd)/(B,Hkv)
        vq, vsc = quantize_rows(v[:, 0])
        kp = kp.at[phys, row % ps].set(kq)
        vp = vp.at[phys, row % ps].set(vq)
        ks = ks.at[phys, row % ps].set(ksc.astype(ks.dtype))
        vs = vs.at[phys, row % ps].set(vsc.astype(vs.dtype))
    Wp = pt.shape[1] * ps
    ar = jnp.arange(Wp)[None, :]
    mask = (ar <= pos[:, None]) & (ar < W)               # (B, Wp)
    o = dispatch("quant_paged_decode_attention", pol, q[:, 0], kp, vp,
                 ks, vs, pt, mask)
    x = x + o.reshape(B, -1) @ p["wo"].astype(x.dtype)

    h2 = norm(x, p["ln2"], cfg.norm, policy=pol)
    y, _ = _ffn_decode(p, h2, cfg, pol)
    return x + y, kp, vp, ks, vs


def decode_step_paged(params, cfg: ModelConfig, cache: Dict[str, jax.Array],
                      tokens: jax.Array, rt: ModelRuntime,
                      *, page_size: int, window: int,
                      ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Paged twin of :func:`decode_step`, with attention layers routed
    through the page pool. Float KV of every attention family decodes in
    one body, :func:`_decode_step_paged_float`, its pools read and
    written in place; int8 KV and the hybrid scan each layer's pools as
    slices. Pure-SSM configs have no KV to page — their state cache
    decodes unchanged (the page table rides along untouched)."""
    fam = cfg.family
    if fam == "ssm":
        return decode_step(params, cfg, cache, tokens, rt)
    quant = "ks" in cache
    if fam in ("dense", "moe", "vlm", "audio") and not quant:
        return _decode_step_paged_float(params, cfg, cache, tokens, rt,
                                        page_size=page_size, window=window)
    pos = cache["pos"]
    pt = cache["pt"]
    x = params["embed"].astype(rt.dtype)[tokens]          # (B, d)
    pol = rt.kernel_policy()

    if fam in ("dense", "moe", "vlm", "audio"):             # int8 KV
        def body(x_, xs):
            lp, kp, vp, ks, vs = xs
            x2, kp, vp, ks, vs = _attn_decode_one_paged_q(
                lp, x_, kp, vp, ks, vs, pt, pos, window, page_size,
                cfg, rt)
            return x2, (kp, vp, ks, vs)

        x, (kp_new, vp_new, ks_new, vs_new) = jax.lax.scan(
            body, x, (params["blocks"], cache["kp"], cache["vp"],
                      cache["ks"], cache["vs"]),
            unroll=rt.unroll_layers)
        new_cache = dict(cache, pos=pos + 1, kp=kp_new, vp=vp_new,
                         ks=ks_new, vs=vs_new)
    else:  # hybrid
        period = cfg.shared_attn_period
        n_groups = cfg.n_layers // period
        nshared = cfg.n_shared_attn_blocks
        grouped = jax.tree.map(
            lambda a: a.reshape((n_groups, period) + a.shape[1:]),
            params["blocks"])
        conv_g = cache["conv"].reshape((n_groups, period)
                                       + cache["conv"].shape[1:])
        ssm_g = cache["ssm"].reshape((n_groups, period)
                                     + cache["ssm"].shape[1:])

        def inner(xc, ys):
            lp, conv, ssm = ys
            h = norm(xc, lp["ln"], cfg.norm, policy=pol)
            y, st = SSM.ssm_decode_step(lp["ssm"], h, {
                "conv": conv, "ssm": ssm}, cfg, policy=pol)
            return xc + y, (st["conv"], st["ssm"])

        def _shared_block(gidx):
            return jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, gidx % nshared, 0, keepdims=False), params["shared"])

        if quant:
            def group(x_, xs):
                gp, gidx, convs, ssms, kp, vp, ks, vs = xs
                x_, (conv2, ssm2) = jax.lax.scan(
                    inner, x_, (gp, convs, ssms), unroll=rt.unroll_layers)
                x_, kp, vp, ks, vs = _attn_decode_one_paged_q(
                    _shared_block(gidx), x_, kp, vp, ks, vs, pt, pos,
                    window, page_size, cfg, rt)
                return x_, (conv2, ssm2, kp, vp, ks, vs)

            x, (conv2, ssm2, kp_new, vp_new, ks_new, vs_new) = jax.lax.scan(
                group, x, (grouped, jnp.arange(n_groups), conv_g, ssm_g,
                           cache["kp"], cache["vp"], cache["ks"],
                           cache["vs"]),
                unroll=rt.unroll_layers)
            new_cache = dict(
                cache, pos=pos + 1,
                conv=conv2.reshape(cache["conv"].shape),
                ssm=ssm2.reshape(cache["ssm"].shape),
                kp=kp_new, vp=vp_new, ks=ks_new, vs=vs_new)
        else:
            def group(x_, xs):
                gp, gidx, convs, ssms, kp, vp = xs
                x_, (conv2, ssm2) = jax.lax.scan(
                    inner, x_, (gp, convs, ssms), unroll=rt.unroll_layers)
                x_, kp, vp, _ = _attn_decode_one_paged(
                    _shared_block(gidx), x_, kp, vp, pt, pos, window,
                    page_size, cfg, rt)
                return x_, (conv2, ssm2, kp, vp)

            x, (conv2, ssm2, kp_new, vp_new) = jax.lax.scan(
                group, x, (grouped, jnp.arange(n_groups), conv_g, ssm_g,
                           cache["kp"], cache["vp"]),
                unroll=rt.unroll_layers)
            new_cache = dict(
                cache, pos=pos + 1,
                conv=conv2.reshape(cache["conv"].shape),
                ssm=ssm2.reshape(cache["ssm"].shape),
                kp=kp_new, vp=vp_new)

    x = norm(x[:, None, :], params["final_norm"], cfg.norm, policy=pol)
    logits = _unembed(params, cfg, x)[:, 0]
    return new_cache, logits


def _zero_counts(cfg: ModelConfig) -> Optional[jax.Array]:
    return jnp.zeros((2,), jnp.int32) if cfg.moe is not None else None


def _decode_step_paged_float(params, cfg: ModelConfig, cache, tokens, rt,
                             *, page_size: int, window: int):
    """:func:`decode_step_paged` for float KV: a scan over periods of
    ``layer_pattern`` (a model of one kind is a period of one layer), the
    layers of a period unrolled. Window layers of a model of mixed kinds
    write and read their ring (``kpw``/``vpw`` under ``ptw``,
    ``_ring_window`` rows); every other layer the main group (``kp``/
    ``vp`` under ``pt``, ``window`` rows: a one-kind sliding window's
    already). The pools ride the scan's carry, take each new row in
    place and are read through the page table offset to the layer's
    pages: no layer's pool is copied. Expert weights stay stacked (their
    grouped GEMMs read each layer's in place)."""
    pos = cache["pos"]
    x = params["embed"].astype(rt.dtype)[tokens]          # (B, d)
    pattern = cfg.layer_pattern if cfg.mixed_attention else (None,)
    # per kind: its pools, page table and rows
    groups = {kind: ("kpw", "vpw", "ptw", _ring_window(cfg, window))
              if kind == "window" else ("kp", "vp", "pt", window)
              for kind in pattern}
    pools = {n: cache[n] for n in ("kp", "vp", "kpw", "vpw") if n in cache}
    blocks = dict(params["blocks"])
    experts = blocks.pop("moe", None)

    def body(carry, xs):
        x_, n_, pools = carry
        lp, period = xs
        seen = {kind: 0 for kind in groups}
        for j, kind in enumerate(pattern):
            pj = jax.tree.map(lambda a: a[j], lp)
            moe_layer = None
            if experts is not None:
                pj["moe"], moe_layer = experts, period * len(pattern) + j
            kn, vn, ptn, rows = groups[kind]
            layer = period * pattern.count(kind) + seen[kind]
            seen[kind] += 1
            x_, k2, v2, n2 = _attn_decode_one_paged(
                pj, x_, pools[kn], pools[vn], cache[ptn], pos, rows,
                page_size, cfg, rt, kind, layer=layer, moe_layer=moe_layer)
            pools = dict(pools, **{kn: k2, vn: v2})
            n_ = _add_counts(n_, n2)
        return (x_, n_, pools), None

    n_periods = cfg.n_layers // len(pattern)
    (x, counts, pools), _ = jax.lax.scan(
        body, (x, _zero_counts(cfg), pools),
        (_periods(cfg, blocks), jnp.arange(n_periods)),
        unroll=rt.unroll_layers)
    new_cache = dict(cache, pos=pos + 1, **pools)
    if counts is not None:
        new_cache["expert_counts"] = counts
    pol = rt.kernel_policy()
    x = norm(x[:, None, :], params["final_norm"], cfg.norm, policy=pol)
    return new_cache, _unembed(params, cfg, x)[:, 0]


def _attn_decode_one(p, x, k_cache, v_cache, pos, cfg: ModelConfig,
                     rt: ModelRuntime, kind: Optional[str] = None):
    """One-layer attention for one token. x: (B, d); pos: (B,) int32 —
    per-sequence positions (continuous batching); ``kind`` the layer's
    attention kind in a model of mixed kinds (its cache holds its rows:
    a window layer's is the ring)."""
    B = x.shape[0]
    hd = cfg.head_dim
    W = k_cache.shape[1]
    pol = rt.kernel_policy()
    h = norm(x, p["ln1"], cfg.norm, policy=pol)[:, None, :]   # (B,1,d)
    q, k, v = _attn_proj(p, h, cfg, policy=pol)
    posv = pos[:, None]                                  # (B, 1)
    if cfg.rope == "mrope":
        posv = jnp.broadcast_to(posv[None], (3, B, 1))
    q, k = L.apply_rope(q, k, posv, cfg, kind)
    slot = (pos % W).astype(jnp.int32)                   # (B,)
    bidx = jnp.arange(B)
    k_cache = k_cache.at[bidx, slot].set(k[:, 0].astype(k_cache.dtype))
    v_cache = v_cache.at[bidx, slot].set(v[:, 0].astype(v_cache.dtype))
    mask = jnp.arange(W)[None, :] <= pos[:, None]        # (B, W)
    o = dispatch("decode_attention", pol, q[:, 0], k_cache, v_cache, mask)
    x = x + o.reshape(B, -1) @ p["wo"].astype(x.dtype)

    h2 = norm(x, p["ln2"], cfg.norm, policy=pol)
    y, _ = _ffn_decode(p, h2, cfg, pol)
    return x + y, k_cache, v_cache


def _attn_decode_one_q(p, x, k_cache, v_cache, ks_cache, vs_cache, pos,
                       cfg: ModelConfig, rt: ModelRuntime):
    """int8-KV twin of :func:`_attn_decode_one`: the new row is
    quantized once at write time (payload int8, per-head scale into the
    ``ks``/``vs`` side-bands) and attention runs through the
    ``quant_decode_attention`` dispatch op."""
    from repro.kernels.quant import quantize_rows

    B = x.shape[0]
    W = k_cache.shape[1]
    pol = rt.kernel_policy()
    h = norm(x, p["ln1"], cfg.norm, policy=pol)[:, None, :]   # (B,1,d)
    q, k, v = _attn_proj(p, h, cfg, policy=pol)
    posv = pos[:, None]                                  # (B, 1)
    if cfg.rope == "mrope":
        posv = jnp.broadcast_to(posv[None], (3, B, 1))
    q, k = L.apply_rope(q, k, posv, cfg)
    slot = (pos % W).astype(jnp.int32)                   # (B,)
    bidx = jnp.arange(B)
    kq, ksc = quantize_rows(k[:, 0])                     # (B,Hkv,hd)/(B,Hkv)
    vq, vsc = quantize_rows(v[:, 0])
    k_cache = k_cache.at[bidx, slot].set(kq)
    v_cache = v_cache.at[bidx, slot].set(vq)
    ks_cache = ks_cache.at[bidx, slot].set(ksc.astype(ks_cache.dtype))
    vs_cache = vs_cache.at[bidx, slot].set(vsc.astype(vs_cache.dtype))
    mask = jnp.arange(W)[None, :] <= pos[:, None]        # (B, W)
    o = dispatch("quant_decode_attention", pol, q[:, 0], k_cache, v_cache,
                 ks_cache, vs_cache, mask)
    x = x + o.reshape(B, -1) @ p["wo"].astype(x.dtype)

    h2 = norm(x, p["ln2"], cfg.norm, policy=pol)
    y, _ = _ffn_decode(p, h2, cfg, pol)
    return x + y, k_cache, v_cache, ks_cache, vs_cache


def _decode_step_mixed(params, cfg: ModelConfig, cache, tokens, rt):
    """:func:`decode_step` for a model of mixed attention kinds: a scan
    over periods of ``layer_pattern``, the layers of a period unrolled;
    window layers in their ring (``kw``/``vw``), full layers in ``k``/
    ``v``."""
    pos = cache["pos"]
    x = params["embed"].astype(rt.dtype)[tokens]          # (B, d)
    nw = cfg.layer_pattern.count("window")
    nf = len(cfg.layer_pattern) - nw

    def body(x_, xs):
        lp, kw, vw, kf, vf = xs
        new = {"window": [], "full": []}
        for j, kind in enumerate(cfg.layer_pattern):
            pj = jax.tree.map(lambda a: a[j], lp)
            kc, vc = (kw, vw) if kind == "window" else (kf, vf)
            i = len(new[kind])
            x_, k2, v2 = _attn_decode_one(pj, x_, kc[i], vc[i], pos, cfg,
                                          rt, kind)
            new[kind].append((k2, v2))
        return x_, tuple(jnp.stack([kv[i] for kv in new[kind]])
                         for kind in ("window", "full") for i in (0, 1))

    xs = (_periods(cfg, params["blocks"]),
          _periods(cfg, cache["kw"], nw), _periods(cfg, cache["vw"], nw),
          _periods(cfg, cache["k"], nf), _periods(cfg, cache["v"], nf))
    x, (kw, vw, kf, vf) = jax.lax.scan(body, x, xs, unroll=rt.unroll_layers)
    new_cache = dict(cache, pos=pos + 1,
                     kw=kw.reshape(cache["kw"].shape),
                     vw=vw.reshape(cache["vw"].shape),
                     k=kf.reshape(cache["k"].shape),
                     v=vf.reshape(cache["v"].shape))
    pol = rt.kernel_policy()
    x = norm(x[:, None, :], params["final_norm"], cfg.norm, policy=pol)
    return new_cache, _unembed(params, cfg, x)[:, 0]


def decode_step(params, cfg: ModelConfig, cache: Dict[str, jax.Array],
                tokens: jax.Array, rt: ModelRuntime = ModelRuntime(),
                ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """tokens: (B,) int32 -> (new cache, logits (B, V))."""
    if cfg.mixed_attention:
        return _decode_step_mixed(params, cfg, cache, tokens, rt)
    pos = cache["pos"]
    x = params["embed"].astype(rt.dtype)[tokens]          # (B, d)
    fam = cfg.family
    pol = rt.kernel_policy()
    quant = "ks" in cache

    if fam in ("dense", "moe", "vlm", "audio"):
        if quant:
            def body(x_, xs):
                lp, kc, vc, ksc, vsc = xs
                x2, kc, vc, ksc, vsc = _attn_decode_one_q(
                    lp, x_, kc, vc, ksc, vsc, pos, cfg, rt)
                return x2, (kc, vc, ksc, vsc)

            x, (k_new, v_new, ks_new, vs_new) = jax.lax.scan(
                body, x, (params["blocks"], cache["k"], cache["v"],
                          cache["ks"], cache["vs"]),
                unroll=rt.unroll_layers)
            new_cache = dict(cache, pos=pos + 1, k=k_new, v=v_new,
                             ks=ks_new, vs=vs_new)
        else:
            def body(x_, xs):
                lp, kc, vc = xs
                x2, kc, vc = _attn_decode_one(lp, x_, kc, vc, pos, cfg, rt)
                return x2, (kc, vc)

            x, (k_new, v_new) = jax.lax.scan(
                body, x, (params["blocks"], cache["k"], cache["v"]),
                unroll=rt.unroll_layers)
            new_cache = dict(cache, pos=pos + 1, k=k_new, v=v_new)
    elif fam == "ssm":
        def body(x_, xs):
            lp, conv, ssm = xs
            h = norm(x_, lp["ln"], cfg.norm, policy=pol)
            y, st = SSM.ssm_decode_step(lp["ssm"], h, {
                "conv": conv, "ssm": ssm}, cfg, policy=pol)
            return x_ + y, (st["conv"], st["ssm"])

        x, (conv_new, ssm_new) = jax.lax.scan(
            body, x, (params["blocks"], cache["conv"], cache["ssm"]),
            unroll=rt.unroll_layers)
        new_cache = dict(cache, pos=pos + 1, conv=conv_new, ssm=ssm_new)
    else:  # hybrid
        period = cfg.shared_attn_period
        n_groups = cfg.n_layers // period
        nshared = cfg.n_shared_attn_blocks
        grouped = jax.tree.map(
            lambda a: a.reshape((n_groups, period) + a.shape[1:]),
            params["blocks"])
        conv_g = cache["conv"].reshape((n_groups, period)
                                       + cache["conv"].shape[1:])
        ssm_g = cache["ssm"].reshape((n_groups, period)
                                     + cache["ssm"].shape[1:])

        def inner(xc, ys):
            lp, conv, ssm = ys
            h = norm(xc, lp["ln"], cfg.norm, policy=pol)
            y, st = SSM.ssm_decode_step(lp["ssm"], h, {
                "conv": conv, "ssm": ssm}, cfg, policy=pol)
            return xc + y, (st["conv"], st["ssm"])

        def _shared_block(gidx):
            return jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, gidx % nshared, 0, keepdims=False), params["shared"])

        if quant:
            def group(x_, xs):
                gp, gidx, convs, ssms, kc, vc, ksc, vsc = xs
                x_, (conv2, ssm2) = jax.lax.scan(
                    inner, x_, (gp, convs, ssms), unroll=rt.unroll_layers)
                x_, kc, vc, ksc, vsc = _attn_decode_one_q(
                    _shared_block(gidx), x_, kc, vc, ksc, vsc, pos, cfg, rt)
                return x_, (conv2, ssm2, kc, vc, ksc, vsc)

            x, (conv2, ssm2, k_new, v_new, ks_new, vs_new) = jax.lax.scan(
                group, x, (grouped, jnp.arange(n_groups), conv_g, ssm_g,
                           cache["k"], cache["v"], cache["ks"],
                           cache["vs"]),
                unroll=rt.unroll_layers)
            new_cache = dict(
                cache, pos=pos + 1,
                conv=conv2.reshape(cache["conv"].shape),
                ssm=ssm2.reshape(cache["ssm"].shape),
                k=k_new, v=v_new, ks=ks_new, vs=vs_new)
        else:
            def group(x_, xs):
                gp, gidx, convs, ssms, kc, vc = xs
                x_, (conv2, ssm2) = jax.lax.scan(
                    inner, x_, (gp, convs, ssms), unroll=rt.unroll_layers)
                x_, kc, vc = _attn_decode_one(
                    _shared_block(gidx), x_, kc, vc, pos, cfg, rt)
                return x_, (conv2, ssm2, kc, vc)

            x, (conv2, ssm2, k_new, v_new) = jax.lax.scan(
                group, x, (grouped, jnp.arange(n_groups), conv_g, ssm_g,
                           cache["k"], cache["v"]),
                unroll=rt.unroll_layers)
            new_cache = dict(
                cache, pos=pos + 1,
                conv=conv2.reshape(cache["conv"].shape),
                ssm=ssm2.reshape(cache["ssm"].shape),
                k=k_new, v=v_new)

    x = norm(x[:, None, :], params["final_norm"], cfg.norm, policy=pol)
    logits = _unembed(params, cfg, x)[:, 0]
    return new_cache, logits
