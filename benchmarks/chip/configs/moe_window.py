"""A decoder of window and full attention layers with routed experts, as
a configuration file's ``model`` describes it (Mellum2's ``config.json``
keys): its plain reference forward pass, its operations and bytes, and
the program's registry entry set to the file.
Configuration files name this module with ``"reference": "moe_window"``.

Reference
---------

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``; no kernels, no cache, no batching, no bucketing,
no sorting of rows. It imports nothing of the program under test; it
takes the weight recipe and the float8 rounding from ``dense.py`` beside
it, and keeps its served-gap rule. The weights are made from the run's seed by
the program's recipe and held in the serving dtype, each layer's upcast
to float32 only while it runs (the whole model in float32 would not fit
beside them).

Architecture, per layer: RMSNorm; grouped-query causal attention whose
kind comes from ``layer_types``: ``sliding_attention`` layers let query
i see key j when i - W < j <= i (W = ``sliding_window``) with plain
rotary embeddings, ``full_attention`` layers see every j <= i with
YaRN's rotary embeddings (below); residual; RMSNorm; the expert layer;
residual. Then a final RMSNorm and the untied output projection.

The expert layer: the router scores all ``router_experts`` in float32,
softmax, the top ``num_experts_per_tok`` renormalised to sum to one
(``norm_topk_prob``). This device holds ``num_experts`` of them, ids
0 onward; each held expert is a SwiGLU of width
``moe_intermediate_size`` applied to every token and weighted by that
token's renormalised gate for it (0 where it was not chosen). Experts
held elsewhere add nothing, as in the program.

YaRN, written out as Hugging Face's ``_compute_yarn_parameters``: the
correction dimensions d(r) = D ln(L0 / (2 pi r)) / (2 ln theta) at r =
``beta_fast`` and ``beta_slow`` (floored and ceiled, clipped to [0,
D - 1]) bound a linear ramp over the D/2 frequency pairs; ``inv_freq``
mixes theta^(-2i/D) / factor (ramp 1) with theta^(-2i/D) (ramp 0), and
cos and sin are multiplied by ``attention_factor``. It applies at every
position.

``control="fp8"`` rounds both operands of every matrix product to
float8 e4m3 with a per-tensor scale: the reference one precision below
the bfloat16 that the configuration serves in.
"""
from __future__ import annotations

import importlib.util
import math
from functools import partial
from pathlib import Path
from typing import Dict, Iterable, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

Q_BLOCK = 512          # query rows per attention block
T_STEP = 1024          # sequences are padded to a multiple of this


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location(
        f"chip_arch_{name}", Path(__file__).with_name(f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


dense = _sibling("dense")
KINDS = {"sliding_attention": "window", "full_attention": "full"}


# ------------------------------------------------------------------ weights
def leaf_specs(m: Dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Tree path -> (shape, init) of every parameter leaf."""
    L, d, V = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    nq, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    E, Eh, f = m["router_experts"], m["num_experts"], \
        m["moe_intermediate_size"]
    b = "['blocks']"
    return {
        "['embed']": ((V, d), "embed"),
        "['final_norm']['scale']": ((d,), "ones"),
        "['lm_head']": ((d, V), "fan_in"),
        b + "['ln1']['scale']": ((L, d), "ones"),
        b + "['ln2']['scale']": ((L, d), "ones"),
        b + "['wq']": ((L, d, nq * hd), "fan_in"),
        b + "['wk']": ((L, d, nkv * hd), "fan_in"),
        b + "['wv']": ((L, d, nkv * hd), "fan_in"),
        b + "['wo']": ((L, nq * hd, d), "fan_in"),
        b + "['moe']['router']": ((L, d, E), "fan_in"),
        b + "['moe']['wg']": ((L, Eh, d, f), "fan_in"),
        b + "['moe']['wi']": ((L, Eh, d, f), "fan_in"),
        b + "['moe']['wo']": ((L, Eh, f, d), "fan_in"),
    }


def make_weights(m: Dict, seed: int, dtype: str) -> Dict[str, jax.Array]:
    """Every leaf, made from ``seed`` and rounded to ``dtype``, in one
    jitted call."""
    specs = leaf_specs(m)

    def init(key):
        return {p: dense._leaf(key, p, s, i).astype(dtype)
                for p, (s, i) in specs.items()}
    return jax.jit(init)(jax.random.PRNGKey(seed))


# --------------------------------------------------------------------- rope
def layer_kinds(m: Dict) -> Tuple[str, ...]:
    """"window" or "full" for every layer, from ``layer_types``."""
    return tuple(KINDS[t] for t in m["layer_types"])


def yarn_inv_freq(m: Dict) -> np.ndarray:
    """The full layers' (D/2,) frequencies, float64."""
    r = m["rope_parameters"]["full_attention"]
    D, base = m["head_dim"], float(r["rope_theta"])
    L0 = r["original_max_position_embeddings"]

    def corr_dim(rotations):
        return D * math.log(L0 / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr_dim(r["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(r["beta_slow"])), D - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(D // 2) - low) / (high - low), 0.0, 1.0)
    theta = base ** (np.arange(0, D, 2) / D)
    return (1.0 / (r["factor"] * theta)) * ramp + (1.0 / theta) * (1 - ramp)


def rope_tables(m: Dict, kind: str, T: int):
    """(T, D/2) cos and sin of a layer of ``kind``."""
    D = m["head_dim"]
    if kind == "full":
        inv = yarn_inv_freq(m)
        scale = m["rope_parameters"]["full_attention"]["attention_factor"]
    else:
        base = m["rope_parameters"]["sliding_attention"]["rope_theta"]
        inv = 1.0 / base ** (np.arange(0, D, 2) / D)
        scale = 1.0
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def _rope(x, cos, sin):
    """x: (T, H, D); rotate-half pairing over the whole head."""
    half = x.shape[-1] // 2
    c, s = cos[:, None, :], sin[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


# ------------------------------------------------------------------ forward
def _rms(x, scale, eps: float):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                        + eps) * scale


@partial(jax.jit, static_argnames=("m_items", "control", "window"))
def _layer(x, w, cos, sin, m_items, control, window):
    m = dict(m_items)
    mm = partial(dense._mm, control=control)
    T = x.shape[0]
    nq, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    G = nq // nkv
    f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    pos = jnp.arange(T)
    h = _rms(x, f32["ln1"], m["rms_norm_eps"])
    q = _rope(mm(h, f32["wq"]).reshape(T, nq, hd), cos, sin)
    k = _rope(mm(h, f32["wk"]).reshape(T, nkv, hd), cos, sin)
    v = mm(h, f32["wv"]).reshape(T, nkv, hd)
    kt = jnp.transpose(k, (1, 2, 0))                      # (nkv, hd, T)
    vt = jnp.transpose(v, (1, 0, 2))                      # (nkv, T, hd)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        qb = jnp.transpose(qb.reshape(Q_BLOCK, nkv, G, hd), (1, 2, 0, 3))
        s = mm(qb, kt[:, None]) / math.sqrt(hd)           # (nkv,G,Qb,T)
        qpos = (i * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None]
        seen = pos[None, :] <= qpos
        if window:
            seen = seen & (pos[None, :] > qpos - window)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        o = mm(p, vt[:, None])                            # (nkv,G,Qb,hd)
        return jnp.transpose(o, (2, 0, 1, 3)).reshape(Q_BLOCK, nq * hd)

    o = jax.lax.map(block, jnp.arange(T // Q_BLOCK)).reshape(T, nq * hd)
    x = x + mm(o, f32["wo"])

    # experts: route over all of them, compute the held ones' share
    h = _rms(x, f32["ln2"], m["rms_norm_eps"])
    probs = jax.nn.softmax(mm(h, f32["router"]), -1)     # (T, E)
    top, idx = jax.lax.top_k(probs, m["num_experts_per_tok"])
    if m["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    held = jnp.arange(m["num_experts"])
    gate = jnp.sum(jnp.where(idx[:, :, None] == held, top[:, :, None], 0.0),
                   axis=1)                                # (T, Eh)

    def expert(y, e):
        z = jax.nn.silu(mm(h, f32["wg"][e])) * mm(h, f32["wi"][e])
        return y + gate[:, e, None] * mm(z, f32["ewo"][e]), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        jnp.arange(m["num_experts"]))
    return x + y


@partial(jax.jit, static_argnames=("eps", "control"))
def _head(x, scale, table, eps, control):
    h = _rms(x, scale.astype(jnp.float32), eps)
    return dense._mm(h, table.astype(jnp.float32), control)


def logits_at(m: Dict, w: Dict[str, jax.Array], tokens: np.ndarray,
              first: int, control: Optional[str] = None) -> np.ndarray:
    """Reference logits at positions ``first .. len(tokens) - 1`` of one
    sequence, layer by layer, padded to a multiple of ``T_STEP``."""
    T = len(tokens)
    Tp = -(-T // T_STEP) * T_STEP
    toks = np.zeros((Tp,), np.int32)
    toks[:T] = tokens
    items = tuple(sorted((k, v) for k, v in m.items()
                         if isinstance(v, (int, float, str, bool))))
    tables = {kind: rope_tables(m, kind, Tp) for kind in ("window", "full")}
    x = w["['embed']"][jnp.asarray(toks)].astype(jnp.float32)
    b = "['blocks']"
    names = {"ln1": "['ln1']['scale']", "ln2": "['ln2']['scale']",
             "wq": "['wq']", "wk": "['wk']", "wv": "['wv']", "wo": "['wo']",
             "router": "['moe']['router']", "wg": "['moe']['wg']",
             "wi": "['moe']['wi']", "ewo": "['moe']['wo']"}
    for i, kind in enumerate(layer_kinds(m)):
        lw = {n: w[b + p][i] for n, p in names.items()}
        window = m["sliding_window"] if kind == "window" else 0
        x = _layer(x, lw, *tables[kind], items, control, window)
    out = _head(x[first:T], w["['final_norm']['scale']"], w["['lm_head']"],
                m["rms_norm_eps"], control)
    return np.asarray(out)


def served_gaps(m: Dict, w: Dict[str, jax.Array], prompt: np.ndarray,
                served: Sequence[int],
                control: Optional[str] = None) -> np.ndarray:
    """Per served token, reference best logit minus the reference logit
    of the token (``dense.served_gaps``'s rule, on this forward)."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    first = len(prompt) - 1
    ref = logits_at(m, w, seq, first)
    pick = served
    if control is not None:
        pick = logits_at(m, w, seq, first, control).argmax(-1)
    return ref.max(-1) - ref[np.arange(len(pick)), pick]


# --------------------------------------------------------------- registry
def program_config(cfg, m: Dict):
    """The program's registry entry (a ``ModelConfig``) with the experts
    this device holds set from the file (``num_experts`` of them, ids 0
    onward); any other difference from the file is an error."""
    import dataclasses
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_held=m["num_experts"]))
    full = m["rope_parameters"]["full_attention"]
    y = cfg.yarn
    want = {
        "n_layers": m["num_hidden_layers"], "d_model": m["hidden_size"],
        "n_heads": m["num_attention_heads"],
        "n_kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
        "vocab_size": m["vocab_size"], "sliding_window": m["sliding_window"],
        "layer_kinds": layer_kinds(m),
        "rope_theta": m["rope_parameters"]["sliding_attention"]["rope_theta"],
        "yarn": (full["factor"], full["original_max_position_embeddings"],
                 full["beta_fast"], full["beta_slow"],
                 full["attention_factor"]),
        "full_theta": full["rope_theta"],
        "n_experts": m["router_experts"],
        "experts_per_token": m["num_experts_per_tok"],
        "d_expert": m["moe_intermediate_size"], "n_shared_experts": 0,
        "norm": "rmsnorm", "mlp": "swiglu", "tie_embeddings": False,
        "qk_norm": False, "rope": "standard", "partial_rotary": 1.0,
        "family": "moe"}
    have = {k: getattr(cfg, k) for k in want if hasattr(cfg, k)}
    have.update(
        layer_kinds=tuple(cfg.layer_kind(i) for i in range(cfg.n_layers)),
        yarn=(y.factor, y.original_max_position, y.beta_fast, y.beta_slow,
              y.attention_factor) if y else None,
        full_theta=cfg.rope_theta, n_experts=cfg.moe.n_experts,
        experts_per_token=cfg.moe.experts_per_token,
        d_expert=cfg.moe.d_expert,
        n_shared_experts=cfg.moe.n_shared_experts)
    bad = {k: (have[k], v) for k, v in want.items() if have[k] != v}
    if bad or not m["norm_topk_prob"] or m["rms_norm_eps"] != 1e-6:
        raise SystemExit(f"{cfg.name}: the program's configuration differs "
                         f"from the benchmark's file: {bad}")
    return cfg


# ------------------------------------------------------ operations, bytes
class ModelCosts:
    """FLOP and byte arithmetic of the model on this device.

    As ``dense.ModelCosts``: multiply-adds count two operations, matrix
    products only, at the work the model needs; bytes are the least a
    decode step moves. Two things differ.

    * Experts. Per token, ``K * E_h / E`` routed rows land on held
      experts (K chosen of E, E_h held) and each costs a SwiGLU of width
      f. A decode step of B tokens reads the weights of the held experts
      that some token chose: under uniform routing, E_h * (1 - (1 -
      K/E)^B) of them a layer. These are expectations: the engine counts
      the real rows and experts hit (``EngineStats.expert_rows``,
      ``experts_hit``), but no counter reaches a reader yet (the harness
      copies only prefill counts into its record).
    * Window layers attend over min(context, W) rows; full layers over
      the whole context."""

    def __init__(self, model: Dict, dtype_bytes: int = 2):
        m = model
        self.L = m["num_hidden_layers"]
        self.d = m["hidden_size"]
        self.nq = m["num_attention_heads"]
        self.nkv = m["num_key_value_heads"]
        self.hd = m["head_dim"]
        self.V = m["vocab_size"]
        self.E = m["router_experts"]
        self.Eh = m["num_experts"]
        self.K = m["num_experts_per_tok"]
        self.f = m["moe_intermediate_size"]
        self.W = m["sliding_window"]
        kinds = layer_kinds(m)
        self.Lw = kinds.count("window")
        self.Lf = self.L - self.Lw
        self.b = dtype_bytes

    # ---------------------------------------------------------------- params
    @property
    def attn_params(self) -> int:
        d, hd = self.d, self.hd
        return d * self.nq * hd * 2 + d * self.nkv * hd * 2

    @property
    def expert_params(self) -> int:
        """One expert's SwiGLU."""
        return 3 * self.d * self.f

    @property
    def params(self) -> int:
        """Held on this device: attention, router, held experts, norms,
        embedding and head."""
        layer = self.attn_params + self.d * self.E \
            + self.Eh * self.expert_params + 2 * self.d
        return self.L * layer + 2 * self.d * self.V + self.d

    @property
    def kv_bytes_per_token(self) -> int:
        """Cached key and value bytes of one token, all layers."""
        return 2 * self.L * self.nkv * self.hd * self.b

    def _kv_rows(self, context: int) -> int:
        """Rows one token attends over, summed over layers."""
        return self.Lf * context + self.Lw * min(context, self.W)

    # ----------------------------------------------------------------- FLOPs
    @property
    def held_rows_per_token(self) -> float:
        return self.K * self.Eh / self.E

    def token_flops(self, context: int) -> float:
        """One token through every layer, attending over ``context``
        rows (itself included; at most W in a window layer); no output
        projection."""
        per_layer = 2 * (self.attn_params + self.d * self.E
                         + self.held_rows_per_token * self.expert_params)
        return (self.L * per_layer
                + 4 * self.nq * self.hd * self._kv_rows(context))

    @property
    def logits_flops(self) -> int:
        return 2 * self.d * self.V

    def prefill_flops(self, prompt_len: int) -> float:
        """A prompt's real tokens, token t at context t + 1, and the
        logits of its last token."""
        P = prompt_len
        dense_part = self.token_flops(0) * P
        pairs = self.Lf * P * (P + 1) // 2 + self.Lw * sum(
            min(t, self.W) for t in range(1, P + 1))
        return dense_part + 4 * self.nq * self.hd * pairs \
            + self.logits_flops

    def decode_flops(self, contexts: Iterable[int]) -> float:
        """One decode step; ``contexts`` are the active sequences'
        context lengths including the new token."""
        return sum(self.token_flops(c) + self.logits_flops
                   for c in contexts)

    # ----------------------------------------------------------------- bytes
    def experts_read(self, batch: int) -> float:
        """Held experts a layer reads in a step of ``batch`` tokens,
        expected under uniform routing."""
        return self.Eh * (1.0 - (1.0 - self.K / self.E) ** batch)

    def decode_bytes(self, contexts: Iterable[int]) -> float:
        """Least bytes one decode step moves over HBM."""
        ctx = list(contexts)
        B = len(ctx)
        weights = (self.L * (self.attn_params + self.d * self.E
                             + self.experts_read(B) * self.expert_params
                             + 2 * self.d)
                   + self.d + self.d * self.V + B * self.d)
        row = 2 * self.nkv * self.hd * self.b       # one layer's k and v
        kv_read = sum(self._kv_rows(c) - self.L for c in ctx) * row
        kv_write = B * self.L * row
        return weights * self.b + kv_read + kv_write

    def decode_seconds_bound(self, contexts: Iterable[int],
                             peak: Dict[str, float]) -> float:
        """The decode step's roofline: the larger of its operations over
        peak FLOP/s and its bytes over peak bandwidth."""
        ctx = list(contexts)
        return max(self.decode_flops(ctx) / peak["bf16_flops"],
                   self.decode_bytes(ctx) / peak["hbm_bytes_per_s"])
