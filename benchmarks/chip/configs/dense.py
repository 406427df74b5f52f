"""A dense decoder-only transformer, as a configuration file's ``model``
describes it: its plain reference forward pass, its operations and
bytes, and the program's registry entry set to the file.
Configuration files name this module with ``"reference": "dense"``.

Reference
---------

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``; no kernels, no cache, no batching, no bucketing.
It imports nothing of the program under test. The weights are made
again here from the run's seed by the same published recipe as the
program's initialiser (a key per leaf folded from the CRC-32 of its tree
path; truncated normals scaled by 1/sqrt(fan-in), embeddings at 0.02),
then rounded to the serving dtype, since the configuration serves those
rounded weights. A CPU test checks that the two recipes agree bit for
bit.

Architecture, per layer: pre-norm (LayerNorm with bias, or RMSNorm),
grouped-query causal attention with rotary embeddings on the whole head
(rotate-half pairing), residual, pre-norm MLP (tanh-GELU, or SwiGLU),
residual; then a final norm and the output projection (the embedding
table, transposed, when tied).

``control="fp8"`` rounds both operands of every matrix product to
float8 e4m3 with a per-tensor scale: the reference one precision below
the bfloat16 that the configuration serves in.

The comparison that decides a run's ``correct``: for each served token,
the gap by which the reference's logit of that token lies below the
reference's best logit at the same position. Greedy serving in exact
arithmetic reads 0; the run's number is the widest gap.
"""
from __future__ import annotations

import math
import zlib
from functools import partial
from typing import Dict, Iterable, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block
T_STEP = 1024          # sequences are padded to a multiple of this


# ------------------------------------------------------------------ weights
def leaf_specs(m: Dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Tree path -> (shape, init) of every parameter leaf."""
    L, d, V = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    nq, nkv, hd, ff = (m["num_attention_heads"], m["num_key_value_heads"],
                       m["head_dim"], m["intermediate_size"])
    out = {"['embed']": ((V, d), "embed")}

    def norm(prefix, stack):
        out[prefix + "['scale']"] = (stack + (d,), "ones")
        if m["norm"] == "layernorm":
            out[prefix + "['bias']"] = (stack + (d,), "zeros")

    norm("['final_norm']", ())
    if not m["tie_word_embeddings"]:
        out["['lm_head']"] = ((d, V), "fan_in")
    b = "['blocks']"
    norm(b + "['ln1']", (L,))
    norm(b + "['ln2']", (L,))
    out[b + "['wq']"] = ((L, d, nq * hd), "fan_in")
    out[b + "['wk']"] = ((L, d, nkv * hd), "fan_in")
    out[b + "['wv']"] = ((L, d, nkv * hd), "fan_in")
    out[b + "['wo']"] = ((L, nq * hd, d), "fan_in")
    if m["mlp"] == "swiglu":
        out[b + "['wg']"] = ((L, d, ff), "fan_in")
    out[b + "['wi']"] = ((L, d, ff), "fan_in")
    out[b + "['wo2']"] = ((L, ff, d), "fan_in")
    return out


def _leaf(key, path: str, shape, init: str):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) % (2 ** 31 - 1))
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if init == "embed":
        return jax.random.normal(k, shape, jnp.float32) * 0.02
    std = 1.0 / math.sqrt(max(1, shape[-2] if len(shape) >= 2 else shape[-1]))
    return jax.random.truncated_normal(k, -2.0, 2.0, shape,
                                       jnp.float32) * std


def make_weights(m: Dict, seed: int, dtype: str) -> Dict[str, jax.Array]:
    """Every leaf, made from ``seed`` and rounded to ``dtype``, in one
    jitted call."""
    specs = leaf_specs(m)

    def init(key):
        return {p: _leaf(key, p, s, i).astype(dtype)
                for p, (s, i) in specs.items()}
    return jax.jit(init)(jax.random.PRNGKey(seed))


# ------------------------------------------------------------------ forward
def _q8(a):
    """Round to float8 e4m3 with a per-tensor scale, back to float32."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, control: Optional[str]):
    if control == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.matmul(a, b, precision=HI)


def _norm(x, scale, bias, kind: str, eps: float):
    if kind == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * scale + bias
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                        + eps) * scale


def _rope(x, pos, theta: float):
    """x: (T, H, D); rotate-half pairing over the whole head."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv          # (T, half)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@partial(jax.jit, static_argnames=("m_items", "control"))
def _layer(x, w, m_items, control):
    m = dict(m_items)
    T = x.shape[0]
    nq, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    G = nq // nkv
    f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    bias = lambda n: f32.get(n + "_bias", 0.0)
    pos = jnp.arange(T)
    h = _norm(x, f32["ln1_scale"], bias("ln1"), m["norm"], m["norm_eps"])
    q = _rope(_mm(h, f32["wq"], control).reshape(T, nq, hd), pos,
              m["rope_theta"])
    k = _rope(_mm(h, f32["wk"], control).reshape(T, nkv, hd), pos,
              m["rope_theta"])
    v = _mm(h, f32["wv"], control).reshape(T, nkv, hd)
    kt = jnp.transpose(k, (1, 2, 0))                      # (nkv, hd, T)
    vt = jnp.transpose(v, (1, 0, 2))                      # (nkv, T, hd)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        qb = jnp.transpose(qb.reshape(Q_BLOCK, nkv, G, hd), (1, 2, 0, 3))
        s = _mm(qb, kt[:, None], control) / math.sqrt(hd)  # (nkv,G,Qb,T)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        o = _mm(p, vt[:, None], control)                  # (nkv,G,Qb,hd)
        return jnp.transpose(o, (2, 0, 1, 3)).reshape(Q_BLOCK, nq * hd)

    o = jax.lax.map(block, jnp.arange(T // Q_BLOCK)).reshape(T, nq * hd)
    x = x + _mm(o, f32["wo"], control)
    h = _norm(x, f32["ln2_scale"], bias("ln2"), m["norm"], m["norm_eps"])
    if m["mlp"] == "swiglu":
        z = jax.nn.silu(_mm(h, f32["wg"], control)) * _mm(h, f32["wi"],
                                                          control)
    else:
        u = _mm(h, f32["wi"], control)
        z = 0.5 * u * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                      * (u + 0.044715 * u ** 3)))
    return x + _mm(z, f32["wo2"], control)


@partial(jax.jit, static_argnames=("m_items", "control"))
def _head(x, scale, bias, table, m_items, control):
    m = dict(m_items)
    h = _norm(x, scale.astype(jnp.float32), bias, m["norm"], m["norm_eps"])
    return _mm(h, table.astype(jnp.float32), control)


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
    x = w["['embed']"][jnp.asarray(toks)].astype(jnp.float32)
    b = "['blocks']"
    names = {"ln1_scale": "['ln1']['scale']", "ln1_bias": "['ln1']['bias']",
             "ln2_scale": "['ln2']['scale']", "ln2_bias": "['ln2']['bias']",
             "wq": "['wq']", "wk": "['wk']", "wv": "['wv']", "wo": "['wo']",
             "wg": "['wg']", "wi": "['wi']", "wo2": "['wo2']"}
    for i in range(m["num_hidden_layers"]):
        lw = {n: w[b + p][i] for n, p in names.items() if b + p in w}
        x = _layer(x, lw, items, control)
    table = (w["['embed']"].T if m["tie_word_embeddings"]
             else w["['lm_head']"])
    fb = w.get("['final_norm']['bias']")
    rows = x[first:T]
    out = _head(rows, w["['final_norm']['scale']"],
                0.0 if fb is None else fb.astype(jnp.float32), table, items,
                control)
    return np.asarray(out)


def served_gaps(m: Dict, w: Dict[str, jax.Array], prompt: np.ndarray,
                served: Sequence[int],
                control: Optional[str] = None) -> np.ndarray:
    """Per served token, reference best logit minus the reference logit
    of the token. With ``control``, the token at each position is the
    one that the control's own logits put first, on the same prompt and
    served tokens."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    first = len(prompt) - 1
    ref = logits_at(m, w, seq, first)
    pick = served
    if control is not None:
        pick = logits_at(m, w, seq, first, control).argmax(-1)
    return ref.max(-1) - ref[np.arange(len(pick)), pick]


# --------------------------------------------------------------- registry
#: ``ModelConfig`` fields that the program takes from the configuration
#: file; every other field of the registry entry has to match the file
FROM_FILE = {"rope_theta": "rope_theta",
             "tie_embeddings": "tie_word_embeddings"}


def program_config(cfg, m: Dict):
    """The program's registry entry (a ``ModelConfig``), set to the
    configuration file's ``model`` where the program has the option
    (``FROM_FILE``); any other difference from the file is an error."""
    import dataclasses
    cfg = dataclasses.replace(cfg, **{f: m[k] for f, k in FROM_FILE.items()})
    want = {"n_layers": m["num_hidden_layers"], "d_model": m["hidden_size"],
            "n_heads": m["num_attention_heads"],
            "n_kv_heads": m["num_key_value_heads"],
            "head_dim": m["head_dim"], "d_ff": m["intermediate_size"],
            "vocab_size": m["vocab_size"], "norm": m["norm"],
            "mlp": {"gelu_tanh": "gelu"}.get(m["mlp"], m["mlp"]),
            "sliding_window": 0, "rope": "standard", "partial_rotary": 1.0,
            "qk_norm": False, "family": "dense", "moe": None, "ssm": None}
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    if bad:
        raise SystemExit(f"{cfg.name}: the program's configuration differs "
                         f"from the benchmark's file: {bad}")
    return cfg


# ------------------------------------------------------ operations, bytes
class ModelCosts:
    """FLOP and byte arithmetic of the model.

    FLOPs count multiply-adds as two operations, matrix products only, at
    the work the model needs: the projections and the MLP for every
    token processed, attention at each token's own context, and the
    output projection only where logits are produced (the last prompt
    token and every decoded token). Bytes are the least a decode step
    moves: every weight once (only the looked-up rows of an untied
    embedding table), each active sequence's cached keys and values read
    once, and its new row written."""

    def __init__(self, model: Dict, dtype_bytes: int = 2):
        m = model
        self.L = m["num_hidden_layers"]
        self.d = m["hidden_size"]
        self.nq = m["num_attention_heads"]
        self.nkv = m["num_key_value_heads"]
        self.hd = m["head_dim"]
        self.ff = m["intermediate_size"]
        self.V = m["vocab_size"]
        self.tied = bool(m["tie_word_embeddings"])
        self.gated = m["mlp"] == "swiglu"
        self.norm_params = 2 if m["norm"] == "layernorm" else 1
        self.b = dtype_bytes

    # ---------------------------------------------------------------- params
    @property
    def layer_matmul_params(self) -> int:
        d, hd = self.d, self.hd
        attn = d * self.nq * hd * 2 + d * self.nkv * hd * 2
        mlp = (3 if self.gated else 2) * d * self.ff
        return attn + mlp

    @property
    def params(self) -> int:
        norms = (2 * self.L + 1) * self.norm_params * self.d
        head = 0 if self.tied else self.d * self.V
        return (self.L * self.layer_matmul_params + self.d * self.V + head
                + norms)

    @property
    def kv_bytes_per_token(self) -> int:
        """Cached key and value bytes of one token, all layers."""
        return 2 * self.L * self.nkv * self.hd * self.b

    # ----------------------------------------------------------------- FLOPs
    def token_flops(self, context: int) -> int:
        """One token through every layer, attending over ``context``
        cached rows (itself included); no output projection."""
        return self.L * (2 * self.layer_matmul_params
                         + 4 * self.nq * self.hd * context)

    @property
    def logits_flops(self) -> int:
        return 2 * self.d * self.V

    def prefill_flops(self, prompt_len: int) -> int:
        """A prompt's real tokens, token t at context t + 1, and the
        logits of its last token."""
        P = prompt_len
        return (self.L * (2 * self.layer_matmul_params * P
                          + 4 * self.nq * self.hd * P * (P + 1) // 2)
                + self.logits_flops)

    def decode_flops(self, contexts: Iterable[int]) -> int:
        """One decode step; ``contexts`` are the active sequences'
        context lengths including the new token."""
        return sum(self.token_flops(c) + self.logits_flops
                   for c in contexts)

    # ----------------------------------------------------------------- bytes
    def decode_bytes(self, contexts: Iterable[int]) -> int:
        """Least bytes one decode step moves over HBM."""
        ctx = list(contexts)
        B = len(ctx)
        weights = (self.L * self.layer_matmul_params
                   + (2 * self.L + 1) * self.norm_params * self.d)
        if self.tied:
            weights += self.d * self.V            # read whole for logits
        else:
            weights += self.d * self.V + B * self.d   # head + looked-up rows
        kv_read = sum(c - 1 for c in ctx) * self.kv_bytes_per_token
        kv_write = B * self.kv_bytes_per_token
        return weights * self.b + kv_read + kv_write

    def decode_seconds_bound(self, contexts: Iterable[int],
                             peak: Dict[str, float]) -> float:
        """The decode step's roofline: the larger of its operations over
        peak FLOP/s and its bytes over peak bandwidth."""
        ctx = list(contexts)
        return max(self.decode_flops(ctx) / peak["bf16_flops"],
                   self.decode_bytes(ctx) / peak["hbm_bytes_per_s"])
