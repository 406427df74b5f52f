"""Shared model primitives: parameter definitions, norms, RoPE variants,
activations, and the cross-entropy loss.

Everything is a pure function over explicit parameter pytrees; parameter
*definitions* (shape + logical sharding axes + initializer) are data, so
``init``, ``jax.eval_shape`` abstract trees, and sharding-spec trees all
derive from one source of truth.
"""
from __future__ import annotations

import math
import zlib
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, YarnRope
from repro.kernels.dispatch import RMSNORM_EPS, KernelPolicy, dispatch


# ===========================================================================
# Parameter definition table
# ===========================================================================
@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]     # logical sharding axes, len == ndim
    init: str = "fan_in"                # fan_in | embed | zeros | ones | const
    scale: float = 1.0
    dtype: str = "float32"              # master params stay f32

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


DefTree = Union[ParamDef, Dict[str, "DefTree"]]


def _is_def(x) -> bool:
    return isinstance(x, ParamDef)


def init_from_defs(defs: DefTree, key: jax.Array):
    """Deterministic init: each leaf's key is folded from its path.

    The path hash must be stable across *processes* (``hash()`` is
    salted per interpreter run), or the same PRNGKey silently yields
    different parameters in every invocation.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(defs, is_leaf=_is_def)

    leaves = []
    for path, d in flat:
        h = zlib.crc32(jax.tree_util.keystr(path).encode()) % (2**31 - 1)
        k = jax.random.fold_in(key, h)
        leaves.append(_init_leaf(d, k))
    return jax.tree.unflatten(treedef, leaves)


def _init_leaf(d: ParamDef, key: jax.Array) -> jax.Array:
    dt = jnp.dtype(d.dtype)
    if d.init == "zeros":
        return jnp.zeros(d.shape, dt)
    if d.init == "ones":
        return jnp.ones(d.shape, dt)
    if d.init == "const":
        return jnp.full(d.shape, d.scale, dt)
    if d.init == "embed":
        return (jax.random.normal(key, d.shape, dt) * 0.02 * d.scale)
    # fan_in: stddev = scale / sqrt(fan_in); fan_in = second-to-last dim
    # (weights stored (in, out)); stacked layer dims excluded by
    # convention: fan_in = shape[-2] for ndim >= 2 else shape[-1].
    fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
    std = d.scale / math.sqrt(max(1, fan_in))
    return jax.random.truncated_normal(key, -2.0, 2.0, d.shape, dt) * std


def axes_from_defs(defs: DefTree):
    return jax.tree.map(lambda d: d.axes, defs, is_leaf=_is_def)


def abstract_from_defs(defs: DefTree):
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype)),
        defs, is_leaf=_is_def)


# ===========================================================================
# Norms (compute in f32, cast back)
# ===========================================================================
def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = RMSNORM_EPS,
            policy: Optional[KernelPolicy] = None) -> jax.Array:
    """RMSNorm, routed through the kernel dispatch layer.

    ``policy=None`` (or an xla policy) runs the pure-jnp path; a pallas
    policy runs the fused VPU kernel. ``eps`` threads through dispatch
    into whichever implementation runs — the single source of truth for
    the epsilon both paths previously hardcoded independently.
    """
    return dispatch("rmsnorm", policy, x, scale, eps=eps)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dt)


def norm(x: jax.Array, p: Dict[str, jax.Array], kind: str,
         policy: Optional[KernelPolicy] = None) -> jax.Array:
    if kind == "layernorm":
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"], policy=policy)


def norm_defs(d_model: int, kind: str) -> Dict[str, ParamDef]:
    out = {"scale": ParamDef((d_model,), (None,), "ones")}
    if kind == "layernorm":
        out["bias"] = ParamDef((d_model,), (None,), "zeros")
    return out


# ===========================================================================
# RoPE (standard / partial / 2d / M-RoPE)
# ===========================================================================
def rotary_dims(cfg: ModelConfig) -> int:
    rot = int(cfg.head_dim * cfg.partial_rotary)
    return rot - (rot % 2)


def yarn_inv_freq(rot: int, theta: float, yarn: YarnRope) -> jax.Array:
    """YaRN's (rot/2,) frequencies, as Hugging Face's
    ``_compute_yarn_parameters`` computes them (correction range
    truncated to whole dimensions)."""
    def corr_dim(rotations: float) -> float:
        return (rot * math.log(yarn.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), rot - 1)
    if low == high:
        high += 0.001
    half = rot // 2
    extrap = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    # ramp 0: keep the frequency; ramp 1: divide it by the factor
    return extrap / yarn.factor * ramp + extrap * (1.0 - ramp)


def _rope_cos_sin(positions: jax.Array, rot: int, theta: float,
                  sections: Tuple[int, ...] = (),
                  yarn: Optional[YarnRope] = None,
                  ) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables (..., rot/2).

    positions: (B, S) for standard RoPE, or (3, B, S) for M-RoPE where
    the leading axis is (temporal, height, width) and ``sections`` gives
    the number of frequency *pairs* assigned to each component. With
    ``yarn`` the frequencies are YaRN's and both tables are scaled by its
    attention factor.
    """
    half = rot // 2
    if yarn is not None:
        inv_freq = yarn_inv_freq(rot, theta, yarn)
    else:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32)
                                    / half))
    if sections:
        assert positions.ndim == 3, "M-RoPE needs (3, B, S) positions"
        assert sum(sections) == half, (sections, half)
        freqs_parts = []
        start = 0
        for comp, sec in enumerate(sections):
            f = positions[comp][..., None].astype(jnp.float32) \
                * inv_freq[start:start + sec]
            freqs_parts.append(f)
            start += sec
        freqs = jnp.concatenate(freqs_parts, axis=-1)       # (B, S, half)
    else:
        if positions.ndim == 3:      # text fed to an M-RoPE-less model
            positions = positions[0]
        freqs = positions[..., None].astype(jnp.float32) * inv_freq
    if yarn is not None:
        a = yarn.attention_factor
        return jnp.cos(freqs) * a, jnp.sin(freqs) * a
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(q: jax.Array, k: jax.Array, positions: jax.Array,
               cfg: ModelConfig, kind: Optional[str] = None,
               ) -> Tuple[jax.Array, jax.Array]:
    """q: (B, S, Hq, hd), k: (B, S, Hkv, hd); positions (B,S) or (3,B,S).

    ``kind`` is the layer's attention kind: ``cfg.yarn`` scales every
    layer but the "window" ones."""
    if cfg.rope == "none":
        return q, k
    rot = rotary_dims(cfg)
    yarn = cfg.yarn if kind != "window" else None
    cos, sin = _rope_cos_sin(positions, rot, cfg.rope_theta,
                             cfg.mrope_sections if cfg.rope == "mrope" else (),
                             yarn=yarn)
    cos = cos[:, :, None, :]      # (B, S, 1, rot/2)
    sin = sin[:, :, None, :]

    def rotate(x):
        xr, xp = x[..., :rot], x[..., rot:]
        x1, x2 = jnp.split(xr, 2, axis=-1)
        out1 = x1 * cos - x2 * sin
        out2 = x2 * cos + x1 * sin
        return jnp.concatenate(
            [out1.astype(x.dtype), out2.astype(x.dtype), xp], axis=-1)

    return rotate(q), rotate(k)


# ===========================================================================
# Activations + loss
# ===========================================================================
def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate) * up


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token cross-entropy; logits promoted to f32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return jnp.mean(logz - gold)
