"""Closed-form HBM capacity model: the pre-execution twin of
``compiled.memory_analysis()``.

:func:`capacity` predicts, per device, the argument residency and the
peak HBM of one train / prefill / decode step from pure shape math —
no tracing, no lowering, no devices. It mirrors exactly what XLA's
argument accounting does (validated byte-exact against every OK ci
dry-run cell) and predicts the peak with per-kind coefficients
calibrated once against the same cells (nnls on the dry-run corpus;
max observed relative error 6.4% decode / 7.1% prefill / 9.4% train —
see ``tests/test_analysis_perf.py`` for the 25% acceptance bar).

Three consumers:

* ``launch/serve.py --preflight`` — reject an oversized serving config
  (``n_slots``/``max_len``/page budget beyond HBM) before allocating
  anything, naming ``capacity-hbm-overflow``;
* the deployment-space DSE — a feasibility gate it can evaluate
  thousands of times without compiling a candidate;
* the ``spmd_lint`` pass — per-cell ``spmd-memory-drift`` findings when
  a dry-run artifact's measured peak diverges from this model.

The argument model is *exact*, not calibrated: per-leaf sharded bytes
through the real ``sanitize_spec`` + ``Recipe.spec_for`` (so silent
spec drops divide — or don't — exactly as they do in production),
train args = 3x f32 params + the step scalar + the batch, prefill args
drop the dead token table when the frontend feeds embeddings (XLA
prunes it), decode args add the KV/state cache and the ``(B,)`` token
vector.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

#: KV-chunk length the runtime scans attention at (ModelRuntime's
#: default ``attn_chunk``); the prefill/train scores feature below is
#: per-chunk. ``liveness`` cross-checks this against the live default
#: (``capacity-spec-drift``).
ATTN_CHUNK = 512

#: Per-kind peak-model coefficients, fitted (non-negative least
#: squares, relative-error weighted) against the 64 OK ci dry-run
#: cells. ``one`` is a constant offset in units of 1e6 bytes.
CALIBRATION: Dict[str, Dict[str, float]] = {
    "decode": {"params": 2.482, "cache": 2.333, "cache_allsh": 0.249,
               "kv_ms": 1.551},
    "prefill": {"scores": 1.473, "moe": 2.330, "ssm": 0.046,
                "act": 1.935, "one": 3.261},
    "train": {"params": 21.772, "scores": 4.501, "moe": 3.033,
              "ssm": 10.441, "logits": 1.942},
}

CALIBRATION_VERSION = 1

#: The acceptance bar the parity test enforces per cell.
PARITY_REL_TOL = 0.25


# ===========================================================================
# Sharded byte accounting (the exact argument model)
# ===========================================================================
class _ProxyMesh:
    """Duck-typed stand-in ``sanitize_spec`` accepts: axis names +
    sizes, no devices behind them."""

    def __init__(self, sizes: Dict[str, int]):
        self.axis_names = tuple(sizes)
        self.axis_sizes = tuple(int(v) for v in sizes.values())


def mesh_sizes(mesh) -> Dict[str, int]:
    """Axis-name -> size from a dict, a ``jax`` Mesh, or a
    ``launch.presets.MeshSpec``; ``None`` -> one replicated device."""
    if mesh is None:
        return {"data": 1, "model": 1}
    if isinstance(mesh, dict):
        return {k: int(v) for k, v in mesh.items()}
    if hasattr(mesh, "axes") and hasattr(mesh, "shape"):      # MeshSpec
        return dict(zip(mesh.axes, (int(s) for s in mesh.shape)))
    if hasattr(mesh, "axis_names"):                           # jax Mesh
        shape = mesh.devices.shape if hasattr(mesh, "devices") \
            else mesh.axis_sizes
        return dict(zip(mesh.axis_names, (int(s) for s in shape)))
    raise TypeError(f"cannot read mesh axis sizes from {type(mesh)!r}")


def shard_factor(spec, shape: Tuple[int, ...],
                 sizes: Dict[str, int]) -> int:
    """How many ways ``sanitize_spec`` actually divides ``shape`` —
    the production divisibility/reuse drops included."""
    from repro.dist.sharding import sanitize_spec

    s = sanitize_spec(spec, shape, _ProxyMesh(sizes))
    f = 1
    for e in tuple(s):
        if e is None:
            continue
        for ax in ((e,) if isinstance(e, str) else e):
            f *= sizes[ax]
    return f


def sharded_bytes(shape: Tuple[int, ...], itemsize: int, spec,
                  sizes: Dict[str, int]) -> int:
    n = math.prod(shape) if shape else 1
    return (n // shard_factor(spec, shape, sizes)) * itemsize


def tree_sharded_bytes(ab, axes, recipe, sizes: Dict[str, int]) -> int:
    """Per-device bytes of an abstract tree under ``recipe``; ``axes``
    is the parallel logical-axes tree (``axes_tree``/``CACHE_AXES``)."""
    import jax
    import jax.numpy as jnp

    from repro.dist.sharding import _is_axes_leaf

    leaves = jax.tree_util.tree_leaves(ab)
    axleaves = jax.tree_util.tree_leaves(axes, is_leaf=_is_axes_leaf)
    if len(leaves) != len(axleaves):
        raise ValueError(f"abstract tree has {len(leaves)} leaves but "
                         f"axes tree has {len(axleaves)}")
    total = 0
    for leaf, ax in zip(leaves, axleaves):
        ax = ax or (None,) * len(leaf.shape)
        total += sharded_bytes(tuple(leaf.shape),
                               jnp.dtype(leaf.dtype).itemsize,
                               recipe.spec_for(ax), sizes)
    return total


def tree_global_bytes(ab) -> int:
    import jax
    import jax.numpy as jnp

    return sum(math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize
               for leaf in jax.tree_util.tree_leaves(ab))


#: Input batches are sharded over (pod, data) on dim 0 everywhere
#: (``launch.lowering.input_specs``).
_BATCH_SPEC = ("pod", "data")


def _batch_bytes(cfg, B: int, S: int, sizes: Dict[str, int],
                 kind: str) -> int:
    if kind == "decode":
        return sharded_bytes((B,), 4, (_BATCH_SPEC,), sizes)
    if cfg.frontend == "token":
        total = sharded_bytes((B, S), 4, (_BATCH_SPEC, None), sizes)
    else:   # embeddings in: (B, S, d_model) bf16
        total = sharded_bytes((B, S, cfg.d_model), 2,
                              (_BATCH_SPEC, None, None), sizes)
    if kind == "train":     # labels
        total += sharded_bytes((B, S), 4, (_BATCH_SPEC, None), sizes)
    return total


def _abstract_cache_tree(cfg, B: int, kv_len: int,
                         kv_dtype: Optional[str] = None):
    import jax
    import jax.numpy as jnp

    from repro.models.model import CACHE_AXES, cache_spec

    cs = cache_spec(cfg, B, kv_len, kv_dtype=kv_dtype)
    ab = {k: jax.ShapeDtypeStruct(s, jnp.dtype(d))
          for k, (s, d) in cs.items()}
    return ab, {k: CACHE_AXES[k] for k in cs}, cs


def _abstract_paged_cache_tree(cfg, n_slots: int, page_budget: int,
                               page_size: int, max_len: int,
                               kv_dtype: Optional[str] = None):
    import jax
    import jax.numpy as jnp

    from repro.models.model import paged_cache_axes, paged_cache_spec

    cs = paged_cache_spec(cfg, n_slots, page_budget, page_size, max_len,
                          kv_dtype=kv_dtype)
    ab = {k: jax.ShapeDtypeStruct(s, jnp.dtype(d))
          for k, (s, d) in cs.items()}
    axes = paged_cache_axes(cfg, kv_dtype)
    return ab, {k: axes[k] for k in cs}, cs


# ===========================================================================
# Peak-model features
# ===========================================================================
def _peak_features(cfg, B: int, S: int, sizes: Dict[str, int],
                   kind: str) -> Dict[str, float]:
    dp = sizes.get("pod", 1) * sizes.get("data", 1)
    ms = sizes.get("model", 1)
    H = cfg.n_heads
    bdiv = dp if B % dp == 0 else 1
    hdiv = ms if H % ms == 0 else 1
    tok = B * S
    attn = cfg.family in ("dense", "moe", "vlm", "audio", "hybrid")
    f: Dict[str, float] = {"one": 1e6}
    f["act"] = tok * cfg.d_model * 4 / bdiv
    f["scores"] = (B * H * S * min(S, ATTN_CHUNK) * 4 / (bdiv * hdiv)
                   if attn and kind != "decode" else 0.0)
    f["logits"] = tok * cfg.vocab_size * 4 / bdiv
    if cfg.moe is not None and kind != "decode":
        E = cfg.moe.n_experts
        cap = math.ceil(cfg.moe.capacity_factor
                        * cfg.moe.experts_per_token * tok / E)
        f["moe"] = (tok / bdiv) * E * cap * 4
    else:
        f["moe"] = 0.0
    if cfg.ssm is not None and kind != "decode":
        s = cfg.ssm
        d_inner = cfg.d_model * s.expand
        n_heads = d_inner // s.head_dim
        n_chunks = max(1, S // s.chunk_size)
        f["ssm"] = (B * n_chunks * n_heads * s.head_dim * s.d_state * 4
                    + B * S * d_inner * 4) / bdiv
    else:
        f["ssm"] = 0.0
    return f


#: KV-cache leaves (payload + int8 scale side-bands) — the keys every
#: byte-accounting sum walks.
KV_LEAVES = ("k", "v", "kp", "vp", "ks", "vs")


def _kv_leaf_keys(cache_tree) -> Tuple[str, ...]:
    return tuple(k for k in cache_tree if k in KV_LEAVES)


# ===========================================================================
# CapacityReport + capacity()
# ===========================================================================
@dataclass(frozen=True)
class CapacityReport:
    """Per-device HBM accounting of one step, from pure shape math."""

    kind: str                       # train | prefill | decode
    recipe: str
    mesh_sizes: Dict[str, int]
    devices: int
    argument_bytes: int             # exact (mirrors memory_analysis)
    peak_bytes: int                 # calibrated prediction
    params_bytes: int               # sharded, at the step's param dtype
    cache_bytes: int                # sharded KV/state (decode only)
    batch_bytes: int
    hbm_bytes: int                  # per-chip budget gated against
    fits: bool
    utilization: float              # peak / hbm
    features: Dict[str, float] = field(default_factory=dict)
    coefficients: Dict[str, float] = field(default_factory=dict)
    notes: Tuple[str, ...] = ()

    def to_json(self) -> Dict[str, Any]:
        return {
            "calibration_version": CALIBRATION_VERSION,
            "kind": self.kind, "recipe": self.recipe,
            "mesh_sizes": dict(self.mesh_sizes), "devices": self.devices,
            "argument_bytes": self.argument_bytes,
            "peak_bytes": self.peak_bytes,
            "params_bytes": self.params_bytes,
            "cache_bytes": self.cache_bytes,
            "batch_bytes": self.batch_bytes,
            "hbm_bytes": self.hbm_bytes, "fits": self.fits,
            "utilization": round(self.utilization, 4),
            "features": {k: float(v) for k, v in self.features.items()},
            "notes": list(self.notes),
        }


def capacity(cfg, shape=None, mesh=None, recipe=None, *,
             n_slots: Optional[int] = None,
             page_budget: Optional[int] = None,
             page_size: int = 8,
             max_len: Optional[int] = None,
             chip=None,
             param_dtype: Optional[str] = None,
             kv_dtype: Optional[str] = None) -> CapacityReport:
    """Predict one step's per-device HBM residency and peak.

    Either pass a ``ShapeConfig`` (``shape``) — the dry-run-cell form —
    or describe a serving config with ``n_slots`` + ``max_len``
    (contiguous cache) and optionally ``page_budget``/``page_size``
    (paged pool); the serving forms imply ``kind='decode'``.

    ``mesh`` is a dict of axis sizes, a ``MeshSpec``, a jax ``Mesh``,
    or ``None`` (one device). ``recipe`` is a ``dist.sharding.Recipe``,
    a recipe name, or ``None`` for ``launch.lowering.default_recipe``.
    Nothing here touches a device: safe for the DSE inner loop and for
    ``--preflight`` before any allocation.
    """
    import jax
    import jax.numpy as jnp

    from repro.models.model import abstract_params, axes_tree

    sizes = mesh_sizes(mesh)
    devices = math.prod(sizes.values()) if sizes else 1
    notes: list = []

    if shape is None:
        if n_slots is None or max_len is None:
            raise ValueError("pass a ShapeConfig, or n_slots= + max_len= "
                             "for a serving config")
        from repro.configs.base import ShapeConfig
        shape = ShapeConfig(name=f"serve_{n_slots}x{max_len}",
                            seq_len=1, global_batch=int(n_slots),
                            kind="decode", kv_len=int(max_len))
    kind = shape.kind

    if recipe is None:
        from repro.launch.lowering import default_recipe
        recipe = default_recipe(cfg, shape, sizes.get("model", 1))
    elif isinstance(recipe, str):
        from repro.dist.sharding import RECIPES
        recipe = RECIPES[recipe]

    ax = axes_tree(cfg)
    B, S = shape.global_batch, shape.seq_len
    if param_dtype is None:
        param_dtype = "float32" if kind == "train" else "bfloat16"
    params_ab = abstract_params(cfg, param_dtype)

    cache_b = 0
    cache_global = 0.0
    kv_global = 0.0
    if kind == "train":
        pb = tree_sharded_bytes(params_ab, ax, recipe, sizes)
        args = pb * 3 + 4 + _batch_bytes(cfg, B, S, sizes, kind)
    elif kind == "prefill":
        ax2 = ax
        if cfg.frontend != "token":
            # XLA prunes the dead token table when embeddings feed in
            params_ab = dict(params_ab)
            ax2 = dict(ax)
            params_ab.pop("embed", None)
            ax2.pop("embed", None)
            notes.append("embed table pruned (non-token frontend)")
        pb = tree_sharded_bytes(params_ab, ax2, recipe, sizes)
        args = pb + _batch_bytes(cfg, B, S, sizes, kind)
        ax = ax2
    else:
        pb = tree_sharded_bytes(params_ab, ax, recipe, sizes)
        kv_len = getattr(shape, "kv_len", None) or shape.seq_len
        if page_budget is not None:
            cache_ab, cache_ax, cs = _abstract_paged_cache_tree(
                cfg, B, page_budget, page_size, kv_len, kv_dtype)
            notes.append(f"paged cache: {page_budget} pages x "
                         f"{page_size} tokens")
        else:
            cache_ab, cache_ax, cs = _abstract_cache_tree(
                cfg, B, kv_len, kv_dtype)
        if kv_dtype is not None and kv_dtype != "bfloat16":
            notes.append(f"kv_dtype={kv_dtype}")
        cache_b = tree_sharded_bytes(cache_ab, cache_ax, recipe, sizes)
        cache_global = tree_global_bytes(cache_ab)
        kv_global = sum(
            math.prod(s) * jnp.dtype(d).itemsize
            for k, (s, d) in cs.items() if k in KV_LEAVES)
        args = pb + cache_b + _batch_bytes(cfg, B, S, sizes, kind)

    batch_b = _batch_bytes(cfg, B, S, sizes, kind)

    # -- calibrated peak ----------------------------------------------------
    feats = _peak_features(cfg, B, S, sizes, kind)
    feats["params"] = float(pb)
    feats["cache"] = float(cache_b)
    feats["cache_allsh"] = cache_global / devices
    dp = sizes.get("pod", 1) * sizes.get("data", 1)
    ms = sizes.get("model", 1)
    bdiv = dp if B % dp == 0 else 1
    feats["kv_ms"] = (kv_global / (ms * bdiv)
                      if cfg.n_kv_heads % ms == 0 else 0.0)
    coef = CALIBRATION[kind]
    peak = sum(c * feats.get(k, 0.0) for k, c in coef.items())
    # the prediction can never undercut what provably resides: the
    # arguments themselves (exact) are a hard floor
    peak = max(int(round(peak)), int(args))

    if chip is None:
        from repro.core.hardware import TPU_V5E
        chip = TPU_V5E
    hbm = int(chip.hbm_bytes) if not isinstance(chip, (int, float)) \
        else int(chip)

    return CapacityReport(
        kind=kind, recipe=recipe.name, mesh_sizes=sizes, devices=devices,
        argument_bytes=int(args), peak_bytes=int(peak),
        params_bytes=int(pb), cache_bytes=int(cache_b),
        batch_bytes=int(batch_b), hbm_bytes=hbm,
        fits=peak <= hbm, utilization=peak / hbm,
        features=feats, coefficients=dict(coef), notes=tuple(notes))


# ===========================================================================
# Dry-run artifact parity (the spmd-memory-drift + parity-test entry)
# ===========================================================================
def measured_peak_bytes(mem: Dict[str, int]) -> int:
    """The measured per-device peak a dry-run cell records: XLA's
    ``peak_bytes`` when the backend reports one (TPU), else the
    argument+output+temp−alias residency sum (CPU)."""
    return int(mem.get("peak_bytes") or
               (mem["argument_bytes"] + mem["output_bytes"]
                + mem["temp_bytes"] - mem["alias_bytes"]))


def capacity_from_artifact(art: Dict[str, Any], preset) -> CapacityReport:
    """Re-derive the cell's capacity prediction from its identity
    fields (arch/shape/mesh_axes) — baseline-variant cells only."""
    cfg = preset.arch(art["arch"])
    shape = preset.shape(art["shape"])
    return capacity(cfg, shape, mesh=art["mesh_axes"])


# ===========================================================================
# Serving preflight (launch/serve.py --preflight)
# ===========================================================================
def serve_preflight(cfg, *, n_slots: int, max_len: int,
                    page_size: Optional[int] = None,
                    page_budget: Optional[int] = None,
                    mesh=None, hbm_gb: Optional[float] = None,
                    param_dtype: str = "float32",
                    kv_dtype: Optional[str] = None,
                    dtype: str = "bfloat16") -> CapacityReport:
    """The serve launcher's capacity gate, evaluated before anything
    allocates. Paged configs default the pool to the fixed engine's
    HBM *bytes* at the activation ``dtype`` (the engine runtime's
    compute dtype), converted into pages at ``kv_dtype`` — the same
    derivation ``PagedServeEngine`` uses, so the preflight gates
    exactly the pool the engine will allocate
    (``n_slots * ceil(window/page_size) + 1`` when the dtypes agree)."""
    chip: Any = None
    if hbm_gb is not None:
        chip = int(hbm_gb * 2**30)
    if page_size:
        if page_budget is None:
            import jax.numpy as jnp

            from repro.models.model import _cache_window, page_count
            W = _cache_window(cfg, max_len)
            base = n_slots * page_count(W, page_size)
            kvd = kv_dtype or dtype
            if kvd != dtype:
                per_tok_base = cfg.head_dim * jnp.dtype(dtype).itemsize
                per_tok_kv = (cfg.head_dim * jnp.dtype(kvd).itemsize
                              + (2 if kvd == "int8" else 0))
                base = base * per_tok_base // per_tok_kv
            page_budget = base + 1
        return capacity(cfg, mesh=mesh, recipe="decode",
                        n_slots=n_slots, max_len=max_len,
                        page_budget=page_budget, page_size=page_size,
                        chip=chip, param_dtype=param_dtype,
                        kv_dtype=kv_dtype)
    return capacity(cfg, mesh=mesh, recipe="decode",
                    n_slots=n_slots, max_len=max_len,
                    chip=chip, param_dtype=param_dtype,
                    kv_dtype=kv_dtype)
