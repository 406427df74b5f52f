"""Logical-axis sharding: recipes map *logical* tensor axes (``embed``,
``heads``, ``ffn``, ...) to physical mesh axes (``data``, ``model``,
``pod``). Model code annotates tensors with logical names only
(:func:`constrain`); which physical sharding that produces is decided by
the active :class:`Recipe` — IS (weights streamed / FSDP-style) vs WS
(weights resident / tensor-parallel), the TPU-domain analogue of the
paper's per-layer dataflow choice (Algorithm 3 STEP2).

Every spec passes through :func:`sanitize_spec` so indivisible or
double-used mesh axes degrade to replication instead of erroring — the
same "resource budget constraints gate the design point" philosophy as
the FPGA models.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

AxisEntry = Optional[Union[str, Tuple[str, ...]]]


# ---------------------------------------------------------------------------
# Recipes
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Recipe:
    """A named mapping logical-axis -> mesh axes (None = replicate)."""

    name: str
    rules: Dict[str, AxisEntry] = field(default_factory=dict)

    def spec_for(self, logical_axes: Sequence[Optional[str]]) -> P:
        return P(*(self.rules.get(a) if a is not None else None
                   for a in logical_axes))

    def with_rules(self, **updates: AxisEntry) -> "Recipe":
        rules = dict(self.rules)
        rules.update(updates)
        return Recipe(self.name, rules)

    def replace_name(self, name: str) -> "Recipe":
        return Recipe(name, dict(self.rules))


_COMMON: Dict[str, AxisEntry] = {
    # activation-only axes
    "batch": ("pod", "data"),
    "tokens": ("pod", "data"),
    "seq": None,
    "q_seq": None,
    # KV-cache sequence rows stay replicated across the mesh: decode
    # gathers them per step, and splitting them would turn every step
    # into a collective. Declared (rather than absent) so the contract
    # checker can tell replicate-by-design from nobody-decided —
    # Recipe.spec_for silently replicates unknown names
    # (contract-axis-unresolvable).
    "kv_seq": None,
    "head_dim": None,
    "capacity": None,
    "layers": None,
}

# IS: weights sharded over `data` too (streamed / ZeRO-3), compute TP'd
IS_RECIPE = Recipe("IS", {
    **_COMMON,
    "embed": ("data",),
    "heads": ("model",),
    "heads_full": ("model",),
    "kv_heads": ("model",),
    "ffn": ("model",),
    "expert_ffn": ("model",),
    "experts": ("model",),
    "vocab": ("model",),
    "ssm_inner": ("model",),
    "ssm_heads": ("model",),
})

# WS: weights resident, sharded over `model` only (Megatron-style TP)
WS_RECIPE = Recipe("WS", {
    **_COMMON,
    "embed": None,
    "heads": ("model",),
    "heads_full": ("model",),
    "kv_heads": ("model",),
    "ffn": ("model",),
    "expert_ffn": ("model",),
    "experts": ("model",),
    "vocab": ("model",),
    "ssm_inner": ("model",),
    "ssm_heads": ("model",),
})

# *_SEQ: head counts indivisible by the model axis — attention shards
# query rows (sequence parallel) instead of heads.
IS_SEQ_RECIPE = IS_RECIPE.with_rules(
    heads=None, heads_full=None, kv_heads=None,
    q_seq=("model",)).replace_name("IS_seq")
WS_SEQ_RECIPE = WS_RECIPE.with_rules(
    heads=None, heads_full=None, kv_heads=None,
    q_seq=("model",)).replace_name("WS_seq")

# decode: one token per sequence; KV cache sharded over heads, weights
# resident (WS) — batch is the only streaming dimension.
DECODE_RECIPE = WS_RECIPE.replace_name("decode")

RECIPES: Dict[str, Recipe] = {
    "IS": IS_RECIPE,
    "WS": WS_RECIPE,
    "IS_seq": IS_SEQ_RECIPE,
    "WS_seq": WS_SEQ_RECIPE,
    "decode": DECODE_RECIPE,
}


# ---------------------------------------------------------------------------
# Spec sanitization
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class SpecDrop:
    """One mesh axis :func:`sanitize_spec` removed from a requested
    spec — the information the silent degrade-to-replication used to
    lose. ``reason`` is ``missing-axis`` (mesh doesn't have it; routine
    for ``pod`` on 2-axis meshes), ``axis-reused`` (already sharding
    another dim) or ``indivisible`` (extent doesn't divide the dim —
    the one that silently replicates real bytes)."""

    path: Optional[str]             # leaf path when the caller knows it
    axis: str                       # the dropped mesh axis
    spec: Tuple                     # the entries requested for the dim
    dim: int                        # dim size the axis failed against
    shape: Tuple[int, ...]
    mesh_sizes: Tuple[Tuple[str, int], ...]
    reason: str                     # missing-axis | axis-reused | indivisible


#: Bounded record of every drop since the last reset (the total keeps
#: counting past the cap). ``sharding_prop`` reads it; tests assert it.
_SPEC_DROPS: list = []
_SPEC_DROP_CAP = 4096
_SPEC_DROP_TOTAL = 0


def reset_spec_drops() -> None:
    global _SPEC_DROP_TOTAL
    _SPEC_DROPS.clear()
    _SPEC_DROP_TOTAL = 0


def spec_drops() -> Tuple[SpecDrop, ...]:
    return tuple(_SPEC_DROPS)


def spec_drop_count(reason: Optional[str] = None) -> int:
    """Drops recorded since the last reset (cap-proof total when
    ``reason`` is None)."""
    if reason is None:
        return _SPEC_DROP_TOTAL
    return sum(1 for d in _SPEC_DROPS if d.reason == reason)


def _record_drop(drop: SpecDrop) -> None:
    global _SPEC_DROP_TOTAL
    _SPEC_DROP_TOTAL += 1
    if len(_SPEC_DROPS) < _SPEC_DROP_CAP:
        _SPEC_DROPS.append(drop)


def _mesh_sizes(mesh) -> Dict[str, int]:
    names = getattr(mesh, "axis_names", None)
    sizes = getattr(mesh, "axis_sizes", None)
    if names is not None and sizes is not None:
        return dict(zip(names, sizes))
    shape = getattr(mesh, "shape", None)
    if shape:
        return dict(shape)
    return {}


def sanitize_spec(spec: P, shape: Tuple[int, ...], mesh,
                  path: Optional[str] = None) -> P:
    """Make ``spec`` legal for a tensor of ``shape`` on ``mesh``:

    * drop mesh axes the mesh doesn't have,
    * never use one mesh axis on two tensor dims,
    * only keep a sharding whose extent divides the dim size.

    Degrades toward replication (never errors) — infeasible shardings
    are "out of budget", mirroring the analytical models' feasibility
    gates. Every drop is recorded (:func:`spec_drops`, with ``path``
    when the caller names the leaf) so the degrade is silent in control
    flow but not in accounting — ``analysis.sharding_prop`` and the
    tests read the record.
    """
    sizes = _mesh_sizes(mesh)
    msizes = tuple(sizes.items())
    used: set = set()
    entries = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
    out = []
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        parts = (entry,) if isinstance(entry, str) else tuple(entry)
        kept = []
        ext = 1
        for ax in parts:
            if ax not in sizes:
                _record_drop(SpecDrop(path, ax, parts, dim, tuple(shape),
                                      msizes, "missing-axis"))
                continue
            if ax in used:
                _record_drop(SpecDrop(path, ax, parts, dim, tuple(shape),
                                      msizes, "axis-reused"))
                continue
            if dim % (ext * sizes[ax]) != 0:
                _record_drop(SpecDrop(path, ax, parts, dim, tuple(shape),
                                      msizes, "indivisible"))
                continue
            kept.append(ax)
            used.add(ax)
            ext *= sizes[ax]
        out.append(None if not kept
                   else kept[0] if len(kept) == 1 else tuple(kept))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


# ---------------------------------------------------------------------------
# Active-recipe context + constrain
# ---------------------------------------------------------------------------
class _Active(threading.local):
    def __init__(self):
        self.recipe: Optional[Recipe] = None


_ACTIVE = _Active()


@contextmanager
def axis_rules(recipe: Optional[Recipe]):
    """Install ``recipe`` as the ambient logical->physical mapping for
    :func:`constrain`. ``axis_rules(None)`` is a no-op context (the
    unsharded CPU smoke-test path)."""
    prev = _ACTIVE.recipe
    _ACTIVE.recipe = recipe
    try:
        yield recipe
    finally:
        _ACTIVE.recipe = prev


def _current_mesh():
    """The ambient mesh ``jax.set_mesh`` installed (what
    ``launch.mesh.use_mesh`` enters), or None outside one. Abstract:
    readable inside ``jit`` traces, where ``constrain`` runs."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def constrain(x: jax.Array, logical_axes: Sequence[Optional[str]]):
    """``with_sharding_constraint`` by logical axis names; identity when
    no recipe/mesh is active, so the same model code runs everywhere."""
    recipe = _ACTIVE.recipe
    if recipe is None:
        return x
    mesh = _current_mesh()
    if mesh is None:
        return x
    spec = sanitize_spec(recipe.spec_for(logical_axes), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Parameter-tree shardings
# ---------------------------------------------------------------------------
def _is_axes_leaf(x) -> bool:
    return x is None or isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x)


def param_sharding_tree(axes_tree, recipe: Recipe, mesh, abstract) -> Any:
    """NamedSharding tree for a parameter tree.

    ``axes_tree`` mirrors ``abstract`` with per-leaf logical-axis tuples
    (``repro.models.model.axes_tree``); each leaf becomes the recipe's
    sanitized spec for that parameter's shape. Despite the name this is
    generic over any (axes, arrays) tree pair — the serving engine
    reuses it with ``models.model.CACHE_AXES`` to shard the decode
    cache (see :func:`shard_tree`).
    """
    path_leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    ax_leaves = jax.tree.flatten(axes_tree, is_leaf=_is_axes_leaf)[0]
    assert len(path_leaves) == len(ax_leaves), \
        f"axes/param tree mismatch: {len(ax_leaves)} vs {len(path_leaves)}"
    shardings = []
    for (path, leaf), axes in zip(path_leaves, ax_leaves):
        axes = axes or (None,) * len(leaf.shape)
        spec = sanitize_spec(recipe.spec_for(axes), leaf.shape, mesh,
                             path=jax.tree_util.keystr(path))
        shardings.append(NamedSharding(mesh, spec))
    return jax.tree.unflatten(treedef, shardings)


def shard_tree(tree, axes_tree, recipe: Recipe, mesh) -> Any:
    """device_put every leaf of ``tree`` with its recipe-derived
    NamedSharding.

    The one-call placement path the sharded ServeEngine uses for both
    the parameter tree (``axes_tree = models.model.axes_tree(cfg)``)
    and the decode cache (``axes_tree = {k: CACHE_AXES[k] ...}``):
    logical names in, mesh-resident arrays out, infeasible shardings
    degraded to replication by :func:`sanitize_spec`.
    """
    shardings = param_sharding_tree(axes_tree, recipe, mesh, tree)
    return jax.tree.map(jax.device_put, tree, shardings)
