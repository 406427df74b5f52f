"""Static buffer liveness over the serving hot paths + the capacity
preflight's drift guards.

:func:`jaxpr_peak` walks a jaxpr's equations in order, tracking the
byte-size of every live value (a value dies after its last use;
subjaxprs — scan/while/remat bodies — contribute their own peak on top
of the values live across the call). It is a *global*, pre-SPMD,
pre-fusion estimate: good for ranking hotspots and proving a donated
cache actually stays live through the step, deliberately **not** the
number the ``--preflight``/parity gate uses — that is the calibrated
closed-form model in :mod:`repro.analysis.capacity` (fusion and
per-tensor sharding move the walk 0.1x–2.4x around the measured peak;
the closed form sits within 10%).

The pass therefore checks *contracts*, not bytes-vs-HBM:

* the capacity mirror still evaluates on every preset arch (a
  params/axes tree drift raises inside the mirror →
  ``capacity-spec-drift``);
* the mirror's baked constants still match the live defaults they were
  calibrated against (the dry-run driver's ``attn_chunk``);
* the decode walk keeps the full cache live across the step (a cache
  leaf dropping out of liveness means the step stopped threading it —
  the recompile/correctness bug the serve engine's donation relies on
  never hitting);
* the smoke serving config still fits the chip
  (``capacity-hbm-overflow`` — the same rule ``--preflight`` names,
  exercised end-to-end by the serve tests).
"""
from __future__ import annotations

import math
from typing import Any, Iterable, List, Optional

from repro.analysis.findings import Finding, Location
from repro.analysis.registry import AnalysisContext, register_pass


# ===========================================================================
# The walk
# ===========================================================================
def aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0
    itemsize = dtype.itemsize
    return math.prod(shape) * itemsize if shape else itemsize


def _as_jaxpr(v) -> Optional[Any]:
    # ClosedJaxpr carries BOTH .jaxpr and (delegated) .eqns — unwrap it
    # first; a raw Jaxpr (remat2's "jaxpr" param) only has .eqns
    if hasattr(v, "jaxpr"):
        return v.jaxpr
    if hasattr(v, "eqns"):
        return v
    return None


def _subjaxprs(eqn) -> Iterable[Any]:
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            j = _as_jaxpr(x)
            if j is not None:
                yield j


def jaxpr_peak(jaxpr) -> int:
    """Peak live bytes of one jaxpr, equations walked in program
    order; sub-computations (scan/cond/remat bodies) recurse."""
    from jax.extend import core as jcore

    last_use = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, jcore.Var):
                last_use[v] = i
    for v in jaxpr.outvars:
        if isinstance(v, jcore.Var):
            last_use[v] = len(jaxpr.eqns)

    live = sum(aval_bytes(v.aval)
               for v in tuple(jaxpr.invars) + tuple(jaxpr.constvars)
               if isinstance(v, jcore.Var) and v in last_use)
    peak = live
    for i, eqn in enumerate(jaxpr.eqns):
        out_b = sum(aval_bytes(v.aval) for v in eqn.outvars
                    if v in last_use)
        inner = 0
        for sub in _subjaxprs(eqn):
            inner = max(inner, jaxpr_peak(sub))
        peak = max(peak, live + out_b + inner)
        live += out_b
        for v in {x for x in eqn.invars if isinstance(x, jcore.Var)}:
            if last_use.get(v) == i:
                live -= aval_bytes(v.aval)
    return peak


# ===========================================================================
# Per-arch contract checks
# ===========================================================================
def _dryrun_attn_chunk_default() -> int:
    """The ``attn_chunk`` the dry-run driver lowers cells at — the
    value the capacity calibration is conditioned on."""
    import inspect

    from repro.launch.lowering import lower_cell

    return inspect.signature(lower_cell).parameters["attn_chunk"].default


def lint_arch(arch: str, *, max_len: int, page_size: int,
              batch: int = 2) -> List[Finding]:
    import jax
    import jax.numpy as jnp

    from repro.analysis.capacity import capacity, tree_global_bytes
    from repro.configs import get_arch, smoke_config
    from repro.models.model import (ModelRuntime, abstract_cache,
                                    abstract_params, decode_step, prefill)
    from repro.serve.scheduler import Scheduler

    cfg = smoke_config(get_arch(arch))
    findings: List[Finding] = []

    # -- the capacity mirror must evaluate (tree drift raises inside) -------
    try:
        report = capacity(cfg, n_slots=batch, max_len=max_len,
                          recipe="decode", param_dtype="bfloat16")
    except Exception as e:
        findings.append(Finding(
            "capacity-spec-drift", "error",
            Location(symbol=f"capacity/{arch}"),
            f"the closed-form capacity model no longer evaluates on "
            f"this arch: {type(e).__name__}: {e} — its param/cache "
            f"mirror drifted from the live trees",
            "realign analysis.capacity with models.model's "
            "param_defs/cache_spec"))
        return findings
    if not report.fits:
        findings.append(Finding(
            "capacity-hbm-overflow", "error",
            Location(symbol=f"capacity/{arch}"),
            f"the smoke serving config ({batch} slots x {max_len} "
            f"tokens) predicts {report.peak_bytes / 2**30:.2f} GiB "
            f"peak, over the {report.hbm_bytes / 2**30:.0f} GiB chip",
            "shrink n_slots/max_len or shard over more devices"))

    if cfg.is_encoder_only:
        return findings

    # -- decode walk: the donated cache must stay live across the step ------
    rt = ModelRuntime(dtype="bfloat16", remat="none", attn_chunk=16,
                      moe_dropless=True)
    params = abstract_params(cfg, dtype=rt.dtype)
    cache = abstract_cache(cfg, batch, max_len, rt.dtype)
    tokens = jax.ShapeDtypeStruct((batch,), jnp.int32)
    try:
        closed = jax.make_jaxpr(
            lambda p, c, t: decode_step(p, cfg, c, t, rt))(
            params, cache, tokens)
        peak = jaxpr_peak(closed.jaxpr)
    except Exception as e:
        findings.append(Finding(
            "capacity-spec-drift", "error",
            Location(symbol=f"liveness/decode/{arch}"),
            f"liveness walk failed over decode_step: "
            f"{type(e).__name__}: {e}"))
        return findings
    floor = tree_global_bytes(cache) + tree_global_bytes(params)
    if peak < floor:
        findings.append(Finding(
            "capacity-spec-drift", "error",
            Location(symbol=f"liveness/decode/{arch}"),
            f"decode-step peak live bytes ({peak}) fall below the "
            f"params+cache floor ({floor}) — the step no longer "
            f"threads the full cache through, so the in-place "
            f"donation contract is broken",
            "return every cache leaf from decode_step"))

    # -- prefill buckets: every scheduler bucket must walk ------------------
    sched = Scheduler(cfg, max_len)
    for L in sched.prefill_lengths:
        batch_in = {"tokens": jax.ShapeDtypeStruct((batch, L), jnp.int32)}
        lengths = (jax.ShapeDtypeStruct((batch,), jnp.int32)
                   if sched.pad_safe else None)
        try:
            closed = jax.make_jaxpr(
                lambda p, b, lens: prefill(p, cfg, b, max_len, rt,
                                           lengths=lens))(
                params, batch_in, lengths)
            jaxpr_peak(closed.jaxpr)
        except Exception as e:
            findings.append(Finding(
                "capacity-spec-drift", "error",
                Location(symbol=f"liveness/prefill/{arch}@L{L}"),
                f"liveness walk failed over the L={L} prefill bucket: "
                f"{type(e).__name__}: {e}"))
    return findings


@register_pass(
    "liveness",
    rules=("capacity-hbm-overflow", "capacity-spec-drift"),
    description="jaxpr buffer-liveness walk over decode/prefill + "
                "capacity-model drift and HBM-overflow guards")
def run_pass(ctx: AnalysisContext) -> List[Finding]:
    from repro.analysis.capacity import ATTN_CHUNK

    findings: List[Finding] = []
    live_chunk = _dryrun_attn_chunk_default()
    if live_chunk != ATTN_CHUNK:
        findings.append(Finding(
            "capacity-spec-drift", "error",
            Location(symbol="capacity/ATTN_CHUNK"),
            f"capacity.ATTN_CHUNK={ATTN_CHUNK} but the dry-run driver "
            f"now lowers at attn_chunk={live_chunk} — the calibrated "
            f"scores feature is conditioned on the old chunk size",
            "recalibrate capacity.CALIBRATION at the new chunk"))
    for arch in ctx.preset.jaxpr_archs:
        findings.extend(lint_arch(arch, max_len=ctx.preset.max_len,
                                  page_size=ctx.preset.page_size))
    return findings
