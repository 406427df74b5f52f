"""JAX-trace front-end: a *real* model apply-fn lowered into the IR.

This is the first path from the executable JAX/Pallas models under
``repro.models`` into the workload vocabulary the analytical models and
DSE consume. ``trace_workload`` builds the abstract parameter/input
trees for one (arch x shape) cell — the same machinery the dry-run
lowering uses — traces the step function with ``jax.make_jaxpr`` (no
compilation, shape-level only), and walks the jaxpr:

* every ``dot_general``/``conv_general_dilated`` is FLOP-counted from
  its avals (2*K per output element), with ``lax.scan`` bodies
  multiplied by their trip count (nested scans compose);
* **parameter provenance** is tracked through the jaxpr: the flattened
  params argument's vars are seeded as weight-derived and propagated
  through view/cast primitives and into scan/pjit/remat bodies. A dot
  with exactly one weight operand is a ``matmul`` (weight bytes
  attributed from the weight aval); a dot between two activations is
  ``attention`` (scores/PV, SSD chunk products);
* large gathers from weights become ``embed`` ops (table bytes, 0 FLOPs).

The result is a :class:`Workload` whose ``matmul`` ops are directly
comparable, per op, with the analytic LM front-end — `repro.workloads
diff` runs that comparison as a standing validation of the analytical
profile (and of this tracer).

``while`` bodies cannot be statically trip-counted; they are counted
once and flagged in ``meta['while_loops']`` so a consumer knows the
trace is a lower bound there (the production forward pass uses scans
throughout, so this path is exercised only by exotic step functions).

Because the layer stack is a ``lax.scan``, traced ops aggregate across
layers and carry ``layer_idx=-1`` — a traced workload has no per-layer
attribution, so the TPU DSE collapses its front/tail split dimensions
when searching over one (see ``tpu_design_space(per_layer=...)``).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple, Union

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.workload.ir import Op, Workload, WorkloadError
from repro.core.workload.frontends.lm import model_flops

# Primitives through which "is derived from a parameter leaf" propagates.
_VIEW_PRIMS = {
    "reshape", "transpose", "convert_element_type", "broadcast_in_dim",
    "squeeze", "slice", "dynamic_slice", "copy", "stop_gradient",
    "bitcast_convert_type", "rev", "expand_dims", "sharding_constraint",
}

# Gathers from a weight table at least this large count as embedding ops.
_EMBED_MIN_BYTES = 1 << 20


def _aval_bytes(var) -> float:
    aval = var.aval
    return float(aval.size * aval.dtype.itemsize)


def _is_lit(v) -> bool:
    return not hasattr(v, "count")      # jax.extend.core.Literal has no .count


class _TraceState:
    """Accumulates raw op records + trace statistics during the walk."""

    def __init__(self) -> None:
        self.records: List[Dict[str, Any]] = []
        self.stats: Dict[str, float] = {
            "eqns": 0, "while_loops": 0, "scans": 0, "max_depth": 0,
        }

    def add(self, kind: str, K: int, N: int, flops: float,
            weight_bytes: float, act_in: float, act_out: float,
            weight_dtype: Optional[str] = None,
            act_dtype: Optional[str] = None) -> None:
        self.records.append(dict(kind=kind, K=int(K), N=int(N),
                                 flops=flops, weight_bytes=weight_bytes,
                                 act_in=act_in, act_out=act_out, count=1,
                                 weight_dtype=weight_dtype,
                                 act_dtype=act_dtype))


def _dot_record(eqn, param: set, mult: float, st: _TraceState) -> None:
    lhs, rhs = eqn.invars[0], eqn.invars[1]
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    out = eqn.outvars[0]
    K = 1
    for i in lc:
        K *= lhs.aval.shape[i]
    flops = 2.0 * K * out.aval.size * mult
    lhs_w = (not _is_lit(lhs)) and lhs in param
    rhs_w = (not _is_lit(rhs)) and rhs in param
    if lhs_w != rhs_w:                      # weight x activation
        wvar, avar = (lhs, rhs) if lhs_w else (rhs, lhs)
        contract = lc if lhs_w else rc
        batch = lb if lhs_w else rb
        N = 1
        for i, dim in enumerate(wvar.aval.shape):
            if i not in contract and i not in batch:
                N *= dim
        st.add("matmul", K, N, flops,
               weight_bytes=_aval_bytes(wvar) * mult,
               act_in=_aval_bytes(avar) * mult,
               act_out=_aval_bytes(out) * mult,
               weight_dtype=str(wvar.aval.dtype),
               act_dtype=str(out.aval.dtype))
    else:                                   # activation x activation
        N = out.aval.shape[-1] if out.aval.shape else 1
        st.add("attention", K, N, flops,
               weight_bytes=0.0,
               act_in=(_aval_bytes(lhs) + _aval_bytes(rhs)) * mult,
               act_out=_aval_bytes(out) * mult,
               act_dtype=str(out.aval.dtype))


def _conv_record(eqn, param: set, mult: float, st: _TraceState) -> None:
    rhs = eqn.invars[1]
    out = eqn.outvars[0]
    dn = eqn.params["dimension_numbers"]
    cout = rhs.aval.shape[dn.rhs_spec[0]]
    k_per_out = rhs.aval.size / max(cout, 1)     # r*s*cin/feature_groups
    flops = 2.0 * out.aval.size * k_per_out * mult
    rhs_w = (not _is_lit(rhs)) and rhs in param
    st.add("conv", int(k_per_out), int(cout), flops,
           weight_bytes=_aval_bytes(rhs) * mult if rhs_w else 0.0,
           act_in=_aval_bytes(eqn.invars[0]) * mult,
           act_out=_aval_bytes(out) * mult,
           weight_dtype=str(rhs.aval.dtype) if rhs_w else None,
           act_dtype=str(out.aval.dtype))


def _map_params(inner_invars, outer_invars, param: set) -> set:
    """Positionally project outer param-ness onto a sub-jaxpr's invars."""
    inner = set()
    for iv, ov in zip(inner_invars, outer_invars):
        if (not _is_lit(ov)) and ov in param:
            inner.add(iv)
    return inner


def _out_flags(jaxpr, param: set) -> List[bool]:
    """Param-ness of a jaxpr's outvars (literals are never params)."""
    return [(not _is_lit(v)) and v in param for v in jaxpr.outvars]


def _mark_outs(eqn, flags: List[bool], param: set) -> None:
    """Project a sub-jaxpr's outvar param-ness onto the eqn outvars, so
    weights surviving a pjit/remat/scan boundary stay weights."""
    for ov, flag in zip(eqn.outvars, flags):
        if flag:
            param.add(ov)


def _walk(jaxpr, param: set, mult: float, st: _TraceState,
          depth: int = 0) -> List[bool]:
    st.stats["max_depth"] = max(st.stats["max_depth"], depth)
    for eqn in jaxpr.eqns:
        st.stats["eqns"] += 1
        p = eqn.primitive.name
        if p == "dot_general":
            _dot_record(eqn, param, mult, st)
        elif p == "conv_general_dilated":
            _conv_record(eqn, param, mult, st)
        elif p == "gather":
            src = eqn.invars[0]
            if (not _is_lit(src)) and src in param \
                    and _aval_bytes(src) >= _EMBED_MIN_BYTES:
                st.add("embed", 0, int(src.aval.shape[-1]), 0.0,
                       weight_bytes=_aval_bytes(src) * mult,
                       act_in=_aval_bytes(eqn.invars[1]) * mult,
                       act_out=_aval_bytes(eqn.outvars[0]) * mult,
                       weight_dtype=str(src.aval.dtype),
                       act_dtype=str(eqn.outvars[0].aval.dtype))
        elif p == "scan":
            st.stats["scans"] += 1
            closed = eqn.params["jaxpr"]
            length = eqn.params["length"]
            nc = eqn.params["num_consts"]
            ncar = eqn.params["num_carry"]
            body = closed.jaxpr
            inner_param = set()
            for i, iv in enumerate(body.invars):
                if nc <= i < nc + ncar:
                    continue                 # carries are activations
                ov = eqn.invars[i]
                if (not _is_lit(ov)) and ov in param:
                    inner_param.add(iv)
            flags = _walk(body, inner_param, mult * length, st, depth + 1)
            # body outvars = carries + ys, same order as eqn.outvars
            _mark_outs(eqn, flags, param)
        elif p == "while":
            st.stats["while_loops"] += 1
            cn = eqn.params["cond_nconsts"]
            body = eqn.params["body_jaxpr"].jaxpr
            outer = eqn.invars[cn:]          # body consts + carry
            flags = _walk(body, _map_params(body.invars, outer, param),
                          mult, st, depth + 1)  # trip count unknown: 1x
            _mark_outs(eqn, flags, param)
        elif p == "cond":
            # count the largest branch (upper bound among branches);
            # outvar param-ness is OR'd across branches
            best: Optional[_TraceState] = None
            out_flags = [False] * len(eqn.outvars)
            for br in eqn.params["branches"]:
                sub = _TraceState()
                flags = _walk(
                    br.jaxpr,
                    _map_params(br.jaxpr.invars, eqn.invars[1:], param),
                    mult, sub, depth + 1)
                out_flags = [a or b for a, b in zip(out_flags, flags)]
                if best is None or (sum(r["flops"] for r in sub.records)
                                    > sum(r["flops"]
                                          for r in best.records)):
                    best = sub
            if best is not None:
                st.records.extend(best.records)
                for k, v in best.stats.items():
                    if k == "max_depth":
                        st.stats[k] = max(st.stats[k], v)
                    else:
                        st.stats[k] += v
            _mark_outs(eqn, out_flags, param)
        elif p in ("pjit", "closed_call", "core_call", "remat", "checkpoint",
                   "custom_jvp_call", "custom_vjp_call",
                   "custom_vjp_call_jaxpr", "named_call"):
            inner = (eqn.params.get("jaxpr")
                     or eqn.params.get("call_jaxpr")
                     or eqn.params.get("fun_jaxpr"))
            if inner is None:
                continue
            body = inner.jaxpr if hasattr(inner, "jaxpr") else inner
            flags = _walk(body, _map_params(body.invars, eqn.invars, param),
                          mult, st, depth + 1)
            _mark_outs(eqn, flags, param)
        elif p in _VIEW_PRIMS:
            if any((not _is_lit(v)) and v in param for v in eqn.invars):
                for ov in eqn.outvars:
                    param.add(ov)
    return _out_flags(jaxpr, param)


# ---------------------------------------------------------------------------
# Record -> Op aggregation
# ---------------------------------------------------------------------------
def _axis_hint(cfg: ModelConfig, K: int, N: int
               ) -> Tuple[Optional[str], int]:
    """Best-effort sharding-axis hint for a traced weight of shape
    (K, N) — lets the TPU model shard a *traced* workload sensibly."""
    d, hd = cfg.d_model, cfg.head_dim
    heads_dims = {cfg.n_heads * hd, cfg.n_kv_heads * hd,
                  (cfg.n_heads + 2 * cfg.n_kv_heads) * hd}
    ssm_dims = set()
    if cfg.ssm is not None:
        di = cfg.ssm.d_inner(d)
        ssm_dims = {di, 2 * di + 2 * cfg.ssm.n_groups * cfg.ssm.d_state
                    + cfg.ssm.n_heads(d)}
    for wd in (N, K):
        if wd == cfg.vocab_size:
            return "vocab", wd
        if cfg.d_ff and wd == cfg.d_ff:
            return "ffn", wd
        if wd in ssm_dims:
            return "ssm_inner", wd
        if wd in heads_dims and wd != d:
            return "heads", cfg.n_heads
    return None, N


def _aggregate(records: List[Dict[str, Any]], cfg: ModelConfig
               ) -> Tuple[Op, ...]:
    """Merge raw records by (kind, K, N) into stable, ordered Op rows."""
    merged: Dict[Tuple[str, int, int], Dict[str, Any]] = {}
    order: List[Tuple[str, int, int]] = []
    for r in records:
        key = (r["kind"], r["K"], r["N"])
        if key not in merged:
            merged[key] = dict(r)
            order.append(key)
        else:
            m = merged[key]
            for f in ("flops", "weight_bytes", "act_in", "act_out"):
                m[f] += r[f]
            m["count"] += 1
    ops = []
    for i, key in enumerate(order):
        r = merged[key]
        kind, K, N = key
        axis, width = (None, N)
        if kind in ("matmul", "embed"):
            axis, width = _axis_hint(cfg, K, N)
        name = f"{kind}.{K}x{N}"
        if r["count"] > 1:
            name += f"(x{r['count']})"
        ops.append(Op(name=name, kind=kind, flops=r["flops"],
                      weight_bytes=r["weight_bytes"],
                      act_in_bytes=r["act_in"], act_out_bytes=r["act_out"],
                      layer_idx=-1, weight_axis=axis, width=width,
                      weight_dtype=r.get("weight_dtype"),
                      act_dtype=r.get("act_dtype")))
    return tuple(ops)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------
def trace_workload(cfg: Union[ModelConfig, str],
                   shape: Union[ShapeConfig, str],
                   kv_len: Optional[int] = None,
                   rt=None) -> Workload:
    """Trace the real apply-fn of one (arch x shape) cell into the IR.

    train/prefill trace :func:`repro.models.forward` (the fwd compute
    core — matching what the analytic front-end profiles); decode traces
    :func:`repro.models.decode_step` against an abstract KV/state cache
    of ``kv_len`` (default ``shape.kv_len`` or ``seq_len``) slots.
    """
    import jax
    import jax.numpy as jnp
    from repro.models import abstract_cache, abstract_params, decode_step, \
        forward
    from repro.models.model import ModelRuntime

    if isinstance(cfg, str):
        from repro.configs import get_arch
        cfg = get_arch(cfg)
    if isinstance(shape, str):
        from repro.configs import get_shape
        shape = get_shape(shape)
    kv = kv_len if kv_len is not None else \
        (getattr(shape, "kv_len", None) or shape.seq_len)
    # remat='none': checkpointing must not change what we count;
    # attn_chunk >= seq collapses the KV-chunk scan so executed == one
    # full pass (the production chunked loop re-executes nothing).
    rt = rt or ModelRuntime(dtype=cfg.dtype, remat="none",
                            attn_chunk=max(shape.seq_len, 16))

    B, S = shape.global_batch, shape.seq_len
    params = abstract_params(cfg, cfg.dtype)
    if shape.kind == "decode":
        cache = abstract_cache(cfg, B, kv)
        tokens = jax.ShapeDtypeStruct((B,), jnp.int32)

        def fn(p, c, t):
            return decode_step(p, cfg, c, t, rt)

        args = (params, cache, tokens)
        traced_pass = "decode_step"
    else:
        if cfg.frontend == "token":
            batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        else:
            batch = {"embeds": jax.ShapeDtypeStruct(
                (B, S, cfg.d_model), jnp.dtype(cfg.dtype))}

        def fn(p, b):
            return forward(p, cfg, b, rt)

        args = (params, batch)
        traced_pass = "forward"

    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as e:                   # noqa: BLE001
        raise WorkloadError(
            f"jax trace of {cfg.name}/{shape.name} failed: "
            f"{type(e).__name__}: {e}") from e

    n_param_leaves = len(jax.tree.leaves(params))
    st = _TraceState()
    seed = set(closed.jaxpr.invars[:n_param_leaves])
    _walk(closed.jaxpr, seed, 1.0, st)

    ops = _aggregate(st.records, cfg)
    if not ops:
        raise WorkloadError(
            f"jax trace of {cfg.name}/{shape.name} produced no "
            f"countable ops — the jaxpr walk found no dots/convs")
    param_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(params))
    return Workload(
        name=f"trace:{cfg.name}/{shape.name}",
        frontend="jax_trace",
        ops=ops,
        kind=shape.kind,
        meta={
            "arch": cfg.name, "shape": shape.name, "pass": traced_pass,
            "seq_len": S, "global_batch": B, "kv_len": kv,
            "param_bytes": int(param_bytes),
            "trace_eqns": int(st.stats["eqns"]),
            "trace_scans": int(st.stats["scans"]),
            "while_loops": int(st.stats["while_loops"]),
            "raw_records": len(st.records),
        },
        model_flops_hint=model_flops(cfg, shape),
    )


# ---------------------------------------------------------------------------
# Traced-vs-analytic comparison (the standing validation `diff` runs)
# ---------------------------------------------------------------------------
def diff_workloads(analytic: Workload, traced: Workload) -> Dict[str, Any]:
    """Cross-check a traced workload against its analytic twin.

    The load-bearing number is ``matmul_ratio`` — traced / analytic
    weight-fed dot FLOPs (matmul+router+conv vs matmul), which must
    agree closely because both sides count the same GEMMs. Attention
    and scan FLOPs are reported but expected to diverge where the
    executable computes masked/padded work the analytic profile skips
    (causal halving, MoE capacity padding) — that gap is a *finding*,
    not an error.
    """
    a_kinds = analytic.flops_by_kind()
    t_kinds = traced.flops_by_kind()
    a_mm = sum(a_kinds.get(k, 0.0) for k in ("matmul", "router", "conv"))
    t_mm = sum(t_kinds.get(k, 0.0) for k in ("matmul", "conv"))
    a_act = sum(a_kinds.get(k, 0.0) for k in ("attention", "scan"))
    t_act = t_kinds.get("attention", 0.0)
    a_wb = analytic.total_weight_bytes()
    t_wb = traced.total_weight_bytes()

    def ratio(t: float, a: float) -> float:
        return t / a if a > 0 else (1.0 if t == 0 else float("inf"))

    rows = []
    for o in traced.ops:
        if o.kind not in ("matmul", "conv"):
            continue
        rows.append({"op": o.name, "kind": o.kind,
                     "gflop": o.flops / 1e9,
                     "weight_mb": o.weight_bytes / 1e6,
                     "axis": o.weight_axis or "-"})
    return {
        "analytic": analytic.name,
        "traced": traced.name,
        "matmul_flops_analytic": a_mm,
        "matmul_flops_traced": t_mm,
        "matmul_ratio": ratio(t_mm, a_mm),
        "activation_flops_analytic": a_act,
        "activation_flops_traced": t_act,
        "activation_ratio": ratio(t_act, a_act),
        "weight_bytes_analytic": a_wb,
        "weight_bytes_traced": t_wb,
        "weight_bytes_ratio": ratio(t_wb, a_wb),
        "while_loops": traced.meta.get("while_loops", 0),
        "traced_matmul_ops": rows,
    }
