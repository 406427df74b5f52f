"""Pallas kernel validator: static checks over captured pallas_calls.

For every registered non-xla implementation in the kernel dispatch
table, abstract-trace it (``jax.eval_shape`` — nothing executes) at the
tune-preset shapes while a spy on ``pl.pallas_call`` records each
launch's grid, BlockSpecs, out shapes and scratch buffers. The captured
launch geometry is then checked *numerically*, without running the
kernel:

* **coverage** — evaluating the output index maps over every grid cell
  must reach every output block, else part of the output is whatever
  was in HBM (``kernel-grid-coverage``);
* **write race** — two grid cells mapping to one output block is only
  legal when the kernel *declares* accumulation: either a VMEM scratch
  carry or a read-modify-write of the output ref (detected in the
  kernel body's AST). TPU grids are sequential so this is a
  revisit-without-carry bug, not a data race in the CUDA sense — the
  second visit silently overwrites the first (``kernel-write-race``);
* **VMEM budget** — the double-buffered per-block footprint
  (2 × (in blocks + out blocks) + VMEM scratch) must fit the per-core
  VMEM budget, or the compiler stalls/spills where the tuner can't see
  it (``kernel-vmem-budget``). An operand left in HBM (``pl.ANY``,
  which the kernel copies by its own DMAs into VMEM scratch), SMEM
  scratch and DMA semaphores take none of it;
* **differentiability** — the impl must either be a ``jax.custom_vjp``
  or have an xla reference to borrow a backward pass from (the
  ``dispatch._ref_backward`` contract), and the borrowed VJP must
  actually trace (``kernel-missing-vjp``);
* **parity** — output shapes/dtypes must match the xla reference
  (``kernel-dtype-parity``).

Grids above ``_MAX_GRID_CELLS`` cells skip the vectorized coverage/race
evaluation (the tune-grid smoke shapes never get close).
"""
from __future__ import annotations

import ast
import contextlib
import functools
import inspect
import textwrap
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.findings import Finding, Location
from repro.analysis.registry import AnalysisContext, register_pass

_MAX_GRID_CELLS = 4_000_000


# ===========================================================================
# Capture
# ===========================================================================
@dataclass
class PallasCapture:
    """One recorded ``pl.pallas_call`` launch, normalized."""

    kernel: Callable
    grid: Tuple[int, ...]
    in_specs: Tuple[Any, ...]
    out_specs: Tuple[Any, ...]
    out_shapes: Tuple[Any, ...]          # ShapeDtypeStruct per output
    scratch_shapes: Tuple[Any, ...]
    num_scalar_prefetch: int
    in_avals: Tuple[Any, ...] = ()       # ShapeDtypeStruct per operand


def _as_tuple(x) -> Tuple[Any, ...]:
    if x is None:
        return ()
    if isinstance(x, (list, tuple)):
        return tuple(x)
    return (x,)


def _normalize(kernel, kwargs: Dict[str, Any],
               operands: Sequence[Any]) -> PallasCapture:
    gs = kwargs.get("grid_spec")
    if gs is not None:
        grid = getattr(gs, "grid", ())
        in_specs = _as_tuple(getattr(gs, "in_specs", ()))
        out_specs = _as_tuple(getattr(gs, "out_specs", ()))
        npf = int(getattr(gs, "num_scalar_prefetch", 0) or 0)
        scratch = _as_tuple(getattr(gs, "scratch_shapes", ()))
    else:
        grid = kwargs.get("grid", ())
        in_specs = _as_tuple(kwargs.get("in_specs", ()))
        out_specs = _as_tuple(kwargs.get("out_specs", ()))
        npf = 0
        scratch = _as_tuple(kwargs.get("scratch_shapes", ()))
    if isinstance(grid, int):
        grid = (grid,)
    import jax
    avals = tuple(jax.ShapeDtypeStruct(x.shape, x.dtype) for x in operands)
    return PallasCapture(
        kernel=kernel, grid=tuple(int(g) for g in grid),
        in_specs=in_specs, out_specs=out_specs,
        out_shapes=_as_tuple(kwargs.get("out_shape")),
        scratch_shapes=scratch, num_scalar_prefetch=npf, in_avals=avals)


@contextlib.contextmanager
def capture_pallas_calls():
    """Spy on ``pl.pallas_call``; yields the list captures append to.

    All repo kernels call ``pl.pallas_call(...)`` through the module
    attribute, so swapping the attribute intercepts every launch. jit
    caches are cleared first — a cached trace would skip the python
    body and record nothing.
    """
    import jax
    from jax.experimental import pallas as pl

    captures: List[PallasCapture] = []
    real = pl.pallas_call

    def spy(kernel, *args, **kwargs):
        inner = real(kernel, *args, **kwargs)

        def launch(*operands):
            captures.append(_normalize(kernel, kwargs, operands))
            return inner(*operands)

        return launch

    pl.pallas_call = spy
    try:
        jax.clear_caches()
        yield captures
    finally:
        pl.pallas_call = real


# ===========================================================================
# Accumulation declaration (race exemption)
# ===========================================================================
def _unwrap_partial(fn) -> Tuple[Callable, Dict[str, Any]]:
    bound: Dict[str, Any] = {}
    while isinstance(fn, functools.partial):
        bound.update(fn.keywords or {})
        fn = fn.func
    return fn, bound


def _positional_params(fn, bound: Dict[str, Any]) -> List[str]:
    sig = inspect.signature(fn)
    kinds = (inspect.Parameter.POSITIONAL_ONLY,
             inspect.Parameter.POSITIONAL_OR_KEYWORD)
    return [p.name for p in sig.parameters.values()
            if p.kind in kinds and p.name not in bound]


def kernel_reads_output(cap: PallasCapture) -> bool:
    """Does the kernel body *read* any output ref (read-modify-write
    accumulation, the paged-attention pattern)? Conservative: source
    unavailable -> False."""
    fn, bound = _unwrap_partial(cap.kernel)
    try:
        params = _positional_params(fn, bound)
        tree = ast.parse(textwrap.dedent(inspect.getsource(fn)))
    except (OSError, TypeError, ValueError, SyntaxError):
        return False
    lo = cap.num_scalar_prefetch + len(cap.in_specs)
    out_names = set(params[lo:lo + len(cap.out_specs)])
    if not out_names:
        return False
    for node in ast.walk(tree):
        if (isinstance(node, ast.Subscript)
                and isinstance(node.ctx, ast.Load)
                and isinstance(node.value, ast.Name)
                and node.value.id in out_names):
            return True
    return False


def declares_accumulation(cap: PallasCapture) -> bool:
    return bool(cap.scratch_shapes) or kernel_reads_output(cap)


# ===========================================================================
# Geometry checks on one capture
# ===========================================================================
def _block_shape(spec, full_shape) -> Tuple[int, ...]:
    bs = getattr(spec, "block_shape", None)
    if bs is None:
        return tuple(full_shape)
    return tuple(full_shape[i] if b is None else int(b)
                 for i, b in enumerate(bs))


def _eval_index_map(spec, cap: PallasCapture, ncells: int,
                    coords: List[np.ndarray]) -> Optional[List[np.ndarray]]:
    """Vectorized block coordinates of ``spec`` over every grid cell."""
    imap = getattr(spec, "index_map", None)
    if imap is None:
        return None
    prefetch = [np.zeros(a.shape, dtype=a.dtype)
                for a in cap.in_avals[:cap.num_scalar_prefetch]]
    try:
        out = imap(*coords, *prefetch)
    except Exception:
        return None
    if not isinstance(out, tuple):
        out = (out,)
    return [np.broadcast_to(np.asarray(c), (ncells,)).astype(np.int64)
            for c in out]


def _in_vmem(obj) -> bool:
    """Does a BlockSpec or scratch shape live in VMEM? A BlockSpec with
    no memory space is pipelined through VMEM."""
    ms = getattr(obj, "memory_space", None)
    return ms is None or getattr(ms, "value", ms) == "vmem"


def check_capture(cap: PallasCapture, *, vmem_budget: int,
                  label: str) -> List[Finding]:
    findings: List[Finding] = []
    ncells = int(np.prod(cap.grid, dtype=np.int64)) if cap.grid else 1

    # -- coverage + write race ----------------------------------------------
    if cap.grid and ncells <= _MAX_GRID_CELLS and cap.out_specs:
        mesh = np.meshgrid(*[np.arange(g) for g in cap.grid],
                           indexing="ij")
        coords = [m.ravel() for m in mesh]
        accum = declares_accumulation(cap)
        for i, spec in enumerate(cap.out_specs):
            if i >= len(cap.out_shapes):
                break
            shape = tuple(cap.out_shapes[i].shape)
            block = _block_shape(spec, shape)
            needed = tuple(max(1, -(-d // b)) for d, b in zip(shape, block))
            bcoords = _eval_index_map(spec, cap, ncells, coords)
            if bcoords is None or len(bcoords) != len(needed):
                continue
            ids = np.ravel_multi_index(
                [np.clip(c, 0, n - 1) for c, n in zip(bcoords, needed)],
                needed)
            nunique = int(np.unique(ids).size)
            total = int(np.prod(needed, dtype=np.int64))
            if nunique < total:
                findings.append(Finding(
                    "kernel-grid-coverage", "error",
                    Location(symbol=f"{label}#out{i}"),
                    f"grid {cap.grid} reaches {nunique}/{total} blocks of "
                    f"output {i} (shape {shape}, block {block}) — uncovered "
                    f"blocks are uninitialized memory",
                    "extend the grid or fix the output index map"))
            if ncells > nunique and not accum:
                findings.append(Finding(
                    "kernel-write-race", "error",
                    Location(symbol=f"{label}#out{i}"),
                    f"{ncells} grid cells map onto {nunique} blocks of "
                    f"output {i} without declared accumulation (no VMEM "
                    f"scratch carry, no output-ref read) — later visits "
                    f"silently overwrite earlier ones",
                    "carry partials in a scratch buffer or read-modify-"
                    "write the output ref"))

    # -- VMEM budget ---------------------------------------------------------
    vmem = 0
    for i, spec in enumerate(cap.in_specs):
        aval = (cap.in_avals[cap.num_scalar_prefetch + i]
                if cap.num_scalar_prefetch + i < len(cap.in_avals) else None)
        if aval is None or not _in_vmem(spec):
            continue
        block = _block_shape(spec, tuple(aval.shape))
        vmem += int(np.prod(block, dtype=np.int64)) * np.dtype(aval.dtype).itemsize
    for i, spec in enumerate(cap.out_specs):
        if i >= len(cap.out_shapes):
            break
        sds = cap.out_shapes[i]
        block = _block_shape(spec, tuple(sds.shape))
        vmem += int(np.prod(block, dtype=np.int64)) * np.dtype(sds.dtype).itemsize
    vmem *= 2                                   # double-buffered pipeline
    for s in cap.scratch_shapes:
        shp = getattr(s, "shape", None)
        dt = getattr(s, "dtype", None)
        if shp is not None and dt is not None and _in_vmem(s):
            vmem += int(np.prod(shp, dtype=np.int64)) * np.dtype(dt).itemsize
    if vmem > vmem_budget:
        findings.append(Finding(
            "kernel-vmem-budget", "error", Location(symbol=label),
            f"double-buffered per-block footprint {vmem / 2**20:.2f} MiB "
            f"exceeds the {vmem_budget / 2**20:.0f} MiB per-core VMEM "
            f"budget",
            "shrink the block sizes in the tune grid"))
    return findings


# ===========================================================================
# One implementation at one shape
# ===========================================================================
def _vjp_wrapper(fn: Callable, ref: Callable,
                 kwargs: Dict[str, Any]) -> Callable:
    """Kernel-forward / reference-backward, exactly as
    ``dispatch._ref_backward`` builds it at dispatch time."""
    import jax

    f_fwd = functools.partial(fn, **kwargs)
    f_ref = functools.partial(ref, **kwargs)

    @jax.custom_vjp
    def wrapped(*arrays):
        return f_fwd(*arrays)

    def fwd(*arrays):
        return f_fwd(*arrays), arrays

    def bwd(arrays, ct):
        return jax.vjp(f_ref, *arrays)[1](ct)

    wrapped.defvjp(fwd, bwd)
    return wrapped


def _grad_error(wrapped: Callable, avals: Sequence[Any]) -> Optional[str]:
    """Abstract-trace the VJP wrt the float operands; None if it
    traces, else the failure message."""
    import jax
    import jax.numpy as jnp

    float_idx = [i for i, a in enumerate(avals)
                 if jnp.issubdtype(a.dtype, jnp.floating)]
    if not float_idx:
        return None

    def scalar(*fargs):
        full, it = [], iter(fargs)
        for i, a in enumerate(avals):
            full.append(next(it) if i in float_idx
                        else jnp.zeros(a.shape, a.dtype))
        out = wrapped(*full)
        tot = 0.0
        for leaf in jax.tree.leaves(out):
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                tot = tot + jnp.sum(leaf.astype(jnp.float32))
        return tot

    try:
        jax.eval_shape(jax.grad(scalar, argnums=tuple(range(len(float_idx)))),
                       *[avals[i] for i in float_idx])
        return None
    except Exception as e:                      # traced, and failed
        return f"{type(e).__name__}: {e}"


def validate_impl(op: str, impl: str, fn: Callable, avals: Sequence[Any],
                  kwargs: Dict[str, Any], *, ref: Optional[Callable] = None,
                  vmem_budget: int = 16 * 1024 * 1024,
                  label: Optional[str] = None) -> List[Finding]:
    """Every static check for one (impl, shape, tuning-params) point.

    ``avals`` are ShapeDtypeStructs (from ``jax.eval_shape`` of a case's
    ``make_args``); nothing is executed. ``ref`` is the op's xla
    reference — parity and borrowed-VJP checks are skipped when absent,
    but its absence is itself a ``kernel-missing-vjp`` finding unless
    the impl carries its own ``custom_vjp``.
    """
    import jax

    label = label or f"{op}/{impl}"
    findings: List[Finding] = []
    bound = functools.partial(fn, **kwargs)

    with capture_pallas_calls() as captures:
        try:
            out = jax.eval_shape(bound, *avals)
        except Exception as e:
            return [Finding(
                "kernel-trace-error", "error", Location(symbol=label),
                f"abstract trace failed at {kwargs or 'default params'}: "
                f"{type(e).__name__}: {e}",
                "the impl must trace at every tune-grid point")]
    for cap in captures:
        findings.extend(check_capture(cap, vmem_budget=vmem_budget,
                                      label=label))

    # -- parity vs reference -------------------------------------------------
    if ref is not None:
        try:
            ref_out = jax.eval_shape(functools.partial(ref, **kwargs), *avals)
        except Exception as e:
            ref_out = None
            findings.append(Finding(
                "kernel-trace-error", "error", Location(symbol=label),
                f"xla reference failed to trace: {type(e).__name__}: {e}"))
        if ref_out is not None:
            got = [(tuple(l.shape), str(l.dtype))
                   for l in jax.tree.leaves(out)]
            want = [(tuple(l.shape), str(l.dtype))
                    for l in jax.tree.leaves(ref_out)]
            if got != want:
                findings.append(Finding(
                    "kernel-dtype-parity", "error", Location(symbol=label),
                    f"impl outputs {got} but the xla reference produces "
                    f"{want}",
                    "match the reference signature exactly — dispatch "
                    "treats implementations as interchangeable"))

    # -- differentiability ---------------------------------------------------
    if isinstance(fn, jax.custom_vjp):
        err = _grad_error(bound, avals)
        if err:
            findings.append(Finding(
                "kernel-missing-vjp", "error", Location(symbol=label),
                f"impl declares a custom_vjp but it fails to trace: {err}"))
    elif ref is None:
        findings.append(Finding(
            "kernel-missing-vjp", "error", Location(symbol=label),
            "impl has no custom_vjp and no xla reference to borrow a "
            "backward pass from — it cannot reach the train path",
            "register an xla reference for the op, or defvjp the impl"))
    else:
        err = _grad_error(_vjp_wrapper(fn, ref, kwargs), avals)
        if err:
            findings.append(Finding(
                "kernel-missing-vjp", "error", Location(symbol=label),
                f"the reference-backward wrapper fails to trace: {err}",
                "the xla reference must be differentiable at the impl's "
                "signature"))
    return findings


# ===========================================================================
# Preset sweep + registered pass
# ===========================================================================
def validate_preset(tune_preset, cells=None, *,
                    vmem_budget: int = 16 * 1024 * 1024) -> List[Finding]:
    """Validate every non-xla impl over a tune preset's cases × grids."""
    import jax

    from repro.kernels.dispatch import implementations
    from repro.kernels.tune import cases_for_cell

    findings: List[Finding] = []
    seen = set()
    for arch, shape_name in (cells or tune_preset.cells):
        cfg = tune_preset.arch(arch)
        shape = tune_preset.shape(shape_name)
        for case in cases_for_cell(cfg, shape,
                                   bench_batch=tune_preset.bench_batch,
                                   page_sizes=tune_preset.paged_page_sizes):
            avals = jax.eval_shape(case.make_args)
            impls = implementations(case.op)
            ref = impls.get("xla")
            for impl in sorted(impls):
                if impl == "xla":
                    continue
                for params in tune_preset.grid(case.op, impl):
                    label = f"{case.op}/{impl}@{arch}/{shape_name}"
                    fs = validate_impl(
                        case.op, impl, impls[impl], avals,
                        {**case.kwargs, **dict(params)}, ref=ref,
                        vmem_budget=vmem_budget, label=label)
                    for f in fs:
                        key = (f.rule_id, f.location.symbol, f.message)
                        if key not in seen:
                            seen.add(key)
                            findings.append(f)
    return findings


@register_pass(
    "kernel_validator",
    rules=("kernel-grid-coverage", "kernel-write-race", "kernel-vmem-budget",
           "kernel-missing-vjp", "kernel-dtype-parity", "kernel-trace-error"),
    description="coverage/race/VMEM/VJP/parity checks on every registered "
                "non-xla kernel over the tune-grid shapes")
def run_pass(ctx: AnalysisContext) -> List[Finding]:
    from repro.kernels.tune import TUNE_PRESETS
    return validate_preset(TUNE_PRESETS[ctx.preset.tune_preset],
                           vmem_budget=ctx.preset.vmem_budget_bytes)
