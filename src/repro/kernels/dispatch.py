"""Kernel dispatch: the live seam between the models and the kernels.

Each compute hot spot is a *registered op* with pluggable
implementations — ``xla`` (the pure-jnp production path) and ``pallas``
(the TPU kernel, interpret mode off-TPU). A :class:`KernelPolicy` names
the implementation per op (plus optional tuning parameters such as
block sizes), and every model path (``forward`` / ``prefill`` /
``decode_step`` / the ServeEngine / the train loop) routes its hot
spots through :func:`dispatch`, so one runtime knob flips the whole
stack between XLA and kernels — this replaces the dead
``ModelRuntime.use_kernels`` bool that no model path ever read.

Registered ops:

    ==================  =============================  ====================
    op                  call-site                      pallas kernel
    ==================  =============================  ====================
    prefill_attention   attn_block (train/prefill)     flash_attention
    decode_attention    _attn_decode_one (decode)      decode_attention_splitkv
    paged_decode_attention  _attn_decode_one_paged     paged_decode_attention_splitkv
    rmsnorm             layers.rmsnorm / norm()        rmsnorm_pallas
    ssd_scan            ssm_block (Mamba-2 SSD)        ssd_scan_pallas
    moe_gemm            moe_ffn dropless expert GEMM   grouped_gemm_padded
                                                       (``ragged``: lax.ragged_dot)
    ==================  =============================  ====================

Gradients: the Pallas kernels here are forward-only, so every non-xla
implementation is wrapped in a ``jax.custom_vjp`` whose backward pass is
the VJP of the op's registered ``xla`` implementation (kernel forward,
reference backward). That is what lets ``use_kernels`` reach the *train*
path, not just inference.

The dispatch table (:func:`implementations`) is deliberately a live,
mutable mapping: the autotuner enumerates it to sweep implementations,
and tests monkeypatch it with counting wrappers to prove a policy's
path is actually taken.
"""
from __future__ import annotations

import functools
import os
from dataclasses import dataclass, replace
from typing import Any, Callable, Dict, Optional, Tuple

import jax

# ===========================================================================
# Policy
# ===========================================================================
#: Op names, in dispatch-table order.
KERNEL_OPS = ("prefill_attention", "decode_attention",
              "paged_decode_attention", "rmsnorm", "ssd_scan", "moe_gemm",
              "quant_matmul", "quant_decode_attention",
              "quant_paged_decode_attention")

#: One default eps for every RMSNorm implementation. Historically
#: ``models.layers.rmsnorm`` and ``kernels.rmsnorm.rmsnorm_pallas`` each
#: hardcoded 1e-6 independently; the call-site value now threads through
#: dispatch into whichever implementation runs.
RMSNORM_EPS = 1e-6

ParamsTuple = Tuple[Tuple[str, Tuple[Tuple[str, Any], ...]], ...]


@dataclass(frozen=True)
class KernelPolicy:
    """Per-op implementation choice + optional tuning parameters.

    Frozen/hashable (params are nested tuples) so it can live inside the
    frozen :class:`~repro.models.model.ModelRuntime` and key jit caches.
    ``params`` entries are merged over the call-site keyword arguments,
    so a calibrated policy carries its winning block sizes with it.
    """

    prefill_attention: str = "xla"
    decode_attention: str = "xla"
    paged_decode_attention: str = "xla"
    rmsnorm: str = "xla"
    ssd_scan: str = "xla"
    moe_gemm: str = "xla"
    quant_matmul: str = "xla"
    quant_decode_attention: str = "xla"
    quant_paged_decode_attention: str = "xla"
    params: ParamsTuple = ()

    # -- construction --------------------------------------------------------
    @classmethod
    def xla(cls) -> "KernelPolicy":
        return cls()

    @classmethod
    def pallas(cls) -> "KernelPolicy":
        return cls(**{op: "pallas" for op in KERNEL_OPS})

    @classmethod
    def from_flag(cls, use_kernels: bool) -> "KernelPolicy":
        """The ``ModelRuntime.use_kernels`` bool, mapped onto a policy."""
        return cls.pallas() if use_kernels else cls.xla()

    @classmethod
    def from_calibration(cls, calib: Dict[str, Any]) -> "KernelPolicy":
        """Build a policy from a ``calibration.json`` payload (the
        ``policy`` block written by ``repro.kernels.tune``): winning
        implementation + winning tuning params per op."""
        choices = calib.get("policy", {})
        kw = {op: choices.get(op, {}).get("impl", "xla")
              for op in KERNEL_OPS}
        params = tuple(
            (op, tuple(sorted(choices[op].get("params", {}).items())))
            for op in sorted(KERNEL_OPS)
            if choices.get(op, {}).get("params"))
        return cls(params=params, **kw)

    # -- queries -------------------------------------------------------------
    def impl_for(self, op: str) -> str:
        if op not in KERNEL_OPS:
            raise KeyError(f"unknown kernel op {op!r}; "
                           f"registered: {KERNEL_OPS}")
        return getattr(self, op)

    def params_for(self, op: str) -> Dict[str, Any]:
        for name, kv in self.params:
            if name == op:
                return dict(kv)
        return {}

    def with_params(self, op: str, **kw: Any) -> "KernelPolicy":
        merged = {**self.params_for(op), **kw}
        by_op = dict(self.params)
        by_op[op] = tuple(sorted(merged.items()))
        # canonical (op-sorted) order: policies that carry the same
        # params compare/hash equal regardless of construction order,
        # so they never trigger spurious retraces when keying jit caches
        return replace(self, params=tuple(sorted(by_op.items())))

    def describe(self) -> str:
        return " ".join(f"{op}={self.impl_for(op)}" for op in KERNEL_OPS)


XLA_POLICY = KernelPolicy.xla()
PALLAS_POLICY = KernelPolicy.pallas()


def resolve_policy(policy: Optional[KernelPolicy]) -> KernelPolicy:
    return XLA_POLICY if policy is None else policy


# ===========================================================================
# Dispatch table
# ===========================================================================
_TABLE: Dict[str, Dict[str, Callable]] = {op: {} for op in KERNEL_OPS}


class KernelValidationError(ValueError):
    """An implementation failed the static kernel validator at
    registration time; the message carries the findings verbatim."""


def _validate_on_register() -> bool:
    """Opt-out flag, read at registration time so tests can flip it."""
    return os.environ.get("REPRO_VALIDATE_KERNELS", "1") != "0"


def register_impl(op: str, impl: str,
                  example: Optional[Callable] = None,
                  validate: Optional[bool] = None,
                  ) -> Callable[[Callable], Callable]:
    """Decorator: register ``fn`` as implementation ``impl`` of ``op``.

    ``example`` opts the implementation into registration-time static
    validation (``repro.analysis.kernel_validator``): a no-arg callable
    returning ``(avals, kwargs)`` — operand ShapeDtypeStructs plus
    call-site kwargs — at which the impl is abstract-traced and its
    grid/BlockSpec geometry checked. Error findings reject the
    registration with a :class:`KernelValidationError` naming the rule,
    instead of the op corrupting output at runtime. ``validate=False``
    (or ``REPRO_VALIDATE_KERNELS=0``) opts out, for tests that seed
    deliberately-broken impls.
    """
    if op not in _TABLE:
        raise KeyError(f"unknown kernel op {op!r}; registered: {KERNEL_OPS}")

    def deco(fn: Callable) -> Callable:
        run = _validate_on_register() if validate is None else validate
        if run and example is not None and impl != "xla":
            from repro.analysis.kernel_validator import validate_impl
            avals, kwargs = example()
            findings = validate_impl(op, impl, fn, avals, dict(kwargs),
                                     ref=_TABLE[op].get("xla"),
                                     label=f"{op}/{impl}@register")
            errors = [f for f in findings if f.severity == "error"]
            if errors:
                raise KernelValidationError(
                    f"refusing to register {op}/{impl}: "
                    + "; ".join(f.describe() for f in errors))
        _TABLE[op][impl] = fn
        return fn

    return deco


def implementations(op: str) -> Dict[str, Callable]:
    """The live implementation mapping for one op.

    Mutable by design: the autotuner enumerates it, tests monkeypatch it
    (e.g. wrap an entry with a counter to prove the path is taken).
    """
    if op not in _TABLE:
        raise KeyError(f"unknown kernel op {op!r}; registered: {KERNEL_OPS}")
    return _TABLE[op]


def _ref_backward(op: str, fn: Callable, kwargs: Dict[str, Any]) -> Callable:
    """Wrap a forward-only implementation with the xla impl's VJP.

    fwd = the kernel (residuals: the primal inputs); bwd = ``jax.vjp``
    of the registered ``xla`` implementation at the same kwargs — the
    standard kernel-forward / reference-backward pairing that makes the
    pallas path differentiable for the train loop.
    """
    ref = _TABLE[op]["xla"]
    f_fwd = functools.partial(fn, **kwargs)
    f_ref = functools.partial(ref, **kwargs)

    @jax.custom_vjp
    def wrapped(*arrays):
        return f_fwd(*arrays)

    def fwd(*arrays):
        return f_fwd(*arrays), arrays

    def bwd(arrays, ct):
        _, vjp = jax.vjp(f_ref, *arrays)
        return vjp(ct)

    wrapped.defvjp(fwd, bwd)
    return wrapped


def dispatch(op: str, policy: Optional[KernelPolicy], *arrays: Any,
             **kwargs: Any) -> Any:
    """Route one hot-spot call through the policy's implementation.

    ``arrays`` are the traced operands; ``kwargs`` are call-site
    parameters (eps, causal, chunk, ...) that the policy's per-op tuning
    params override. Implementations accept ``**_`` so parameters
    meaningful only to the other implementation are ignored rather than
    rejected.
    """
    pol = resolve_policy(policy)
    impl = pol.impl_for(op)
    table = implementations(op)
    if impl not in table:
        raise KeyError(
            f"kernel op {op!r} has no implementation {impl!r}; "
            f"registered: {sorted(table)}")
    merged = {**kwargs, **pol.params_for(op)}
    fn = table[impl]
    # the op's name scopes every instruction it lowers to (``op_name``
    # metadata in the compiled HLO), so a device trace names the kernel
    with jax.named_scope(op):
        if impl != "xla":
            return _ref_backward(op, fn, merged)(*arrays)
        return fn(*arrays, **merged)


# ===========================================================================
# Implementations
# ===========================================================================
# XLA paths lazily import the model modules (which themselves import this
# module at top level) — the import cycle never materializes because the
# body only runs at trace time.

@register_impl("prefill_attention", "xla")
def _prefill_attention_xla(q, k, v, *, causal: bool = True, window: int = 0,
                           chunk: int = 512, **_):
    from repro.models.attention import chunked_attention
    return chunked_attention(q, k, v, causal=causal, window=window,
                             chunk=chunk)


@register_impl("prefill_attention", "pallas")
def _prefill_attention_pallas(q, k, v, *, causal: bool = True,
                              window: int = 0, block_q: int = 128,
                              block_k: int = 512, **_):
    from repro.kernels.ops import flash_attention
    return flash_attention(q, k, v, causal=causal, window=window,
                           block_q=block_q, block_k=block_k)


@register_impl("decode_attention", "xla")
def _decode_attention_xla(q, k_cache, v_cache, kv_mask, **_):
    from repro.models.attention import decode_attention
    return decode_attention(q, k_cache, v_cache, kv_mask)


@register_impl("decode_attention", "pallas")
def _decode_attention_pallas(q, k_cache, v_cache, kv_mask, *,
                             block_k: int = 512, **_):
    from repro.kernels.ops import decode_attention
    return decode_attention(q, k_cache, v_cache, kv_mask, block_k=block_k)


@register_impl("paged_decode_attention", "xla")
def _paged_decode_attention_xla(q, k_pages, v_pages, page_table, kv_mask,
                                **_):
    from repro.models.attention import paged_decode_attention
    return paged_decode_attention(q, k_pages, v_pages, page_table, kv_mask)


@register_impl("paged_decode_attention", "pallas")
def _paged_decode_attention_pallas(q, k_pages, v_pages, page_table, kv_mask,
                                   *, pages_per_block: Optional[int] = None,
                                   **_):
    from repro.kernels.ops import paged_decode_attention
    return paged_decode_attention(q, k_pages, v_pages, page_table, kv_mask,
                                  pages_per_block=pages_per_block)


@register_impl("rmsnorm", "xla")
def _rmsnorm_xla(x, scale, *, eps: float = RMSNORM_EPS, **_):
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


@register_impl("rmsnorm", "pallas")
def _rmsnorm_pallas(x, scale, *, eps: float = RMSNORM_EPS,
                    block_rows: int = 256, **_):
    from repro.kernels.ops import rmsnorm
    return rmsnorm(x, scale, eps=eps, block_rows=block_rows)


@register_impl("ssd_scan", "xla")
def _ssd_scan_xla(x, dt, A, B, C, *, chunk: int = 128, **_):
    from repro.models.ssm import ssd_chunked
    return ssd_chunked(x, dt, A, B, C, chunk)


@register_impl("ssd_scan", "pallas")
def _ssd_scan_pallas(x, dt, A, B, C, *, chunk: int = 128, **_):
    from repro.kernels.ops import ssd_scan
    return ssd_scan(x, dt, A, B, C, chunk=chunk)


@register_impl("moe_gemm", "xla")
def _moe_gemm_xla(x, w, expert_of_row, *, n_experts: int, **_):
    """Gather-based per-row expert GEMM (reference semantics)."""
    import jax.numpy as jnp
    del n_experts
    return jnp.einsum("td,tdf->tf", x, w[expert_of_row])


@register_impl("moe_gemm", "ragged")
def _moe_gemm_ragged(x, w, expert_of_row, *, n_experts: int,
                     sorted_rows: bool = False, **_):
    """Grouped GEMM by ``jax.lax.ragged_dot``: each expert's weights
    meet only its own rows. ``sorted_rows`` promises rows already
    grouped by expert in ascending order; rows whose expert is
    ``n_experts`` or more then come last, belong to no group and come
    out zero. Unsorted rows are sorted here and put back."""
    import jax.numpy as jnp
    if not sorted_rows:
        order = jnp.argsort(expert_of_row, stable=True)
        y = _moe_gemm_ragged(x[order], w, expert_of_row[order],
                             n_experts=n_experts, sorted_rows=True)
        return y[jnp.argsort(order)]
    sizes = jnp.bincount(expert_of_row, length=n_experts)
    return jax.lax.ragged_dot(x, w, sizes.astype(jnp.int32))


@register_impl("moe_gemm", "pallas")
def _moe_gemm_pallas(x, w, expert_of_row, *, n_experts: int,
                     block_m: int = 128, block_f: int = 512, **_):
    from repro.kernels.ops import moe_grouped_matmul
    return moe_grouped_matmul(x, w, expert_of_row, n_experts=n_experts,
                              block_m=block_m, block_f=block_f)


# --- quantized ops (int8 weights / int8 KV + float scale side-bands) -------

def _quant_matmul_example():
    import jax.numpy as jnp
    s = jax.ShapeDtypeStruct
    return ((s((128, 64), jnp.float32), s((64, 256), jnp.int8),
             s((256,), jnp.float32)), {})


def _quant_decode_example():
    import jax.numpy as jnp
    s = jax.ShapeDtypeStruct
    B, Hq, Hkv, D, W = 2, 4, 2, 64, 256
    return ((s((B, Hq, D), jnp.float32),
             s((B, W, Hkv, D), jnp.int8), s((B, W, Hkv, D), jnp.int8),
             s((B, W, Hkv), jnp.bfloat16), s((B, W, Hkv), jnp.bfloat16),
             s((B, W), jnp.bool_)), {})


def _quant_paged_decode_example():
    import jax.numpy as jnp
    s = jax.ShapeDtypeStruct
    B, Hq, Hkv, D, P, ps, NP = 2, 4, 2, 64, 16, 8, 4
    return ((s((B, Hq, D), jnp.float32),
             s((P, ps, Hkv, D), jnp.int8), s((P, ps, Hkv, D), jnp.int8),
             s((P, ps, Hkv), jnp.bfloat16), s((P, ps, Hkv), jnp.bfloat16),
             s((B, NP), jnp.int32), s((B, NP * ps), jnp.bool_)), {})


@register_impl("quant_matmul", "xla")
def _quant_matmul_xla(x, w_q, scale, **_):
    from repro.kernels.quant import quant_matmul_xla
    return quant_matmul_xla(x, w_q, scale)


@register_impl("quant_matmul", "pallas", example=_quant_matmul_example)
def _quant_matmul_pallas(x, w_q, scale, *, block_t: int = 128,
                         block_n: int = 256, **_):
    from repro.kernels.ops import quant_matmul
    return quant_matmul(x, w_q, scale, block_t=block_t, block_n=block_n)


@register_impl("quant_decode_attention", "xla")
def _quant_decode_attention_xla(q, k_q, v_q, k_scale, v_scale, kv_mask, **_):
    from repro.kernels.quant import quant_decode_attention_xla
    return quant_decode_attention_xla(q, k_q, v_q, k_scale, v_scale, kv_mask)


@register_impl("quant_decode_attention", "pallas",
               example=_quant_decode_example)
def _quant_decode_attention_pallas(q, k_q, v_q, k_scale, v_scale, kv_mask,
                                   *, block_k: int = 512, **_):
    from repro.kernels.ops import quant_decode_attention
    return quant_decode_attention(q, k_q, v_q, k_scale, v_scale, kv_mask,
                                  block_k=block_k)


@register_impl("quant_paged_decode_attention", "xla")
def _quant_paged_decode_attention_xla(q, k_pages, v_pages, k_scales,
                                      v_scales, page_table, kv_mask, **_):
    from repro.kernels.quant import quant_paged_decode_attention_xla
    return quant_paged_decode_attention_xla(q, k_pages, v_pages, k_scales,
                                            v_scales, page_table, kv_mask)


@register_impl("quant_paged_decode_attention", "pallas",
               example=_quant_paged_decode_example)
def _quant_paged_decode_attention_pallas(q, k_pages, v_pages, k_scales,
                                         v_scales, page_table, kv_mask, *,
                                         pages_per_block: int = 1, **_):
    from repro.kernels.ops import quant_paged_decode_attention
    return quant_paged_decode_attention(q, k_pages, v_pages, k_scales,
                                        v_scales, page_table, kv_mask,
                                        pages_per_block=pages_per_block)
