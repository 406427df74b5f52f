"""Quantized-vs-reference serving parity: the accuracy-proxy harness.

Teacher-forced comparison of two :class:`~repro.models.model.
ModelRuntime`\\ s over the same prompts: both runtimes prefill the same
tokens and then decode the same forced continuation (the *reference*
runtime's greedy tokens), so every step compares logits computed at an
identical context — free-running divergence can never compound into the
measurement. The report carries the max abs logit deviation (the
accuracy-proxy objective the DSE's precision axis is scored on) and the
greedy-argmax agreement.

The acceptance contract is the deviation bound
(:data:`~repro.kernels.quant.QUANT_PARITY_TOL`): per-row symmetric int8
KV keeps logits within a small envelope of bf16, but near argmax *ties*
a sub-tolerance deviation can still flip the greedy token — that is
reported as ``token_match_frac``, not asserted, because it is a
property of the logit gap, not of the quantizer.
"""
from __future__ import annotations

from contextlib import nullcontext
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.quant import QUANT_PARITY_TOL
from repro.models import decode_step, decode_step_paged, prefill
from repro.models.model import (ModelRuntime, init_paged_cache, page_count,
                                write_prefill_pages,
                                write_prefill_pages_quant)


@dataclass(frozen=True)
class ParityReport:
    """Teacher-forced deviation of one runtime pair over a prompt set."""

    max_logit_dev: float       # max abs logit deviation over every step
    token_match_frac: float    # greedy-argmax agreement over every step
    n_tokens: int              # compared positions (prefill + decode)
    tol: float = QUANT_PARITY_TOL

    @property
    def within_tol(self) -> bool:
        return self.max_logit_dev <= self.tol

    def to_json(self) -> Dict[str, Any]:
        return {
            "max_logit_dev": round(float(self.max_logit_dev), 6),
            "token_match_frac": round(float(self.token_match_frac), 4),
            "n_tokens": int(self.n_tokens),
            "tol": float(self.tol),
            "within_tol": bool(self.within_tol),
        }


def _pad_prompts(prompts: Sequence[np.ndarray]
                 ) -> Tuple[np.ndarray, np.ndarray]:
    rows = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
    if not rows:
        raise ValueError("logit_parity needs at least one prompt")
    toks = np.zeros((len(rows), max(len(p) for p in rows)), np.int32)
    lengths = np.zeros((len(rows),), np.int32)
    for i, p in enumerate(rows):
        toks[i, : len(p)] = p
        lengths[i] = len(p)
    return toks, lengths


def _to_paged(cache: Dict[str, jax.Array], cfg: ModelConfig,
              rt: ModelRuntime, page_size: int, max_len: int):
    """Re-lay a prefilled contiguous cache into a page pool: row ``b``
    owns physical pages ``1 + b * npp ...`` (page 0 stays the null
    page), exactly the rows the paged engine would have scattered."""
    B, W = cache["k"].shape[1], cache["k"].shape[2]
    npp = page_count(W, page_size)
    pool = init_paged_cache(cfg, B, B * npp + 1, page_size, max_len,
                            rt.dtype, kv_dtype=rt.kv_dtype)
    pt = 1 + jnp.arange(B * npp, dtype=jnp.int32).reshape(B, npp)
    if "ks" in pool:
        kp, vp, ks, vs = write_prefill_pages_quant(
            pool["kp"], pool["vp"], pool["ks"], pool["vs"], cache["k"],
            cache["v"], cache["ks"], cache["vs"], pt, page_size=page_size)
        pool.update(ks=ks, vs=vs)
    else:
        kp, vp = write_prefill_pages(pool["kp"], pool["vp"], cache["k"],
                                     cache["v"], pt, page_size=page_size)
    pool.update(kp=kp, vp=vp, pt=pt, pos=cache["pos"])
    pool.update({n: cache[n] for n in ("conv", "ssm") if n in cache})
    return pool


def teacher_forced_logits(params, cfg: ModelConfig, rt: ModelRuntime,
                          prompts: Sequence[np.ndarray], *,
                          steps: int, max_len: int,
                          forced: Optional[Sequence[np.ndarray]] = None,
                          page_size: int = 0,
                          ctx: Callable[[], Any] = nullcontext
                          ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """Prefill ``prompts`` then decode ``steps`` tokens, feeding
    ``forced[i]`` at step ``i`` (default: this runtime's own greedy
    argmax). Returns the ``steps + 1`` f32 logit arrays (prefill first)
    and the tokens fed. ``page_size`` > 0 decodes through the page pool
    (``decode_step_paged``, the paged engine's step); ``ctx`` wraps
    every jitted call (a mesh + axis-rules context for sharded runs)."""
    toks, lengths = _pad_prompts(prompts)
    with ctx():
        cache, logits = jax.jit(lambda pr, t, ln: prefill(
            pr, cfg, {"tokens": t}, max_len, rt, lengths=ln))(
            params, jnp.asarray(toks), jnp.asarray(lengths))
        if page_size:
            W = cache["k"].shape[2]
            cache = jax.jit(lambda c: _to_paged(c, cfg, rt, page_size,
                                                max_len))(cache)
            step = jax.jit(lambda pr, c, t: decode_step_paged(
                pr, cfg, c, t, rt, page_size=page_size, window=W))
        else:
            step = jax.jit(lambda pr, c, t: decode_step(pr, cfg, c, t, rt))
        out, fed = [np.asarray(logits, np.float32)], []
        for i in range(steps):
            tok = (np.asarray(forced[i], np.int32) if forced is not None
                   else out[-1].argmax(-1).astype(np.int32))
            fed.append(tok)
            cache, logits = step(params, cache, jnp.asarray(tok))
            out.append(np.asarray(logits, np.float32))
    return out, fed


def compare_logits(ref: Sequence[np.ndarray], test: Sequence[np.ndarray],
                   tol: float = QUANT_PARITY_TOL) -> ParityReport:
    """Max abs deviation + argmax agreement of two logit sequences. A
    NaN or infinite logit on either side makes the deviation non-finite
    (``np.max`` propagates NaN), so the report is never within tol."""
    dev = float(np.max([np.max(np.abs(r - t)) for r, t in zip(ref, test)]))
    match = sum(int(np.sum(r.argmax(-1) == t.argmax(-1)))
                for r, t in zip(ref, test))
    n = sum(r.shape[0] for r in ref)
    return ParityReport(max_logit_dev=dev,
                        token_match_frac=match / max(n, 1), n_tokens=n,
                        tol=tol)


def logit_parity(params, cfg: ModelConfig,
                 prompts: Sequence[np.ndarray], *,
                 rt_ref: Optional[ModelRuntime] = None,
                 rt_test: Optional[ModelRuntime] = None,
                 max_new_tokens: int = 8,
                 max_len: Optional[int] = None,
                 page_size: int = 0) -> ParityReport:
    """Measure ``rt_test``'s logit deviation from ``rt_ref``.

    Defaults compare the bf16 KV reference against the int8-quantized
    cache (``ModelRuntime(kv_dtype='int8')``) — the serving benchmark's
    accuracy sidebar. Both runtimes see identical tokens at every step:
    the forced continuation is always the *reference* greedy argmax.
    ``page_size`` > 0 decodes both through the paged engine's step.
    """
    rt_ref = rt_ref if rt_ref is not None else ModelRuntime()
    rt_test = rt_test if rt_test is not None \
        else ModelRuntime(kv_dtype="int8")
    if max_len is None:
        max_len = _pad_prompts(prompts)[0].shape[1] + max_new_tokens
    kw = dict(steps=max_new_tokens, max_len=max_len, page_size=page_size)
    ref, fed = teacher_forced_logits(params, cfg, rt_ref, prompts, **kw)
    test, _ = teacher_forced_logits(params, cfg, rt_test, prompts,
                                    forced=fed, **kw)
    return compare_logits(ref, test)
