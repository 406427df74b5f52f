"""Reduction of the serving engine's own spans and op scopes in a
profiler trace, beside ``devtrace``'s reduction of the harness's spans.

The engine (``repro.serve.engine``, ``repro.serve.paged``) wraps each
phase of a step in a host span named ``serve.<phase>``; the model names
its kernels and blocks with ``jax.named_scope`` (every ``dispatch`` op,
``mlp``, ``kv_write``). A TPU trace's op events carry no scope: the
compiled program's HLO text does, as each instruction's ``op_name``
(``op_names``). ``summarize`` returns ``devtrace.summarize`` with two
keys more:

* ``spans``: per ``serve.*`` name, over the spans that start inside the
  window, ``count``, host seconds (``host_s``) and seconds in which the
  device was idle (``idle_s``);
* ``scopes``: device self-seconds of the decode programs' ops by their
  outermost program scope, ``unscoped`` for ops under none.

JAX's persistent compilation cache leaves metadata out of its key, so
a program compiled before its scopes existed is served without them;
its ops then all read ``unscoped``.
"""
from __future__ import annotations

import re
from typing import Dict, List, Sequence

import devtrace

SPAN_PREFIX = "serve."
UNSCOPED = "unscoped"

_NAME = re.compile(r"%?([\w.\-]+)")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$", re.M)
_REF = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def program_scopes() -> frozenset:
    """The scope names the program gives: every kernel op of the
    dispatch registry, and the model's own blocks."""
    from repro.kernels.dispatch import KERNEL_OPS
    return frozenset(KERNEL_OPS) | {"mlp", "kv_write"}


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` path, from a compiled program's
    HLO text (``jax.stages.Compiled.as_text()``). An instruction that the
    compiler added without one (a convert of gathered pages, a copy)
    takes that of its first operand that has one: operands are defined
    before their users, so one pass in text order resolves chains."""
    out: Dict[str, str] = {}
    for name, rest in _INSTR.findall(hlo_text):
        m = _OP_NAME.search(rest)
        path = m.group(1) if m else next(
            (out[r] for r in _REF.findall(rest) if r in out), None)
        if path is not None:
            out[name] = path
    return out


def instruction(op: str) -> str:
    """``%fusion.7 = bf16[...] fusion(...)`` (or ``devtrace``'s short
    ``fusion.7 bf16[...]``) -> ``fusion.7``."""
    return _NAME.match(op).group(1)


def outermost(path: str, known: frozenset) -> str:
    """The first component of ``path`` that names a program scope."""
    return next((p for p in path.split("/") if p in known), UNSCOPED)


def spans(host: Sequence[Sequence], merged: List, w0: float,
          w1: float) -> Dict[str, Dict]:
    """Per ``serve.*`` span name: count, host and device-idle seconds of
    the spans starting in [w0, w1); ``merged`` is the device's disjoint
    busy cover."""
    starts = [a for a, _ in merged]
    out: Dict[str, Dict] = {}
    for a, b, name in host:
        if not name.startswith(SPAN_PREFIX) or not w0 <= a < w1:
            continue
        s = out.setdefault(name, {"count": 0, "host_s": 0.0, "idle_s": 0.0})
        s["count"] += 1
        s["host_s"] += (b - a) / 1e9
        s["idle_s"] += (b - a - devtrace.covered(merged, starts, a, b)) / 1e9
    return out


def scopes(device: Dict, w0: float, w1: float, names: Dict[str, str],
           known: frozenset) -> Dict[str, float]:
    """Device self-seconds of the ops of the decode programs whose
    midpoint lies in [w0, w1], by outermost program scope; ``names``
    maps the decode program's instructions to their ``op_name``."""
    runs = sorted((a, b) for a, b, n in device["modules"]
                  if n.startswith(devtrace.DECODE_MODULE + "(")
                  and w0 <= (a + b) / 2 <= w1)
    ops, i = [], 0
    for op in sorted(device["ops"]):
        while i < len(runs) and runs[i][1] < op[0]:
            i += 1
        if i < len(runs) and runs[i][0] <= op[0] and op[1] <= runs[i][1]:
            ops.append(op)
    return devtrace.self_times(
        [[a, b, outermost(names.get(instruction(n), ""), known)]
         for a, b, n in ops])


def summarize(trace: Dict, rec, step_names: Dict[str, str]) -> Dict:
    """``devtrace.summarize`` with ``spans`` and ``scopes`` added;
    ``step_names`` is ``op_names`` of the decode program."""
    out = devtrace.summarize(trace, rec)
    w0, w1 = devtrace._window(trace["host"])
    name = min(trace["devices"], key=lambda n: int(n.rsplit(":", 1)[1]))
    dev = trace["devices"][name]
    merged = devtrace.union(devtrace._clip(dev["ops"], w0, w1))
    out["spans"] = spans(trace["host"], merged, w0, w1)
    out["scopes"] = scopes(dev, w0, w1, step_names, program_scopes())
    return out
