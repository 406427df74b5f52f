"""The expert layer's scopes in a decode program's compiled HLO, for
``enginetrace``'s reduction.

The model runs the held experts' grouped GEMMs under the ``moe_gemm``
scope and routing under ``moe_route``. The TPU compiler rewrites each
``lax.ragged_dot`` into instructions named ``ragged-dot-*`` whose
``op_name`` is that name alone, so the scope they ran under is lost:
``names`` puts them back under ``moe_gemm``. ``known`` is
``enginetrace.program_scopes`` with ``moe_route``.
"""
from __future__ import annotations

from typing import Dict

import enginetrace

GEMM = "moe_gemm"
ROUTE = "moe_route"


def names(step_names: Dict[str, str]) -> Dict[str, str]:
    """``enginetrace.op_names`` of a decode program, ragged dots under
    ``moe_gemm``."""
    return {k: f"{GEMM}/{v}" if v.startswith("ragged-dot") else v
            for k, v in step_names.items()}


def known() -> frozenset:
    return enginetrace.program_scopes() | {ROUTE}
